"""Tokens of all workers in the steps completed in the window, over the
window's seconds (host clock)."""


def read(ctx):
    return ctx["steps"] * ctx["tokens_per_step"] / ctx["window_s"]
