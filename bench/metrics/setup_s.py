"""Seconds from process start to the first timed step: imports, device
start, weights, optimizer state, compile (or its load from the cache), the
pool of batches and the proof steps."""


def read(ctx):
    return ctx["setup_s"]
