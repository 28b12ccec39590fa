"""Device time of the all-gather operations (the compiled step's
instructions that hold an all-gather) per step, per chip, in ms."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["steps"] or tr["gather_s_per_chip"] <= 0:
        return None
    return 1e3 * tr["gather_s_per_chip"] / tr["steps"]
