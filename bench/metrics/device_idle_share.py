"""1 - (union of the intervals in which an operation ran on the device /
the traced window), averaged over the chips, in %."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
