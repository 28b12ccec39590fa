"""Device time of the Mosaic kernels (the compiled step's
``tpu_custom_call`` instructions: the model has none, so each is DIANA's)
per step, per chip, in ms."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["steps"] or tr["kernel_s_per_chip"] <= 0:
        return None
    return 1e3 * tr["kernel_s_per_chip"] / tr["steps"]
