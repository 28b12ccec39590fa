"""Device time of DIANA's Mosaic kernels (the compiled step's
``tpu_custom_call`` instructions named after DIANA's encode and decode
wrappers, ``bench/trace.py::KERNEL_FAMILIES``; the model's own kernels, such
as Mamba-2's SSD chunk scan, are not counted) per step, per chip, in ms."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["steps"] or tr["kernel_s_per_chip"]["diana"] <= 0:
        return None
    return 1e3 * tr["kernel_s_per_chip"]["diana"] / tr["steps"]
