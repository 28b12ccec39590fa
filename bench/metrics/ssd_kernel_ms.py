"""Device time of Mamba-2's SSD chunk-scan kernels (the compiled step's
``tpu_custom_call`` instructions named ``ssd_chunk_scan*``: the forward, its
recompute and the backward) per step, per chip, in ms."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["steps"] or tr["kernel_s_per_chip"]["ssd"] <= 0:
        return None
    return 1e3 * tr["kernel_s_per_chip"]["ssd"] / tr["steps"]
