"""The DIANA kernels' share of the HBM roofline, in %: the bytes encode and
decode_sum_apply must move in a step (``bench/flops.py``, by shape), over
the device time per step of DIANA's kernels alone (the model's own kernels,
such as Mamba-2's SSD chunk scan, are not counted; ``diana_kernel_ms``) and
the chip's HBM bandwidth."""

from bench.flops import diana_kernel_bytes


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if not tr or not peaks or not tr["steps"] or tr["kernel_s_per_chip"]["diana"] <= 0:
        return None
    m = ctx["model"]
    need = diana_kernel_bytes(ctx["param_count"], ctx["workers"], m["comp_block"])["total"]
    seconds = tr["kernel_s_per_chip"]["diana"] / tr["steps"]
    return 100.0 * need / seconds / peaks["hbm_bytes_per_s"]
