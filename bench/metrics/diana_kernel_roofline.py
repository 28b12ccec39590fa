"""The DIANA kernels' share of the HBM roofline, in %: the bytes encode and
decode_sum_apply must move in a step (``bench/flops.py``, by shape), over
their device time per step and the chip's HBM bandwidth."""

from bench.flops import diana_kernel_bytes


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if not tr or not peaks or not tr["steps"] or tr["kernel_s_per_chip"] <= 0:
        return None
    m = ctx["model"]
    need = diana_kernel_bytes(ctx["param_count"], ctx["workers"], m["comp_block"])["total"]
    seconds = tr["kernel_s_per_chip"] / tr["steps"]
    return 100.0 * need / seconds / peaks["hbm_bytes_per_s"]
