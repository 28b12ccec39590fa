"""Largest ``memory_stats()["peak_bytes_in_use"]`` over the cell's devices
after the window, in GiB."""


def read(ctx):
    if not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 2**30
