"""Time per step, per chip, in ms, in which DIANA's all-gather is in flight
and the device runs no other work: the exchange's exposed part.

Read from the traced window's reduction by the program's named scopes
(``bench/scopes.py::layer_metrics``); nothing in an untraced run."""

from bench import scopes


def read(ctx):
    return scopes.layer_metrics(ctx["scopes"])["allgather_exposed_ms"] if ctx["scopes"] else None
