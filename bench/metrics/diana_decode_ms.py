"""Device self time of the decode of every worker's payload and its
application (scope ``diana.decode_sum_apply``), per step, per chip, in ms.

Read from the traced window's reduction by the program's named scopes
(``bench/scopes.py::layer_metrics``); nothing in an untraced run."""

from bench import scopes


def read(ctx):
    return scopes.layer_metrics(ctx["scopes"])["diana_decode_ms"] if ctx["scopes"] else None
