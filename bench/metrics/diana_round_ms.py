"""Device self time of the whole DIANA round (the ``diana.*`` scopes:
flatten, encode, exchange, decode, memory, unflatten), per step, per chip,
in ms.

Read from the traced window's reduction by the program's named scopes
(``bench/scopes.py::layer_metrics``); nothing in an untraced run."""

from bench import scopes


def read(ctx):
    return scopes.layer_metrics(ctx["scopes"])["diana_round_ms"] if ctx["scopes"] else None
