"""Device self time of the model's backward pass and of the forward it
recomputes (the operations under the ``model.*`` scopes inside a transpose),
per step, per chip, in ms.

Read from the traced window's reduction by the program's named scopes
(``bench/scopes.py::layer_metrics``); nothing in an untraced run."""

from bench import scopes


def read(ctx):
    return scopes.layer_metrics(ctx["scopes"])["backward_ms"] if ctx["scopes"] else None
