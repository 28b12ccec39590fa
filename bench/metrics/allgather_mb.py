"""Megabytes (1e6 bytes) one chip receives per step by the all-gathers
under ``diana.allgather``, from their shapes in the compiled step (its own
row left out); only a run with more than one worker gathers.

Read from the traced window's reduction by the program's named scopes
(``bench/scopes.py::layer_metrics``); nothing in an untraced run."""

from bench import scopes


def read(ctx):
    return scopes.layer_metrics(ctx["scopes"])["allgather_mb"] if ctx["scopes"] else None
