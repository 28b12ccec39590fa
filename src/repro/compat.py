"""The repo's one spelling of ``shard_map``.

Call sites use the keyword style of ``jax.shard_map``:

    shard_map(f, mesh=mesh, in_specs=..., out_specs=...,
              axis_names={...}, check_vma=False)

with ``axis_names=None`` meaning "manualize every mesh axis".
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "supports_nested_manual"]


def supports_nested_manual() -> bool:
    """Whether this jax/XLA can nest a shard_map that completes the
    manualization inside an already partial-manual body (DESIGN.md §6).
    True on the installed jax; the callers' fallbacks are kept until they are
    removed with their tests."""
    return True


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, check_vma=False):
    """``jax.shard_map``; ``axis_names`` — the mesh axes the body manualizes
    (``None`` = all of them)."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma, **kwargs)
