"""Dense (identity-operator) payload kernels.

The identity "compressor" ships raw f32 values, so there is nothing to
decode — but the server still folds ``n`` worker payloads into one mean, and
on the bucketed path that reduction is the whole server tail.  The kernels
here accumulate the worker sum in place over the sequential TPU grid (one
``(d,)`` stripe of VMEM instead of an ``(n, d)`` HBM temporary) and the
``_mean`` variant fuses the divide, mirroring the accumulate-then-epilogue
pattern of :mod:`repro.kernels.unpack_reduce`.

``dense_copy`` is the compress-side counterpart (a straight VMEM pass-through)
so the identity operator exercises the same kernel-capability plumbing as the
real compressors — the linter (``tools/check_kernels.py``) can then assert
the full registry matrix without special-casing identity.

Shapes are exact and validated bitwise against
:func:`repro.kernels.ref.ref_dense_decode_sum` under ``interpret=True``;
like the sparse kernels these are interpret-contract only and ``use_kernel``
auto resolves to off for identity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["dense_copy", "dense_decode_sum", "dense_decode_sum_mean"]


def _copy_kernel(x_ref, out_ref):
    out_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dense_copy(x: jax.Array, *, interpret: bool = True) -> jax.Array:
    """x (d,) f32 -> (d,) f32 (wire payload pass-through)."""
    d = x.shape[0]
    return pl.pallas_call(
        _copy_kernel,
        name="dense_copy",
        grid=(1,),
        in_specs=[pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((d,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32))


def _accumulate(i, dense, out_ref):
    # Init with the first worker's row (the fallback recurrence starts from
    # ``values[0]``, and zeros + (-0.0) would flip signed zeros).
    @pl.when(i == 0)
    def _init():
        out_ref[...] = dense

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += dense


def _sum_kernel(val_ref, out_ref):
    _accumulate(pl.program_id(0), val_ref[0], out_ref)


def _mean_kernel(val_ref, out_ref, *, n):
    _sum_kernel(val_ref, out_ref)

    @pl.when(pl.program_id(0) == n - 1)
    def _mean():
        out_ref[...] = out_ref[...] / jnp.float32(n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dense_decode_sum(values: jax.Array, *, interpret: bool = True) -> jax.Array:
    """values (n, d) f32 -> (d,) f32 accumulated worker sum."""
    n, d = values.shape
    return pl.pallas_call(
        _sum_kernel,
        name="dense_decode_sum",
        grid=(n,),
        in_specs=[pl.BlockSpec((1, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((d,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        interpret=interpret,
    )(values.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def dense_decode_sum_mean(
    values: jax.Array, *, interpret: bool = True
) -> jax.Array:
    """Fused sum + divide: values (n, d) f32 -> (d,) mean over workers."""
    n, d = values.shape
    return pl.pallas_call(
        functools.partial(_mean_kernel, n=n),
        name="dense_decode_sum_mean",
        grid=(n,),
        in_specs=[pl.BlockSpec((1, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((d,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        interpret=interpret,
    )(values.astype(jnp.float32))
