"""Sparse (rand-k / top-k) payload kernels: value gather and scatter-add
decode_sum, with the optional fused DIANA server update.

The fusion boundary (DESIGN.md §Kernels): index SELECTION — ``top_k`` of
random tags for rand-k, magnitude ``top_k`` for top-k — stays in lax.  It is
control logic, it owns the PRNG schedule that the bitwise bucketed==per-leaf
contract depends on, and XLA's sort lowerings are already tuned.  What Pallas
owns is the data movement: the compress-side value gather and the server-side
scatter-add accumulation ``sum_i scatter(idx_i, values_i * scale)``, which the
sequential TPU grid accumulates in place so the ``(n, d)`` dense per-worker
tensor never materialises in HBM (traffic: ``n*k`` index/value pairs in,
``4d`` bytes out, instead of ``n * 4d``).

Shapes are exact (no lane padding) and the kernels are validated bitwise
against :func:`repro.kernels.ref.ref_sparse_decode_sum` under
``interpret=True`` — the CI contract.  Compiled Mosaic lowering of dynamic
gather/scatter is not portable across TPU generations, so these kernels are
interpret-contract only and ``use_kernel`` stays opt-in for the sparse
operators (``auto`` resolves to off; see ``tools/check_kernels.py``).

``scale`` is always a per-entry (k,) vector operand: ``full(d/k)`` for
per-leaf rand-k (bitwise-equal to the scalar multiply of the fallback),
the per-segment ``d_l/k_l`` staircase for bucketed rand-k, and ones for
top-k (``x * 1.0 == x`` exactly).

The fused ``_mean`` variant folds the final ``/n`` into the last grid step —
a single correctly rounded op, so fusing it cannot perturb bits.  There is
deliberately NO fused alpha-apply variant: the DIANA memory tail
``h' = h + alpha*dm`` composes OUTSIDE the kernel via the operator's base
hooks.  XLA's FMA contraction of that multiply-add is decided per-fusion at
codegen, so the kernel route stays bitwise-equal to the lax fallback only if
both routes feed the IDENTICAL epilogue fusion a materialised sum — which
they do: the fallback's scatter chain and this kernel's grid loop both
materialise ``s``, and the base-hook composition downstream is literally the
same code.  (The ternary/natural families fuse their epilogue in-kernel
instead; their fallback decode is one elementwise fusion, which contracts
the same way as the kernel body — asserted by the coverage tests.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "sparse_gather",
    "sparse_decode_sum",
    "sparse_decode_sum_mean",
]


def _gather_kernel(x_ref, idx_ref, out_ref):
    out_ref[...] = x_ref[...][idx_ref[...]]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_gather(
    x: jax.Array, idx: jax.Array, *, interpret: bool = True
) -> jax.Array:
    """Compress-side value gather: x (d,) f32, idx (k,) int32 -> (k,) f32."""
    d, k = x.shape[0], idx.shape[0]
    return pl.pallas_call(
        _gather_kernel,
        name="sparse_gather",
        grid=(1,),
        in_specs=[
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((k,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((k,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((k,), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32), idx)


def _dense_row(idx_ref, val_ref, scale_ref, d: int):
    scaled = val_ref[0] * scale_ref[...]
    return jnp.zeros((d,), jnp.float32).at[idx_ref[0]].add(scaled)


def _accumulate(i, dense, out_ref):
    # Init with the first worker's scatter (not zeros + add): the fallback
    # recurrence starts from ``decode(select(0))`` and -0.0 products must
    # survive bitwise (0.0 + (-0.0) == +0.0 would lose them).
    @pl.when(i == 0)
    def _init():
        out_ref[...] = dense

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += dense


def _sum_kernel(idx_ref, val_ref, scale_ref, out_ref):
    i = pl.program_id(0)
    _accumulate(i, _dense_row(idx_ref, val_ref, scale_ref, out_ref.shape[0]), out_ref)


def _mean_kernel(idx_ref, val_ref, scale_ref, out_ref, *, n):
    _sum_kernel(idx_ref, val_ref, scale_ref, out_ref)

    @pl.when(pl.program_id(0) == n - 1)
    def _mean():
        out_ref[...] = out_ref[...] / jnp.float32(n)


def _sparse_specs(n, k, d):
    in_specs = [
        pl.BlockSpec((1, k), lambda i: (i, 0)),   # idx
        pl.BlockSpec((1, k), lambda i: (i, 0)),   # values
        pl.BlockSpec((k,), lambda i: (0,)),       # scale (shared)
    ]
    out_spec = pl.BlockSpec((d,), lambda i: (0,))
    return in_specs, out_spec


@functools.partial(jax.jit, static_argnames=("d", "interpret"))
def sparse_decode_sum(
    idx: jax.Array,
    values: jax.Array,
    scale: jax.Array,
    *,
    d: int,
    interpret: bool = True,
) -> jax.Array:
    """idx/values (n, k), scale (k,) -> (d,) f32 scatter-add sum over workers."""
    n, k = idx.shape
    in_specs, out_spec = _sparse_specs(n, k, d)
    return pl.pallas_call(
        _sum_kernel,
        name="sparse_decode_sum",
        grid=(n,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        interpret=interpret,
    )(idx, values.astype(jnp.float32), scale.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("d", "interpret"))
def sparse_decode_sum_mean(
    idx: jax.Array,
    values: jax.Array,
    scale: jax.Array,
    *,
    d: int,
    interpret: bool = True,
) -> jax.Array:
    """Fused scatter-add decode_sum + divide -> (d,) mean over workers.

    The divide is a single correctly rounded op, so fusing it is
    contraction-safe — unlike the memory multiply-add, which is why there is
    no ``apply`` variant (module docstring)."""
    n, k = idx.shape
    in_specs, out_spec = _sparse_specs(n, k, d)
    return pl.pallas_call(
        functools.partial(_mean_kernel, n=n),
        name="sparse_decode_sum_mean",
        grid=(n,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        interpret=interpret,
    )(idx, values.astype(jnp.float32), scale.astype(jnp.float32))


