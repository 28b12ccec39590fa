"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True on CPU backends (this container) and False on
TPU, where the same kernel bodies compile to Mosaic.  Kernel-backed
compressors (every operator in :mod:`repro.core.compressors` constructed with
``use_kernel=True``) advertise the capability themselves and route their
encode / server-side decode through the ``*_op`` wrappers here — consumers of
the compressor interface never switch on an external flag (DESIGN.md §2).

Since the PRNG unification (every fallback draws ``jax.random.bits`` and maps
them through :func:`repro.core.quantization.uniform_from_bits`, the same
shift/scale the kernel bodies apply), the pre-drawn-bits kernel encodes are
bitwise-EQUAL to the pure-jnp fallbacks given the same key — as are all
decode_sum and fused decode_sum+apply kernels (same f32 accumulate
recurrence).  ``tools/check_kernels.py`` enforces that every registry
operator names its interpret-mode oracle for exactly this contract.

The ONE exception: on compiled TPU backends the stochastic encodes route
through the ``*_prng_op`` variants, which draw their bits INSIDE the kernel
(``pltpu.prng_seed`` + ``prng_random_bits`` seeded from the PRNG key's two
words), so the uint32 bits operand and its 4 bytes/dim of HBM input traffic
disappear.  Those agree with the fallback in distribution, not bitwise; under
``interpret=True`` (CPU CI) the pre-drawn-bits bodies remain the oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .dense import dense_copy, dense_decode_sum, dense_decode_sum_mean
from .nat_pack import (
    nat_decode_sum,
    nat_decode_sum_apply,
    nat_decode_sum_mean,
    nat_pack,
    nat_pack_prng,
)
from .quantize_pack import quantize_pack, quantize_pack_prng
from .sparse import sparse_decode_sum, sparse_decode_sum_mean, sparse_gather
from .ssd import ssd_chunk_scan
from .unpack_reduce import unpack_reduce, unpack_reduce_apply, unpack_reduce_mean

__all__ = [
    "default_interpret",
    "quantize_pack_op",
    "quantize_pack_prng_op",
    "unpack_reduce_op",
    "unpack_reduce_mean_op",
    "unpack_reduce_apply_op",
    "nat_pack_op",
    "nat_pack_prng_op",
    "nat_decode_sum_op",
    "nat_decode_sum_mean_op",
    "nat_decode_sum_apply_op",
    "sparse_gather_op",
    "sparse_decode_sum_op",
    "sparse_decode_sum_mean_op",
    "dense_copy_op",
    "dense_decode_sum_op",
    "dense_decode_sum_mean_op",
    "ssd_chunk_scan_op",
]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _key_words(key) -> jax.Array:
    """A PRNG key's two 32-bit words as an (2,) int32 seed for the in-kernel
    PRNG (accepts both raw uint32 keys and new-style typed keys)."""
    try:
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            key = jax.random.key_data(key)
    except (AttributeError, TypeError):
        pass
    words = key.reshape(-1).astype(jnp.uint32)
    if words.shape[0] < 2:
        words = jnp.concatenate([words, jnp.zeros((1,), jnp.uint32)])
    return jax.lax.bitcast_convert_type(words[:2], jnp.int32)


# -- ternary (diana / qsgd / terngrad / dqgd) -------------------------------

def quantize_pack_op(delta2d, bits, *, p: float):
    return quantize_pack(delta2d, bits, p=p, interpret=default_interpret())


def quantize_pack_prng_op(delta2d, key, *, p: float):
    return quantize_pack_prng(delta2d, _key_words(key), p=p)


def unpack_reduce_op(packed, scales):
    return unpack_reduce(packed, scales, interpret=default_interpret())


def unpack_reduce_mean_op(packed, scales):
    return unpack_reduce_mean(packed, scales, interpret=default_interpret())


def unpack_reduce_apply_op(packed, scales, h, *, alpha: float):
    return unpack_reduce_apply(
        packed, scales, h, alpha=alpha, interpret=default_interpret()
    )


# -- natural ----------------------------------------------------------------

def nat_pack_op(x, bits):
    return nat_pack(x, bits, interpret=default_interpret())


def nat_pack_prng_op(x, key):
    return nat_pack_prng(x, _key_words(key))


def nat_decode_sum_op(codes):
    return nat_decode_sum(codes, interpret=default_interpret())


def nat_decode_sum_mean_op(codes):
    return nat_decode_sum_mean(codes, interpret=default_interpret())


def nat_decode_sum_apply_op(codes, h, *, alpha: float):
    return nat_decode_sum_apply(
        codes, h, alpha=alpha, interpret=default_interpret()
    )


# -- sparse (rand-k / top-k + EF) -------------------------------------------

def sparse_gather_op(x, idx):
    return sparse_gather(x, idx.astype(jnp.int32), interpret=default_interpret())


def sparse_decode_sum_op(idx, values, scale, *, d: int):
    return sparse_decode_sum(
        idx.astype(jnp.int32), values, scale, d=d, interpret=default_interpret()
    )


def sparse_decode_sum_mean_op(idx, values, scale, *, d: int):
    return sparse_decode_sum_mean(
        idx.astype(jnp.int32), values, scale, d=d, interpret=default_interpret()
    )


# -- dense (identity) -------------------------------------------------------

def dense_copy_op(x):
    return dense_copy(x, interpret=default_interpret())


def dense_decode_sum_op(values):
    return dense_decode_sum(values, interpret=default_interpret())


def dense_decode_sum_mean_op(values):
    return dense_decode_sum_mean(values, interpret=default_interpret())


# -- Mamba-2 chunked SSD scan (differentiable) --------------------------------

def ssd_chunk_scan_op(x, dt, a, bm, cm, *, chunk: int, n_groups: int):
    return ssd_chunk_scan(x, dt, a, bm, cm, chunk, n_groups, default_interpret())
