"""p-quantization and block p-quantization operators (paper Def. 1 / Def. 2).

The operator transforms ``delta`` into a random ternary vector

    qhat_j = ||delta||_p * sign(delta_j) * xi_j,   xi_j ~ Be(|delta_j| / ||delta||_p)

It is unbiased (Lemma 2), has variance ``Psi(delta) = ||d||_1 ||d||_p - ||d||_2^2``
and expected sparsity ``E||qhat||_0 = ||d||_1 / ||d||_p`` (Theorem 1).

Everything here is pure jnp, shape-static and vmap/scan/pjit friendly.  The
internal representation of a quantized block is ``(signs, scale)`` where
``signs`` is an int8 tensor in {-1, 0, +1} and ``scale`` is the block's
``||.||_p`` norm — this is what gets bit-packed (2 bits/dim) for communication
(see :mod:`repro.core.packing`).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

__all__ = [
    "QuantizedBlocks",
    "alpha_p",
    "lp_norm",
    "quantize_blocks",
    "dequantize_blocks",
    "quantize_pytree",
    "dequantize_pytree",
    "expected_sparsity",
    "quantization_variance",
    "pad_axis_to_multiple",
    "pad_to_blocks",
    "num_blocks",
    "quantize_blocks_from_uniform",
    "uniform_from_bits",
    "pow2",
]


def uniform_from_bits(bits: jax.Array) -> jax.Array:
    """uint32 -> uniform [0,1) f32 using the top 24 bits (TPU-friendly).

    THE one bits->uniform map of the repo: every stochastic operator draws
    ``jax.random.bits`` and feeds them through this function, and the Pallas
    kernels apply the identical shift/scale to their bits operand (or to
    ``pltpu.prng_random_bits`` on compiled TPU) — which is what makes the
    kernel routes bitwise-EQUAL to the pure-jnp fallbacks given the same
    bits, not merely equal in distribution.
    """
    # The top 24 bits fit an int32 exactly; the signed convert is the one
    # Mosaic lowers (it refuses uint32 -> f32), and the value is the same.
    top = jax.lax.bitcast_convert_type(bits >> jnp.uint32(8), jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def pow2(e: jax.Array) -> jax.Array:
    """Exact f32 ``2.0 ** e`` for integer ``e``, written into the exponent
    bits: subnormal below ``2^-126``, 0 below ``2^-149``, inf above
    ``2^127``.

    THE one power-of-two map of natural compression's decode, shared by the
    fallback and the kernels like :func:`uniform_from_bits`: ``jnp.exp2`` is
    a transcendental approximation whose XLA and Mosaic lowerings disagree
    on a TPU, while this is exact on every backend.
    """
    e = e.astype(jnp.int32)
    normal = jax.lax.bitcast_convert_type(
        (jnp.clip(e, -126, 127) + 127) << 23, jnp.float32)
    sub = jax.lax.bitcast_convert_type(
        jnp.left_shift(jnp.int32(1), jnp.clip(e + 149, 0, 22)), jnp.float32)
    out = jnp.where(e >= -126, normal, jnp.where(e >= -149, sub, 0.0))
    return jnp.where(e > 127, jnp.inf, out)


# ---------------------------------------------------------------------------
# alpha_p — the key geometric constant (Lemma 1)
# ---------------------------------------------------------------------------

def alpha_p(p: float, d: int) -> float:
    """``alpha_p(d) = inf_x ||x||_2^2 / (||x||_1 ||x||_p)`` (paper eq. 12).

    Closed forms (Lemma 1): ``alpha_1 = 1/d``, ``alpha_2 = 1/sqrt(d)``,
    ``alpha_inf = 2/(1+sqrt(d))``.  For other ``p`` we fall back to the valid
    lower bound ``d^{-(1 - 1/p)} * ...`` via interpolation; the three values the
    paper analyses are exact.
    """
    if d <= 0:
        raise ValueError(f"block size must be positive, got {d}")
    if d == 1:
        return 1.0
    if p == 1:
        return 1.0 / d
    if p == 2:
        return 1.0 / math.sqrt(d)
    if p == math.inf:
        return 2.0 / (1.0 + math.sqrt(d))
    # General p: ||x||_1 <= d^{1-1/p}||x||_p and ||x||_p <= ||x||_2 for p>=2 give
    # a valid lower bound; exactness only claimed for p in {1, 2, inf}.
    if p > 2:
        return 1.0 / (d ** (1.0 - 1.0 / p))
    raise ValueError(f"unsupported quantization norm power p={p}")


def lp_norm(x: jax.Array, p: float, axis=-1, keepdims: bool = False) -> jax.Array:
    """``||x||_p`` along ``axis`` with stable handling of p = inf."""
    if p == math.inf:
        return jnp.max(jnp.abs(x), axis=axis, keepdims=keepdims)
    if p == 2:
        return jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=keepdims))
    if p == 1:
        return jnp.sum(jnp.abs(x), axis=axis, keepdims=keepdims)
    return jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=keepdims) ** (1.0 / p)


# ---------------------------------------------------------------------------
# Block quantization
# ---------------------------------------------------------------------------

class QuantizedBlocks(NamedTuple):
    """Ternary representation of a block-quantized vector.

    signs:  int8  (num_blocks, block_size) in {-1, 0, +1}
    scales: f32   (num_blocks,)  — per-block ||.||_p norm

    The original (unpadded) length is NOT stored (it would become a traced
    pytree leaf under vmap/jit); pass ``shape`` to :func:`dequantize_blocks`.
    """

    signs: jax.Array
    scales: jax.Array


def num_blocks(d: int, block_size: int) -> int:
    return -(-d // block_size)


def pad_axis_to_multiple(x: jax.Array, multiple: int, axis: int = 0) -> jax.Array:
    """Zero-pad ``x`` along ``axis`` up to the next multiple of ``multiple``.

    The ONE shared block-padding helper (used by :func:`pad_to_blocks` and the
    kernel wrappers in :mod:`repro.kernels`): implemented with ``concatenate``,
    not ``jnp.pad``, because the HLO Pad op RET_CHECKs in old XLA's SPMD
    partitioner inside partial-manual shard_map bodies (DESIGN.md §6) — the
    aggregation runs inside a shard_map whose worker axes are manual while the
    inner axes stay auto, and every op on that path must stay partitionable.
    Zero blocks quantize (and decode) to zero, so the padding is harmless.
    """
    n = x.shape[axis]
    pad = -n % multiple
    if pad:
        pad_shape = x.shape[:axis] + (pad,) + x.shape[axis + 1:]
        x = jnp.concatenate([x, jnp.zeros(pad_shape, x.dtype)], axis=axis)
    return x


def pad_to_blocks(x: jax.Array, block_size: int) -> jax.Array:
    """Flatten and zero-pad ``x`` to a (num_blocks, block_size) matrix."""
    flat = pad_axis_to_multiple(x.reshape(-1), block_size)
    return flat.reshape(-1, block_size)


def quantize_blocks_from_uniform(
    blocks: jax.Array, u: jax.Array, *, p: float
) -> QuantizedBlocks:
    """Block p-quantization of an (m, B) block matrix given the uniform draws.

    The PRNG-free body of :func:`quantize_blocks`, shared with the bucketed
    whole-model path (:mod:`repro.core.bucket`), which concatenates per-leaf
    uniform draws so ONE vectorized call reproduces the per-leaf quantization
    bitwise.
    """
    scales = lp_norm(blocks, p, axis=-1)             # (m,)
    safe = jnp.where(scales > 0, scales, 1.0)
    probs = jnp.abs(blocks) / safe[:, None]          # in [0, 1]
    xi = (u < probs).astype(jnp.int8)
    signs = jnp.sign(blocks).astype(jnp.int8) * xi
    scales = jnp.where(scales > 0, scales, 0.0).astype(jnp.float32)
    return QuantizedBlocks(signs=signs, scales=scales)


@partial(jax.jit, static_argnames=("p", "block_size"))
def quantize_blocks(
    x: jax.Array,
    key: jax.Array,
    *,
    p: float = math.inf,
    block_size: int = 1024,
) -> QuantizedBlocks:
    """Block p-quantization (Def. 2) of an arbitrary-shaped tensor.

    Zero blocks quantize to zero (Def. 1 handles ``delta = 0`` separately); the
    Bernoulli probabilities ``|x_j| / ||x(l)||_p`` are well-defined (<= 1) for
    every ``p >= 1``.
    """
    blocks = pad_to_blocks(x, block_size)            # (m, B)
    # Draw raw bits and derive the uniforms with the kernels' bits->uniform
    # map, so the pre-drawn-bits kernel route consumes the SAME stream and
    # produces bitwise-identical wire payloads (DESIGN.md §Kernels).
    bits = jax.random.bits(key, blocks.shape, dtype=jnp.uint32)
    return quantize_blocks_from_uniform(blocks, uniform_from_bits(bits), p=p)


def dequantize_blocks(q: QuantizedBlocks, shape=None, dtype=jnp.float32) -> jax.Array:
    """Reconstruct the dense (unbiased) estimate ``scale * signs``.

    ``shape`` (or its product) tells how many leading entries of the padded
    flat vector are real data; defaults to everything.
    """
    dense = q.signs.astype(dtype) * q.scales[:, None].astype(dtype)
    flat = dense.reshape(-1)
    if shape is not None:
        size = int(np_prod(shape))
        flat = flat[:size]
        return flat.reshape(shape)
    return flat


def np_prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


# ---------------------------------------------------------------------------
# Pytree-level quantization (one leaf = one or more blocks)
# ---------------------------------------------------------------------------

def quantize_pytree(tree, key: jax.Array, *, p: float, block_size: int):
    """Quantize every leaf of a pytree with independent PRNG streams.

    Block boundaries never straddle leaves — this is the paper's "layers have
    different scales" motivation for bucketed quantization taken to its natural
    limit: blocks align with (slices of) parameter tensors.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    qs = [
        quantize_blocks(leaf, k, p=p, block_size=block_size)
        for leaf, k in zip(leaves, keys)
    ]
    return jax.tree_util.tree_unflatten(treedef, qs)


def dequantize_pytree(qtree, like):
    """Inverse of :func:`quantize_pytree` given the template pytree ``like``."""
    q_leaves = [
        x for x in jax.tree_util.tree_leaves(qtree, is_leaf=lambda t: isinstance(t, QuantizedBlocks))
    ]
    like_leaves, treedef = jax.tree_util.tree_flatten(like)
    outs = [
        dequantize_blocks(q, shape=l.shape, dtype=l.dtype)
        for q, l in zip(q_leaves, like_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, outs)


# ---------------------------------------------------------------------------
# Theory quantities (for tests / benchmarks)
# ---------------------------------------------------------------------------

def expected_sparsity(x: jax.Array, p: float, block_size: int) -> jax.Array:
    """Theorem 1: ``E ||qhat||_0 = sum_l ||x(l)||_1 / ||x(l)||_p``."""
    blocks = pad_to_blocks(x, block_size)
    n1 = lp_norm(blocks, 1, axis=-1)
    np_ = lp_norm(blocks, p, axis=-1)
    return jnp.sum(jnp.where(np_ > 0, n1 / jnp.where(np_ > 0, np_, 1.0), 0.0))


def quantization_variance(x: jax.Array, p: float, block_size: int) -> jax.Array:
    """Lemma 2: ``E||qhat - x||_2^2 = sum_l ||x(l)||_1 ||x(l)||_p - ||x(l)||_2^2``."""
    blocks = pad_to_blocks(x, block_size)
    n1 = lp_norm(blocks, 1, axis=-1)
    np_ = lp_norm(blocks, p, axis=-1)
    n2sq = jnp.sum(blocks * blocks, axis=-1)
    return jnp.sum(n1 * np_ - n2sq)
