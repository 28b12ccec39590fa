"""Fused block p-quantization + 2-bit pack as a Pallas TPU kernel.

One HBM->VMEM pass per tile of quantization blocks: the kernel computes the
per-block ``||.||_p`` scale (a VPU row reduction), draws the Bernoulli mask by
comparing uniform bits against ``|delta| / scale``, forms ternary signs, and
packs four 2-bit codes per byte — so the value leaving VMEM is already the
wire format for the compressed all-gather.  This is the TPU adaptation of the
paper's CPU-side quantize + Elias-encode step (DESIGN.md §2).

Tiling: the grid walks ``m`` (number of blocks) in tiles of ``TILE_M`` rows of
``B = block_size`` lanes.  ``B`` is a multiple of 128 in every production
config, so rows map cleanly onto VPU lanes; the packed output has ``B/4``
bytes per row, byte ``j`` holding the codes of lanes ``j, j+B/4, j+B/2,
j+3B/4`` (four lane-slices; see ``repro.core.packing``).  VMEM footprint per
grid step is ``TILE_M * B * (4 + 4 + 1 + 0.25)`` bytes — with the default TILE_M=8 and
B=2048 that is ~150 KiB, far under the ~16 MiB VMEM budget, leaving headroom
for double buffering.

Randomness — two variants sharing one quantization body:

* :func:`quantize_pack` takes pre-drawn uint32 bits, so the identical body
  runs under ``interpret=True`` on CPU — the CI oracle, validated bitwise
  against :func:`repro.kernels.ref.ref_quantize_pack`.
* :func:`quantize_pack_prng` (compiled TPU only) draws the bits INSIDE the
  kernel with ``pltpu.prng_seed`` + ``pltpu.prng_random_bits``, seeded per
  tile from the key's two words (the first offset by the grid index).  This
  removes the uint32 bits operand entirely — 4 bytes/dim of pure HBM input
  traffic, as large as the gradient itself — cutting the encode's HBM reads
  roughly in half.  Values agree with the bits variant in distribution, not
  bitwise (independent stream), which is already the stated contract for the
  kernel encode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import pack2bit
from repro.core.quantization import pad_axis_to_multiple, uniform_from_bits

__all__ = ["quantize_pack", "quantize_pack_prng", "DEFAULT_TILE_M"]

DEFAULT_TILE_M = 8


def _quantize_body(delta, bits, packed_ref, scales_ref, *, p: float):
    """Shared quantize+pack body: delta (TILE_M, B) f32, bits uint32."""
    delta = delta.astype(jnp.float32)
    if p == math.inf:
        scale = jnp.max(jnp.abs(delta), axis=-1, keepdims=True)
    elif p == 2:
        scale = jnp.sqrt(jnp.sum(delta * delta, axis=-1, keepdims=True))
    elif p == 1:
        scale = jnp.sum(jnp.abs(delta), axis=-1, keepdims=True)
    else:
        scale = jnp.sum(jnp.abs(delta) ** p, axis=-1, keepdims=True) ** (1.0 / p)

    safe = jnp.where(scale > 0, scale, 1.0)
    probs = jnp.abs(delta) / safe
    xi = uniform_from_bits(bits) < probs
    # Signs stay int32 (the VPU has no 8-bit arithmetic) until pack2bit
    # narrows the packed bytes.
    signs = jnp.where(xi, jnp.sign(delta).astype(jnp.int32), 0)
    scales_ref[...] = scale.astype(jnp.float32)
    packed_ref[...] = pack2bit(signs)


def _kernel(delta_ref, bits_ref, packed_ref, scales_ref, *, p: float):
    _quantize_body(delta_ref[...], bits_ref[...], packed_ref, scales_ref, p=p)


def _kernel_prng(seed_ref, delta_ref, packed_ref, scales_ref, *, p: float):
    # Per-tile stream: the key's first word offset by the grid index (the
    # chip's seed takes two words), so every tile of blocks draws its own
    # bits regardless of launch shape.
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0), seed_ref[1])
    bits = pltpu.bitcast(
        pltpu.prng_random_bits(delta_ref.shape), jnp.uint32
    )
    _quantize_body(delta_ref[...], bits, packed_ref, scales_ref, p=p)


def _check_block(b: int):
    if b % 128:
        raise ValueError(f"block size {b} must be a multiple of 128 (VPU lanes)")


@functools.partial(
    jax.jit, static_argnames=("p", "tile_m", "interpret")
)
def quantize_pack(
    delta: jax.Array,
    bits: jax.Array,
    *,
    p: float = math.inf,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
):
    """delta (m, B) f32, bits (m, B) uint32 -> (packed (m, B/4) u8, scales (m,1) f32).

    ``m`` is padded to a multiple of ``tile_m`` internally (zero blocks quantize
    to zero, so padding is harmless and stripped on return).
    """
    m, b = delta.shape
    _check_block(b)
    delta = pad_axis_to_multiple(delta, tile_m)
    bits = pad_axis_to_multiple(bits, tile_m)
    mp = delta.shape[0]

    grid = (mp // tile_m,)
    packed, scales = pl.pallas_call(
        functools.partial(_kernel, p=p),
        name="quantize_pack",
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, b), lambda i: (i, 0)),
            pl.BlockSpec((tile_m, b), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_m, b // 4), lambda i: (i, 0)),
            pl.BlockSpec((tile_m, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, b // 4), jnp.uint8),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(delta, bits)
    return packed[:m], scales[:m]


@functools.partial(jax.jit, static_argnames=("p", "tile_m"))
def quantize_pack_prng(
    delta: jax.Array,
    seed: jax.Array,
    *,
    p: float = math.inf,
    tile_m: int = DEFAULT_TILE_M,
):
    """In-kernel-PRNG variant: delta (m, B) f32, seed (2,) int32 words.

    Compiled Mosaic only — the ``pltpu`` PRNG primitives have no interpret
    lowering, so CI keeps validating the shared quantization body through the
    pre-drawn-bits oracle (:func:`quantize_pack`) and this wrapper is reached
    exclusively on real TPU backends (see ``repro.kernels.ops``).
    """
    m, b = delta.shape
    _check_block(b)
    delta = pad_axis_to_multiple(delta, tile_m)
    mp = delta.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // tile_m,),
        in_specs=[
            pl.BlockSpec((tile_m, b), lambda i, seed_ref: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_m, b // 4), lambda i, seed_ref: (i, 0)),
            pl.BlockSpec((tile_m, 1), lambda i, seed_ref: (i, 0)),
        ],
    )
    packed, scales = pl.pallas_call(
        functools.partial(_kernel_prng, p=p),
        name="quantize_pack_prng",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((mp, b // 4), jnp.uint8),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        ],
    )(seed.astype(jnp.int32), delta)
    return packed[:m], scales[:m]
