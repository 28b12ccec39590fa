"""Natural compression as Pallas TPU kernels (paper §Natural, omega = 1/8).

Encode: each f32 gradient entry is stochastically rounded to a signed power of
two and stored as a 9-bit sign+exponent code in an int16 container (the wire
format of :class:`repro.core.compressors.natural.NaturalCompressor`).  The
fallback derives the rounding probability through ``jnp.frexp``; the kernel
reads the exponent and mantissa straight out of the float's BIT pattern:

* ``p_up = (bits & 0x7FFFFF) * 2^-23`` — for a normal float this is exactly
  ``2*|mant| - 1`` (the fractional part of the mantissa; Sterbenz applies, no
  rounding), i.e. the probability of rounding UP to the next power of two.
* ``chosen = (bits >> 23) - 127 + bernoulli(u < p_up)`` — the unbiased
  exponent, bumped with the stochastic-rounding draw.
* Subnormals are pre-scaled by ``2^24`` (exact — it only shifts the exponent)
  so the same two lines apply, then 24 is subtracted back.

Bitwise agreement with the frexp oracle (:func:`repro.kernels.ref.ref_nat_pack`)
holds on ALL finite inputs including subnormals — that equality is a real test
of the bit trick and is enforced in CI under ``interpret=True``.

Decode_sum: the server unpacks each worker's codes (``sign * 2^(|code|-BIAS)``,
the power of two written into the exponent bits by ``pow2``) and accumulates in place over the ``(m_tiles, n)``
grid, worker axis innermost, so no ``(n, d)`` dense float tensor ever
materialises in HBM — traffic is ``2nd`` bytes of codes in, ``4d`` bytes
out.  The ``_apply`` variant fuses
DIANA's server memory update into the last grid step (see
:mod:`repro.kernels.unpack_reduce` for the pattern).

Randomness mirrors :mod:`repro.kernels.quantize_pack`: a pre-drawn-bits
variant (the CI oracle, bitwise-equal to the fallback because both use
``uniform_from_bits``) and a compiled-TPU-only in-kernel PRNG variant that
never materialises the ``(d,)`` uint32 bits operand in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import (
    pad_axis_to_multiple, pow2, uniform_from_bits,
)

from .unpack_reduce import DECODE_PARAMS

__all__ = [
    "nat_pack",
    "nat_pack_prng",
    "nat_decode_sum",
    "nat_decode_sum_mean",
    "nat_decode_sum_apply",
    "NAT_BIAS",
    "LANES",
    "DEFAULT_TILE_M",
]

NAT_BIAS = 160  # == repro.core.compressors.natural._BIAS
LANES = 128
DEFAULT_TILE_M = 8


# ---------------------------------------------------------------------------
# Encode
# ---------------------------------------------------------------------------

def _encode_body(x, bits):
    """f32 tile + uint32 bits -> int16 nat codes, bitwise == the frexp oracle.

    The float's bit pattern is read as int32 (|x| has a zero sign bit, so
    every field is non-negative): Mosaic converts int32, not uint32, to f32.
    """
    u = uniform_from_bits(bits)
    b0 = jax.lax.bitcast_convert_type(jnp.abs(x), jnp.int32)
    # Subnormals have a zero exponent field; scaling by 2^24 is exact and
    # moves them into the normal range so one code path covers everything.
    is_sub = ((b0 >> 23) == 0) & (x != 0.0)
    xs = jnp.where(is_sub, x * jnp.float32(1 << 24), x)
    bs = jax.lax.bitcast_convert_type(jnp.abs(xs), jnp.int32)
    p_up = (bs & 0x7FFFFF).astype(jnp.float32) * jnp.float32(2.0 ** -23)
    expo = (bs >> 23) - 127 - jnp.where(is_sub, 24, 0)
    chosen = expo + (u < p_up).astype(jnp.int32)
    sign = jnp.where(x < 0.0, -1, 1)
    code = sign * (chosen + NAT_BIAS)
    return jnp.where(x == 0.0, 0, code).astype(jnp.int16)


def _kernel(x_ref, bits_ref, out_ref):
    out_ref[...] = _encode_body(x_ref[...], bits_ref[...])


def _kernel_prng(seed_ref, x_ref, out_ref):
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0), seed_ref[1])
    bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
    out_ref[...] = _encode_body(x_ref[...], bits)


def _rows(flat: jax.Array, tile_m: int) -> jax.Array:
    """(d,) -> (mp, LANES) with mp a multiple of tile_m (zero padded)."""
    x2 = pad_axis_to_multiple(flat, LANES * tile_m).reshape(-1, LANES)
    return x2


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def nat_pack(
    x: jax.Array,
    bits: jax.Array,
    *,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
) -> jax.Array:
    """x (d,) f32, bits (d,) uint32 -> (d,) int16 natural-compression codes."""
    d = x.shape[0]
    x2 = _rows(x.astype(jnp.float32), tile_m)
    b2 = _rows(bits, tile_m)
    mp = x2.shape[0]
    codes = pl.pallas_call(
        _kernel,
        name="nat_pack",
        grid=(mp // tile_m,),
        in_specs=[
            pl.BlockSpec((tile_m, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tile_m, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, LANES), jnp.int16),
        interpret=interpret,
    )(x2, b2)
    return codes.reshape(-1)[:d]


@functools.partial(jax.jit, static_argnames=("tile_m",))
def nat_pack_prng(
    x: jax.Array,
    seed: jax.Array,
    *,
    tile_m: int = DEFAULT_TILE_M,
) -> jax.Array:
    """In-kernel-PRNG encode: x (d,) f32, seed (2,) int32 -> (d,) int16.

    Compiled Mosaic only (``pltpu`` PRNG has no interpret lowering); reached
    exclusively on real TPU backends via ``repro.kernels.ops``.
    """
    d = x.shape[0]
    x2 = _rows(x.astype(jnp.float32), tile_m)
    mp = x2.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // tile_m,),
        in_specs=[pl.BlockSpec((tile_m, LANES), lambda i, seed_ref: (i, 0))],
        out_specs=pl.BlockSpec((tile_m, LANES), lambda i, seed_ref: (i, 0)),
    )
    codes = pl.pallas_call(
        _kernel_prng,
        name="nat_pack_prng",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, LANES), jnp.int16),
    )(seed.astype(jnp.int32), x2)
    return codes.reshape(-1)[:d]


# ---------------------------------------------------------------------------
# Decode + accumulate (+ fused server apply)
# ---------------------------------------------------------------------------

def _decode_body(codes):
    c = codes.astype(jnp.int32)
    mag = pow2(jnp.abs(c) - NAT_BIAS)
    sign = jnp.sign(c).astype(jnp.float32)
    return jnp.where(c == 0, 0.0, sign * mag)


def _accumulate(i, dense, out_ref):
    # Initialise with the FIRST worker's decode (not zeros) so the kernel
    # reproduces the fallback recurrence ``acc = decode(0); acc += decode(i)``
    # bitwise — natural decode can produce -0.0 (sign * underflowed exp2) and
    # ``0.0 + (-0.0)`` would flip it to +0.0.
    @pl.when(i == 0)
    def _init():
        out_ref[...] = dense

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += dense


def _sum_kernel(codes_ref, out_ref):
    _accumulate(pl.program_id(1), _decode_body(codes_ref[0]), out_ref)


def _mean_kernel(codes_ref, out_ref, *, n):
    _sum_kernel(codes_ref, out_ref)

    @pl.when(pl.program_id(1) == n - 1)
    def _mean():
        out_ref[...] = out_ref[...] / jnp.float32(n)


def _apply_kernel(codes_ref, h_ref, ghat_ref, newh_ref, *, n, alpha):
    _accumulate(pl.program_id(1), _decode_body(codes_ref[0]), ghat_ref)

    @pl.when(pl.program_id(1) == n - 1)
    def _apply():
        dm = ghat_ref[...] / jnp.float32(n)
        h = h_ref[...]
        ghat_ref[...] = h + dm
        newh_ref[...] = h + jnp.float32(alpha) * dm


def _codes_rows(codes: jax.Array, tile_m: int) -> jax.Array:
    """(n, d) int16 -> (n, mp, LANES), zero padded (code 0 decodes to 0.0)."""
    n, d = codes.shape
    c = pad_axis_to_multiple(codes, LANES * tile_m, axis=1)
    return c.reshape(n, -1, LANES)


def _codes_spec(tile_m: int):
    """Worker i's codes for output tile j, on the ``(m_tiles, n)`` grid."""
    return pl.BlockSpec((1, tile_m, LANES), lambda j, i: (i, j, 0))


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def nat_decode_sum(
    codes: jax.Array,
    *,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
) -> jax.Array:
    """codes (n, d) int16 -> (d,) f32 sum of decodes over workers."""
    d = codes.shape[1]
    c = _codes_rows(codes, tile_m)
    n, mp, _ = c.shape
    out = pl.pallas_call(
        _sum_kernel,
        name="nat_decode_sum",
        grid=(mp // tile_m, n),
        in_specs=[_codes_spec(tile_m)],
        out_specs=pl.BlockSpec((tile_m, LANES), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, LANES), jnp.float32),
        compiler_params=DECODE_PARAMS,
        interpret=interpret,
    )(c)
    return out.reshape(-1)[:d]


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def nat_decode_sum_mean(
    codes: jax.Array,
    *,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
) -> jax.Array:
    """Fused decode_sum + divide: codes (n, d) -> (d,) mean of decodes."""
    d = codes.shape[1]
    c = _codes_rows(codes, tile_m)
    n, mp, _ = c.shape
    out = pl.pallas_call(
        functools.partial(_mean_kernel, n=n),
        name="nat_decode_sum_mean",
        grid=(mp // tile_m, n),
        in_specs=[_codes_spec(tile_m)],
        out_specs=pl.BlockSpec((tile_m, LANES), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, LANES), jnp.float32),
        compiler_params=DECODE_PARAMS,
        interpret=interpret,
    )(c)
    return out.reshape(-1)[:d]


@functools.partial(jax.jit, static_argnames=("alpha", "tile_m", "interpret"))
def nat_decode_sum_apply(
    codes: jax.Array,
    h: jax.Array,
    *,
    alpha: float,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused decode_sum + DIANA server update.

    codes (n, d) int16, h (d,) f32 -> flat ``(h + dm, h + alpha * dm)`` with
    ``dm = sum_i decode(codes_i) / n``, both (d,).
    """
    d = codes.shape[1]
    if h.shape[0] != d:
        raise ValueError(f"h length {h.shape[0]} != payload dim {d}")
    c = _codes_rows(codes, tile_m)
    n, mp, _ = c.shape
    h2 = pad_axis_to_multiple(h.astype(jnp.float32), LANES * tile_m).reshape(
        -1, LANES
    )
    ghat, newh = pl.pallas_call(
        functools.partial(_apply_kernel, n=n, alpha=float(alpha)),
        name="nat_decode_sum_apply",
        grid=(mp // tile_m, n),
        in_specs=[
            _codes_spec(tile_m),
            pl.BlockSpec((tile_m, LANES), lambda j, i: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_m, LANES), lambda j, i: (j, 0)),
            pl.BlockSpec((tile_m, LANES), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, LANES), jnp.float32),
            jax.ShapeDtypeStruct((mp, LANES), jnp.float32),
        ],
        compiler_params=DECODE_PARAMS,
        interpret=interpret,
    )(c, h2)
    return ghat.reshape(-1)[:d], newh.reshape(-1)[:d]
