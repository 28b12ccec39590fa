"""Server-side decode: unpack 2-bit ternary payloads and accumulate the sum
over workers — the Pallas realisation of DIANA's ``mean_i dhat_i``.

Grid layout ``(m_tiles, n_workers)``, declared ``("parallel", "arbitrary")``:
the worker (reduction) axis is innermost, so each output tile stays resident
in VMEM while every worker's payload for it is added in place
(``out += unpack(packed_i) * scale_i``, initialised on the first worker with
``pl.when``) and is written back once, when the tile index moves on.  Peak
VMEM per step is one packed tile (``TILE_M * B/4`` bytes), one scales column
and the f32 accumulator tile — the dense per-worker payload is never
materialised in HBM, which is the whole point: HBM traffic is ``n * d/4``
bytes in, ``4d`` bytes out, instead of the ``n * 4d`` a naive unpack-then-sum
would move.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import unpack2bit
from repro.core.quantization import pad_axis_to_multiple

__all__ = [
    "unpack_reduce",
    "unpack_reduce_mean",
    "unpack_reduce_apply",
    "DEFAULT_TILE_M",
    "DECODE_PARAMS",
]

DEFAULT_TILE_M = 8

# Output tiles are independent; the worker axis revisits one output block.
DECODE_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _kernel(packed_ref, scales_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)  # first worker
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dense = unpack2bit(packed_ref[0], dtype=jnp.float32)       # (TILE_M, B)
    out_ref[...] += dense * scales_ref[0].astype(jnp.float32)


def _kernel_mean(packed_ref, scales_ref, out_ref, *, n):
    _kernel(packed_ref, scales_ref, out_ref)

    @pl.when(pl.program_id(1) == n - 1)
    def _mean():
        out_ref[...] = out_ref[...] / jnp.float32(n)


def _kernel_apply(packed_ref, scales_ref, h_ref, ghat_ref, newh_ref, *, n, alpha):
    # Accumulate the worker sum in ghat_ref, then on the LAST worker visit run
    # the server epilogue in-register: dm = s/n, ghat = h + dm, h' = h + a*dm.
    # The aggregated sum never round-trips HBM between decode and apply.
    _kernel(packed_ref, scales_ref, ghat_ref)

    @pl.when(pl.program_id(1) == n - 1)
    def _apply():
        dm = ghat_ref[...] / jnp.float32(n)
        h = h_ref[...]
        ghat_ref[...] = h + dm
        newh_ref[...] = h + jnp.float32(alpha) * dm


def _payload_specs(tile_m, b4):
    return [
        pl.BlockSpec((1, tile_m, b4), lambda j, i: (i, j, 0)),
        pl.BlockSpec((1, tile_m, 1), lambda j, i: (i, j, 0)),
    ]


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def unpack_reduce(
    packed: jax.Array,
    scales: jax.Array,
    *,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
) -> jax.Array:
    """packed (n, m, B/4) u8, scales (n, m, 1) f32 -> (m, B) f32 sum over n."""
    n, m, b4 = packed.shape
    packed = pad_axis_to_multiple(packed, tile_m, axis=1)
    scales = pad_axis_to_multiple(scales, tile_m, axis=1)
    mp = packed.shape[1]

    out = pl.pallas_call(
        _kernel,
        name="unpack_reduce",
        grid=(mp // tile_m, n),
        in_specs=_payload_specs(tile_m, b4),
        out_specs=pl.BlockSpec((tile_m, b4 * 4), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, b4 * 4), jnp.float32),
        compiler_params=DECODE_PARAMS,
        interpret=interpret,
    )(packed, scales)
    return out[:m]


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def unpack_reduce_mean(
    packed: jax.Array,
    scales: jax.Array,
    *,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
) -> jax.Array:
    """Fused decode_sum + divide: (n, m, B/4) u8 -> (m, B) f32 mean over n."""
    n, m, b4 = packed.shape
    packed = pad_axis_to_multiple(packed, tile_m, axis=1)
    scales = pad_axis_to_multiple(scales, tile_m, axis=1)
    mp = packed.shape[1]

    out = pl.pallas_call(
        functools.partial(_kernel_mean, n=n),
        name="unpack_reduce_mean",
        grid=(mp // tile_m, n),
        in_specs=_payload_specs(tile_m, b4),
        out_specs=pl.BlockSpec((tile_m, b4 * 4), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, b4 * 4), jnp.float32),
        compiler_params=DECODE_PARAMS,
        interpret=interpret,
    )(packed, scales)
    return out[:m]


@functools.partial(jax.jit, static_argnames=("alpha", "tile_m", "interpret"))
def unpack_reduce_apply(
    packed: jax.Array,
    scales: jax.Array,
    h: jax.Array,
    *,
    alpha: float,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused decode_sum + DIANA server update for the ternary family.

    packed (n, m, B/4) u8, scales (n, m, 1) f32, h (d,) f32 with
    d <= m * B.  Returns flat ``(ghat, new_h) = (h + dm, h + alpha * dm)``
    where ``dm = sum_i unpack(packed_i) * scales_i / n``, both (d,).
    """
    n, m, b4 = packed.shape
    b = b4 * 4
    d = h.shape[0]
    h2 = pad_axis_to_multiple(h.astype(jnp.float32), b).reshape(-1, b)
    if h2.shape[0] != m:
        raise ValueError(f"h rows {h2.shape[0]} != packed rows {m}")
    packed = pad_axis_to_multiple(packed, tile_m, axis=1)
    scales = pad_axis_to_multiple(scales, tile_m, axis=1)
    h2 = pad_axis_to_multiple(h2, tile_m, axis=0)
    mp = packed.shape[1]

    ghat, newh = pl.pallas_call(
        functools.partial(_kernel_apply, n=n, alpha=float(alpha)),
        name="unpack_reduce_apply",
        grid=(mp // tile_m, n),
        in_specs=_payload_specs(tile_m, b4) + [
            pl.BlockSpec((tile_m, b), lambda j, i: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_m, b), lambda j, i: (j, 0)),
            pl.BlockSpec((tile_m, b), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, b), jnp.float32),
            jax.ShapeDtypeStruct((mp, b), jnp.float32),
        ],
        compiler_params=DECODE_PARAMS,
        interpret=interpret,
    )(packed, scales, h2)
    return ghat.reshape(-1)[:d], newh.reshape(-1)[:d]
