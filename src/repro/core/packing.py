"""2-bit packing of ternary sign tensors for compressed collectives.

The paper communicates Elias-coded sparse vectors through MPI Gather; on TPU we
use fixed-width 2-bit codes (4 ternary values per int8 byte) so payloads have
static shapes and can be moved by a single all-gather.  Encoding: sign s in
{-1, 0, +1} -> code (s + 1) in {0, 1, 2}; code 3 is unused.  A row of ``L``
codes packs into ``L/4`` bytes by quarters: byte ``j`` holds the codes of
positions ``j, j + L/4, j + L/2, j + 3L/4`` at bits 0, 2, 4 and 6.  Each
quarter is a contiguous lane-slice, so packing and unpacking take slices and
shifts on 32-bit integers and never split lanes: the same functions run in
the jnp fallbacks and inside the Pallas kernels (Mosaic has no 8-bit vector
arithmetic and refuses lane-splitting reshapes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["pack2bit", "unpack2bit", "packed_nbytes", "PACK_FACTOR"]

PACK_FACTOR = 4  # ternary values per byte


def packed_nbytes(n: int) -> int:
    """Bytes needed for ``n`` ternary values."""
    return -(-n // PACK_FACTOR)


def pack2bit(signs: jax.Array) -> jax.Array:
    """Pack an integer {-1,0,1} tensor (..., L) into (..., L/4) uint8.

    Last dim must be a multiple of 4 (block sizes are; enforced statically).
    """
    if signs.shape[-1] % PACK_FACTOR:
        raise ValueError(f"last dim {signs.shape[-1]} not a multiple of {PACK_FACTOR}")
    codes = signs.astype(jnp.int32) + 1                         # {0,1,2}
    q = codes.shape[-1] // PACK_FACTOR
    packed = codes[..., :q]
    for k in range(1, PACK_FACTOR):
        packed = packed | (codes[..., k * q:(k + 1) * q] << (2 * k))
    return packed.astype(jnp.uint8)


def unpack2bit(packed: jax.Array, n: int | None = None,
               dtype=jnp.int8) -> jax.Array:
    """Inverse of :func:`pack2bit`: {-1,0,1} in ``dtype``, last dim 4x."""
    p = packed.astype(jnp.int32)
    out = jnp.concatenate(
        [((p >> (2 * k)) & 3) - 1 for k in range(PACK_FACTOR)], axis=-1
    ).astype(dtype)
    if n is not None:
        out = out[..., :n]
    return out
