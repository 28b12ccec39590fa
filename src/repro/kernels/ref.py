"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package is validated against the functions here across a
sweep of shapes / dtypes / norm powers (see ``tests/test_kernels.py`` and
``tests/test_kernel_coverage.py``) — bitwise under ``interpret=True``, which
is the CI contract (``tools/check_kernels.py`` enforces that every registry
operator names its oracle).

The oracles are deliberately written in the most literal jnp style (frexp for
natural compression, ``.at[].add`` scatters, sequential worker accumulation)
while the kernels use TPU-shaped bodies (exponent bit masks, ``pl.when``
accumulators).  Bitwise agreement between the two is therefore a real check
of the kernels' bit tricks, not a tautology.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.packing import pack2bit, unpack2bit

# The one bits->uniform map, shared with every fallback operator (re-exported
# here for the kernel tests; the definition lives with the quantizers so the
# operators never import the kernel package).
from repro.core.quantization import lp_norm, pow2, uniform_from_bits

__all__ = [
    "uniform_from_bits",
    "ref_quantize_pack",
    "ref_unpack_reduce",
    "ref_unpack_reduce_apply",
    "ref_nat_pack",
    "ref_nat_decode_sum",
    "ref_sparse_gather",
    "ref_sparse_decode_sum",
    "ref_dense_decode_sum",
    "ref_apply_server",
    "ref_ssd_chunk_scan",
]

NAT_BIAS = 160  # == repro.core.compressors.natural._BIAS (int16 code bias)


def ref_quantize_pack(delta: jax.Array, bits: jax.Array, p: float):
    """Fused block p-quantize + 2-bit pack oracle.

    delta: (m, B) f32 — one row per quantization block.
    bits:  (m, B) uint32 random bits.
    Returns (packed (m, B/4) uint8, scales (m, 1) f32).
    """
    scales = lp_norm(delta, p, axis=-1, keepdims=True)            # (m, 1)
    safe = jnp.where(scales > 0, scales, 1.0)
    probs = jnp.abs(delta) / safe
    u = uniform_from_bits(bits)
    xi = (u < probs).astype(jnp.int8)
    signs = jnp.sign(delta).astype(jnp.int8) * xi
    return pack2bit(signs), scales.astype(jnp.float32)


def ref_unpack_reduce(packed: jax.Array, scales: jax.Array) -> jax.Array:
    """Server-side decode: sum_i unpack(packed_i) * scales_i.

    packed: (n, m, B/4) uint8; scales: (n, m, 1) f32 -> (m, B) f32 sum,
    accumulated worker by worker from zeros — the exact recurrence of the
    ternary fallback ``decode_sum`` (a parallel ``jnp.sum`` reduces in a
    different association order and is NOT bitwise-comparable).
    """
    signs = unpack2bit(packed).astype(jnp.float32)                # (n, m, B)
    acc = jnp.zeros(signs.shape[1:], jnp.float32)
    for i in range(signs.shape[0]):
        acc = acc + signs[i] * scales[i]
    return acc


def ref_apply_server(s: jax.Array, n: int, h: jax.Array, alpha) -> tuple:
    """The fused-apply epilogue oracle: ``dm = s / n`` then the alpha-memory
    server rule ``(ghat, new_h) = (h + dm, h + alpha * dm)`` — exactly the
    composition ``Compressor.decode_sum_apply`` runs as its fallback.

    Compare under ``jax.jit``: XLA CPU contracts ``h + alpha * dm`` into an
    FMA inside any jitted graph (kernel epilogues and the jitted fallback
    alike, consistently), while op-by-op eager execution rounds the multiply
    separately — so eager-vs-jit differs by 1 ulp, jit-vs-jit is bitwise."""
    dm = s / jnp.float32(n)
    return h + dm, h + alpha * dm


def ref_unpack_reduce_apply(packed, scales, h, alpha, n: int):
    """Fused decode_sum + server update oracle for the ternary family."""
    s = ref_unpack_reduce(packed, scales).reshape(-1)[: h.shape[0]]
    return ref_apply_server(s, n, h, alpha)


def ref_nat_pack(x: jax.Array, bits: jax.Array) -> jax.Array:
    """Natural-compression encode oracle — the literal frexp formulation.

    x, bits: (d,) f32 / uint32 -> int16 sign*(exponent+NAT_BIAS) codes, 0 for
    exact zeros.  The kernel computes the same codes from the exponent BITS of
    the float representation (no frexp on the VPU); bitwise agreement between
    the two formulations is exact on all finite inputs including subnormals.
    """
    u = uniform_from_bits(bits)
    mant, expo = jnp.frexp(x)                     # |mant| in [0.5, 1)
    p_up = 2.0 * jnp.abs(mant) - 1.0              # exact (Sterbenz)
    chosen = expo - 1 + (u < p_up).astype(expo.dtype)
    sign = jnp.sign(x).astype(jnp.int16)
    code = sign * (chosen.astype(jnp.int16) + jnp.int16(NAT_BIAS))
    return jnp.where(x == 0.0, jnp.int16(0), code)


def _nat_decode(code: jax.Array) -> jax.Array:
    mag = pow2(jnp.abs(code).astype(jnp.int32) - NAT_BIAS)
    return jnp.where(code == 0, 0.0, jnp.sign(code).astype(jnp.float32) * mag)


def ref_nat_decode_sum(codes: jax.Array) -> jax.Array:
    """codes (n, d) int16 -> (d,) f32 — the sequential worker recurrence."""
    acc = _nat_decode(codes[0])
    for i in range(1, codes.shape[0]):
        acc = acc + _nat_decode(codes[i])
    return acc


def ref_sparse_gather(x: jax.Array, idx: jax.Array) -> jax.Array:
    """Compress-side value gather oracle: x (d,) f32, idx (k,) int -> (k,)."""
    return x[idx]


def ref_sparse_decode_sum(idx: jax.Array, values: jax.Array,
                          scale: jax.Array, d: int) -> jax.Array:
    """Sparse server decode: idx/values (n, k), scale (k,) -> (d,) f32 sum,
    accumulated worker by worker (the fallback scatter-add recurrence)."""

    def one(i):
        return jnp.zeros((d,), jnp.float32).at[idx[i]].add(values[i] * scale)

    acc = one(0)
    for i in range(1, idx.shape[0]):
        acc = acc + one(i)
    return acc


def ref_dense_decode_sum(values: jax.Array) -> jax.Array:
    """Dense (identity) decode: values (n, d) f32 -> (d,) sequential sum."""
    acc = values[0]
    for i in range(1, values.shape[0]):
        acc = acc + values[i]
    return acc


def ref_ssd_chunk_scan(x, dt, a, bm, cm, *, chunk: int, n_groups: int):
    """Oracle of :func:`repro.kernels.ssd.ssd_chunk_scan`: the model's XLA
    path, :func:`repro.models.mamba2._ssd_chunked`, fed as ``mamba_layer``
    feeds it (x dt, A dt, and B, C repeated from groups to heads).

    x (B, L, H*P) bf16, dt (B, L, H) f32, a (H,) f32, bm/cm (B, L, G*N) bf16
    -> y (B, L, H*P) f32."""
    from repro.models.mamba2 import _ssd_chunked

    bsz, l, h = dt.shape
    rep = h // n_groups

    def heads(t):
        return jnp.repeat(t.reshape(bsz, l, n_groups, -1), rep, axis=2)

    xt = x.reshape(bsz, l, h, -1).astype(jnp.float32) * dt[..., None]
    y = _ssd_chunked(xt, a * dt, heads(bm), heads(cm), chunk)
    return y.reshape(bsz, l, -1)
