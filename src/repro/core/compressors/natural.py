"""Natural compression — unbiased power-of-two exponent rounding (9 bits/dim).

``C_nat(x)`` keeps the sign and rounds ``|x|`` to one of its two enclosing
powers of two, up with probability ``(|x| - 2^(e-1)) / 2^(e-1)`` — exactly the
mantissa-dropping scheme of Horvath et al. 2019 ("Natural Compression for
Distributed Deep Learning"): unbiased, variance bound ``omega = 1/8``, and a
wire cost of sign + 8-bit exponent = 9 bits/dim regardless of vector length.

Wire format: one signed exponent code per coordinate in ``Payload.packed``
(int16 container; the logical payload is the 9-bit sign+exponent).  Code 0 is
an exact zero; otherwise ``code = sign * (exponent + _BIAS)``.

With its default alpha ``1/(1 + omega) = 8/9`` it drops straight into DIANA's
memory loop (the variance-reduction composition of Horvath et al.'s follow-up,
arXiv:1904.05115), converging linearly to the exact optimum in batch mode.

Kernel capability: with ``use_kernel=True`` the encode routes through
``nat_pack`` — the same stochastic exponent rounding computed from the float's
exponent/mantissa BITS instead of ``frexp`` (bitwise-equal given the same
``jax.random.bits`` draw; on compiled TPU the ``nat_pack_prng`` variant draws
the bits in-kernel) — and the server decode through the streaming
``nat_decode_sum(+apply)`` accumulator, which fuses DIANA's memory update into
the last grid step.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..quantization import pow2, uniform_from_bits
from .base import Compressor, Payload

__all__ = ["NaturalCompressor"]

# Exponent bias for the int16 code: f32 frexp exponents live in [-148, 128],
# so code magnitudes stay within [1, _BIAS + 128] << int16 range.
_BIAS = 160
OMEGA_NAT = 1.0 / 8.0


class NaturalCompressor(Compressor):
    name = "natural"
    unbiased = True
    kernel_oracle = "repro.kernels.ref::ref_nat_pack"

    def __init__(
        self,
        *,
        alpha: Optional[float] = None,
        memory: bool = True,
        use_kernel: Optional[bool] = None,
    ):
        self.alpha = alpha
        self.carries_state = memory
        # Capability auto-resolution: the natural kernels are Mosaic-shaped
        # (lane-aligned tiles, elementwise bodies), so auto engages on TPU
        # like the ternary family; interpret=True stays an explicit opt-in.
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        self.use_kernel = use_kernel

    # ---------------------------------------------------------------- wire

    @staticmethod
    def _encode(x: jax.Array, u: jax.Array) -> Payload:
        """PRNG-free encode body given the uniform draws (shared with the
        bucketed path, which concatenates per-segment draws)."""
        mant, expo = jnp.frexp(x)                     # x = mant * 2^expo, |mant| in [0.5, 1)
        # |x| in [2^(e-1), 2^e): round up to 2^e w.p. 2|mant| - 1 (unbiased)
        p_up = 2.0 * jnp.abs(mant) - 1.0
        chosen = expo - 1 + (u < p_up).astype(expo.dtype)
        sign = jnp.sign(x).astype(jnp.int16)
        code = sign * (chosen.astype(jnp.int16) + jnp.int16(_BIAS))
        return Payload(packed=jnp.where(x == 0.0, jnp.int16(0), code))

    def _draw_bits(self, key: jax.Array, shape) -> jax.Array:
        return jax.random.bits(key, shape, dtype=jnp.uint32)

    def compress(self, delta: jax.Array, key: jax.Array) -> Payload:
        x = delta.astype(jnp.float32)
        if self.use_kernel:
            from repro.kernels import ops as _kops

            if _kops.default_interpret():
                bits = self._draw_bits(key, x.shape)
                return Payload(packed=_kops.nat_pack_op(x, bits))
            # Compiled TPU: bits drawn in-kernel — no (d,) uint32 operand.
            return Payload(packed=_kops.nat_pack_prng_op(x, key))
        bits = self._draw_bits(key, x.shape)
        return self._encode(x, uniform_from_bits(bits))

    def decode(self, payload: Payload, d: int) -> jax.Array:
        code = payload.packed
        mag = pow2(jnp.abs(code).astype(jnp.int32) - _BIAS)
        return jnp.where(
            code == 0, 0.0, jnp.sign(code).astype(jnp.float32) * mag
        )[:d]

    def decode_sum(self, gathered: Payload, n: int, d: int) -> jax.Array:
        """Streaming decode+accumulate over workers (kernel) or the base
        sequential loop — identical f32 recurrence, bitwise-interchangeable."""
        if not self.use_kernel:
            return super().decode_sum(gathered, n, d)
        from repro.kernels import ops as _kops

        return _kops.nat_decode_sum_op(gathered.packed)[:d]

    def decode_sum_apply(self, gathered: Payload, n: int, d: int, h_server):
        """Fused decode_sum + DIANA server update in one kernel launch: the
        memory epilogue runs on the accumulator tile at the last grid step."""
        if not self.use_kernel:
            return super().decode_sum_apply(gathered, n, d, h_server)
        from repro.kernels import ops as _kops

        if self.carries_state:
            return _kops.nat_decode_sum_apply_op(
                gathered.packed, h_server, alpha=self.memory_alpha(d)
            )
        return _kops.nat_decode_sum_mean_op(gathered.packed)[:d], h_server

    def bits_per_dim(self, d: Optional[int] = None) -> float:
        return 9.0  # sign + 8-bit exponent (int16 is only the container)

    # ------------------------------------------------- bucketed (flat) path

    def compress_bucketed_keys(self, layout, delta: jax.Array,
                               keys: jax.Array, fallback_key=None) -> Payload:
        """ONE vectorized encode over the whole buffer; per-segment bits
        drawn with the per-leaf key schedule so codes match the per-leaf path
        bitwise (alignment is 1: segments are unpadded and contiguous)."""
        x = delta.astype(jnp.float32)
        if self.use_kernel:
            from repro.kernels import ops as _kops

            if not _kops.default_interpret():
                # One whole-buffer in-kernel PRNG stream from fallback_key
                # (distribution-equal, the documented compiled-TPU exception).
                if fallback_key is None:
                    fallback_key = keys[0]
                return Payload(packed=_kops.nat_pack_prng_op(x, fallback_key))
        bits = jnp.concatenate([
            self._draw_bits(k, (s,))
            for k, s in zip(keys, layout.padded_sizes)
        ])
        if self.use_kernel:
            from repro.kernels import ops as _kops

            return Payload(packed=_kops.nat_pack_op(x, bits))
        return self._encode(x, uniform_from_bits(bits))

    def decode_bucketed(self, layout, payload: Payload) -> jax.Array:
        return self.decode(payload, layout.padded_size)

    def decode_sum_bucketed(self, layout, gathered: Payload, n: int) -> jax.Array:
        return self.decode_sum(gathered, n, layout.padded_size)

    def decode_sum_apply_bucketed(self, layout, gathered, n, h_server):
        """Alpha is d-independent for natural compression, so the per-leaf
        fused kernel serves the flat buffer unchanged."""
        return self.decode_sum_apply(gathered, n, layout.padded_size, h_server)

    # -------------------------------------------------------- memory rule

    def memory_alpha(self, d: Optional[int] = None) -> float:
        if not self.carries_state:
            return 0.0
        return self.alpha if self.alpha is not None else 1.0 / (1.0 + OMEGA_NAT)
