"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) mixer.

Training/prefill uses the chunked SSD algorithm: quadratic attention-like
math *within* chunks (MXU-friendly einsums) and a linear recurrence *across*
chunks carried by ``lax.scan`` — the TPU-native formulation of the paper's
block-decomposition.  Decode keeps the O(1) recurrent state
``(B, H, P, N)`` plus a depthwise-conv ring of width-1 inputs.

Sequence length must divide ``chunk_size`` (all assigned shapes do).

On a TPU the training/prefill scan takes the fused Pallas kernel pair
:func:`repro.kernels.ssd.ssd_chunk_scan` (forward and backward; state and
per-chunk intermediates stay in VMEM, ``C Bᵀ`` once per group, B and C never
repeated to heads) where :func:`_fused_ssd` holds: the backend is a TPU,
every mesh axis of size > 1 is manual (each device holds whole arrays), and
the shapes tile — head dim 64 or 128, ``d_state`` a multiple of 128, chunk
128 or 256 dividing the sequence, and each group a whole number of 128-lane
head blocks (``mamba2-130m``: P=64, N=128, G=1, Q=256).  Everything else —
the CPU, ``jamba``'s ``d_state=16`` layers, and the single-token recurrent
decode (``cache`` given) — runs the XLA :func:`_ssd_chunked` below, unchanged.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ops import ssd_chunk_scan_op
from repro.kernels.ssd import supports as ssd_kernel_supports

from .sharding import arrays_are_local, shard

__all__ = ["init_mamba", "mamba_layer", "MambaCache", "init_mamba_cache"]


class MambaCache(NamedTuple):
    conv: jax.Array    # (B, W-1, conv_channels) — last inputs for the causal conv
    ssm: jax.Array     # (B, H, P, N) — recurrent state
    pos: jax.Array


def _dims(cfg):
    sc = cfg.ssm
    d_in = sc.d_inner(cfg.d_model)
    h = sc.n_heads(cfg.d_model)
    return sc, d_in, h, sc.head_dim, sc.d_state, sc.n_groups


def init_mamba(key, cfg, dtype) -> dict:
    sc, d_in, h, p, n, g = _dims(cfg)
    conv_ch = d_in + 2 * g * n
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(cfg.d_model)
    d_proj = 2 * d_in + 2 * g * n + h       # z, x, B, C, dt
    return {
        "in_proj": (jax.random.normal(k1, (cfg.d_model, d_proj)) * s).astype(dtype),
        "conv_w": (jax.random.normal(k2, (sc.conv_width, conv_ch)) / math.sqrt(sc.conv_width)).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "norm_scale": jnp.ones((d_in,), dtype),
        "out_proj": (jax.random.normal(k4, (d_in, cfg.d_model)) * (1.0 / math.sqrt(d_in)) / math.sqrt(2 * cfg.n_layers)).astype(dtype),
    }


def init_mamba_cache(cfg, batch: int, dtype) -> MambaCache:
    sc, d_in, h, p, n, g = _dims(cfg)
    conv_ch = d_in + 2 * g * n
    return MambaCache(
        conv=jnp.zeros((batch, sc.conv_width - 1, conv_ch), dtype),
        ssm=jnp.zeros((batch, h, p, n), jnp.float32),
        pos=jnp.zeros((), jnp.int32),
    )


def _segsum(at):
    """Stable segment-sum: (..., Q) -> (..., Q, Q) lower-triangular cumulative sums."""
    q = at.shape[-1]
    cs = jnp.cumsum(at, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    idx = jnp.arange(q)
    mask = idx[:, None] >= idx[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def _ssd_chunked(xt, at, b_, c_, chunk: int, unroll: bool = False):
    """Chunked SSD scan.

    xt: (B, L, H, P) — dt-discretised inputs (x * dt)
    at: (B, L, H)    — dt-discretised log-decays (A * dt, negative)
    b_, c_: (B, L, H, N) — input/output projections (already group-broadcast)
    Returns y: (B, L, H, P).
    """
    bsz, l, h, p = xt.shape
    n = b_.shape[-1]
    assert l % chunk == 0, f"seq {l} not divisible by chunk {chunk}"
    c = l // chunk

    def r(t):  # (B, L, ...) -> (B, C, Q, ...)
        return t.reshape(bsz, c, chunk, *t.shape[2:])

    xt, at, b_, c_ = r(xt), r(at), r(b_), r(c_)
    at = at.astype(jnp.float32)

    # --- intra-chunk (quadratic, MXU): Y_diag = (C B^T ∘ L) X
    lmat = jnp.exp(_segsum(jnp.moveaxis(at, -1, 2)))            # (B,C,H,Q,Q)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", c_.astype(jnp.float32), b_.astype(jnp.float32))
    y_diag = jnp.einsum("bchqk,bchqk,bckhp->bcqhp", scores, lmat, xt.astype(jnp.float32))

    # --- chunk states: what each chunk contributes to the running state
    a_cum = jnp.cumsum(at, axis=2)                               # (B,C,Q,H)
    a_tot = a_cum[:, :, -1]                                      # (B,C,H)
    decay_states = jnp.exp(a_tot[:, :, None] - a_cum)            # (B,C,Q,H)
    states = jnp.einsum("bcqhn,bcqh,bcqhp->bchpn", b_.astype(jnp.float32), decay_states, xt.astype(jnp.float32))

    # --- inter-chunk recurrence (linear scan over chunks)
    def step(carry, inp):
        st, a_t = inp                                            # (B,H,P,N), (B,H)
        new = carry * jnp.exp(a_t)[:, :, None, None] + st
        return new, carry                                        # emit state BEFORE this chunk

    init = jnp.zeros((bsz, h, p, n), jnp.float32)
    _, prev_states = jax.lax.scan(
        step, init, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(a_tot, 1, 0)),
        unroll=c if unroll else 1,
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)                # (B,C,H,P,N)

    # --- inter-chunk output: Y_off = C · (decay_in * prev_state)
    decay_out = jnp.exp(a_cum)                                   # (B,C,Q,H)
    y_off = jnp.einsum("bcqhn,bcqh,bchpn->bcqhp", c_.astype(jnp.float32), decay_out, prev_states)

    return (y_diag + y_off).reshape(bsz, l, h, p)


def _fused_ssd(cfg, s: int, chunk: int) -> bool:
    """Whether ``mamba_layer`` takes the fused SSD kernel (module docstring)."""
    sc, d_in, h, p, n, g = _dims(cfg)
    return (jax.default_backend() == "tpu" and arrays_are_local()
            and ssd_kernel_supports(seq=s, chunk=chunk, n_heads=h, head_dim=p,
                                    d_state=n, n_groups=g))


def _split_proj(proj, cfg):
    sc, d_in, h, p, n, g = _dims(cfg)
    z, x, b_, c_, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + g * n, 2 * d_in + 2 * g * n], axis=-1
    )
    return z, x, b_, c_, dt


def _conv_full(params, u, cfg):
    """Causal depthwise conv over (B, L, CH) with width W."""
    w = params["conv_w"].astype(jnp.float32)                     # (W, CH)
    width = w.shape[0]
    up = jnp.pad(u.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(up[:, i : i + u.shape[1]] * w[i] for i in range(width))
    return jax.nn.silu(out + params["conv_b"].astype(jnp.float32)).astype(cfg.compute_dtype)


def mamba_layer(
    params, x, cfg, cache: Optional[MambaCache] = None
) -> Tuple[jax.Array, Optional[MambaCache]]:
    """x: (B, S, D) -> (out, new_cache).  cache=None: chunked SSD (train/prefill);
    else single-token recurrent decode."""
    sc, d_in, h, p, n, g = _dims(cfg)
    bsz, s, _ = x.shape
    rep = h // g

    proj = x @ params["in_proj"].astype(cfg.compute_dtype)       # (B,S,dproj)
    z, xr, braw, craw, dt_raw = _split_proj(proj, cfg)
    conv_in = jnp.concatenate([xr, braw, craw], axis=-1)

    if cache is None:
        conv_out = _conv_full(params, conv_in, cfg)
        new_cache = None
    else:
        assert s == 1
        hist = jnp.concatenate([cache.conv.astype(cfg.compute_dtype), conv_in], axis=1)
        w = params["conv_w"].astype(jnp.float32)
        out = jnp.einsum("bwc,wc->bc", hist.astype(jnp.float32), w)
        conv_out = jax.nn.silu(out + params["conv_b"].astype(jnp.float32))[:, None].astype(cfg.compute_dtype)
        new_conv = hist[:, 1:]
    xr, braw, craw = jnp.split(conv_out, [d_in, d_in + g * n], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])     # (B,S,H)
    a = -jnp.exp(params["A_log"])                                # (H,)
    chunk = min(sc.chunk_size, s)

    if cache is None and _fused_ssd(cfg, s, chunk):
        # Heads stay side by side in (B, S, H*P), as the kernel reads and
        # writes them: no relayout to (B, S, H, P) and back.
        y = ssd_chunk_scan_op(xr, dt, a, braw, craw, chunk=chunk, n_groups=g)
        y = y + jnp.repeat(params["D"], p) * xr.astype(jnp.float32)
    else:
        xt = xr.reshape(bsz, s, h, p)
        xt = shard(xt, "batch", None, "model", None)
        bmat = braw.reshape(bsz, s, g, n)
        cmat = craw.reshape(bsz, s, g, n)
        bh = jnp.repeat(bmat, rep, axis=2)                       # (B,S,H,N)
        ch = jnp.repeat(cmat, rep, axis=2)
        if cache is None:
            y = _ssd_chunked(
                xt.astype(jnp.float32) * dt[..., None],
                a * dt,
                bh,
                ch,
                chunk,
                unroll=getattr(cfg, "scan_unroll", False),
            )
        else:
            dt0 = dt[:, 0]                                       # (B,H)
            decay = jnp.exp(a * dt0)                             # (B,H)
            xin = xt[:, 0].astype(jnp.float32) * dt0[..., None]  # (B,H,P)
            new_ssm = (
                cache.ssm * decay[:, :, None, None]
                + xin[..., None] * bh[:, 0, :, None, :].astype(jnp.float32)
            )
            y = jnp.einsum("bhpn,bhn->bhp", new_ssm, ch[:, 0].astype(jnp.float32))[:, None]
            new_cache = MambaCache(conv=new_conv, ssm=new_ssm, pos=cache.pos + 1)

        y = y + params["D"][:, None] * xt.astype(jnp.float32)
        y = y.reshape(bsz, s, d_in)

    # gated RMSNorm (mamba2): norm(y * silu(z))
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(gated * gated, axis=-1, keepdims=True)
    yn = gated * jax.lax.rsqrt(var + cfg.norm_eps) * params["norm_scale"].astype(jnp.float32)

    out = yn.astype(cfg.compute_dtype) @ params["out_proj"].astype(cfg.compute_dtype)
    return shard(out, "batch", None, None), new_cache
