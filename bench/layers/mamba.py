"""Mamba-2's mixer (arXiv:2405.21060), as a layer kind of the reference:
in-projection, causal depthwise convolution, the SSD scan by the paper's
minimal chunked listing, gated RMSNorm, out-projection.

The gated RMSNorm normalises ``y * silu(z)`` over each of the ``n_groups``
groups of ``d_in / n_groups`` channels apart, each group with its own mean
square, and scales by one vector of ``d_in``: mamba_ssm's ``Mamba2``
(``RMSNormGated(d_ssm, norm_before_gate=False, group_size=d_ssm //
ngroups)``), as transformers' Zamba2 copies it (``Zamba2RMSNormGated``).
With one group it is the RMSNorm over the whole inner width.

The yardstick's counts of one layer (``bench/flops.py``) are here too:
:func:`matmul_params` and :func:`sequence_flops`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import F32, HIGHEST, rms_norm

SSD_CHUNK = 128           # the reference's own SSD chunk (any size is exact)


def _dims(model):
    """The layer's sizes.  The inner width is ``expand * d_model`` (Mamba-2),
    or ``num_heads * head_dim`` where the configuration gives the heads
    (Nemotron-H's ``mamba_num_heads``: 64 heads of 64 beside a hidden size
    of 2,688, no whole multiple of it)."""
    d = model["d_model"]
    ssm = model["ssm"]
    d_in = ssm["num_heads"] * ssm["head_dim"] if "num_heads" in ssm else ssm["expand"] * d
    return dict(d=d, d_in=d_in, h=d_in // ssm["head_dim"], p=ssm["head_dim"],
                n=ssm["d_state"], g=ssm["n_groups"], w=ssm["conv_width"])


def matmul_params(model) -> int:
    """Weights each token multiplies once in a layer's forward pass: the
    in- and out-projections and the depthwise convolution's MACs."""
    m = _dims(model)
    d, d_in, gn = m["d"], m["d_in"], m["g"] * m["n"]
    conv = m["w"] * (d_in + 2 * gn)
    return d * (2 * d_in + 2 * gn + m["h"]) + conv + d_in * d


def sequence_flops(model, seq_len: int) -> int:
    """Forward operations per token of a layer beyond its projections:
    C B^T per group and (C B^T o L) X per head, causal within a chunk of
    ``chunk_size``; each chunk's state B^T X and its readout C h, per head."""
    m = _dims(model)
    h, p, n, g = m["h"], m["p"], m["n"], m["g"]
    q = min(model["ssm"]["chunk_size"], seq_len)
    return g * n * (q + 1) + h * p * (q + 1) + 4 * h * n * p


def _a_log(key, shape):
    """A uniform in [1, 16] (Mamba-2)."""
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))


def _dt_bias(key, shape):
    """softplus^-1 of dt, dt log-uniform in [1e-3, 1e-1]."""
    dt_ = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3), math.log(1e-1)))
    return dt_ + jnp.log(-jnp.expm1(-dt_))


INITS = {"a_log": _a_log, "dt_bias": _dt_bias}


def layout(model, stacked) -> dict:
    pdt = model["param_dtype"]
    m = _dims(model)
    d = m["d"]
    out_scale = 1.0 / math.sqrt(2 * model["n_layers"])
    conv_ch = m["d_in"] + 2 * m["g"] * m["n"]
    d_proj = 2 * m["d_in"] + 2 * m["g"] * m["n"] + m["h"]
    return {
        "in_proj": stacked((d, d_proj), pdt, "normal", 1 / math.sqrt(d)),
        "conv_w": stacked((m["w"], conv_ch), pdt, "normal", 1 / math.sqrt(m["w"])),
        "conv_b": stacked((conv_ch,), pdt, "zeros"),
        "dt_bias": stacked((m["h"],), "float32", "dt_bias"),
        "A_log": stacked((m["h"],), "float32", "a_log"),
        "D": stacked((m["h"],), "float32", "ones"),
        "norm_scale": stacked((m["d_in"],), pdt, "ones"),
        "out_proj": stacked((m["d_in"], d), pdt, "normal", out_scale / math.sqrt(m["d_in"])),
    }


def segsum(a):
    """(..., T) -> (..., T, T): entry [i, j] is a[j+1] + ... + a[i] for
    j <= i, and -inf above the diagonal (the stable form of the listing)."""
    t = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (t,))
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    cs = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), cs, -jnp.inf)


def ssd(x, a, b, c, chunk, dot):
    """Mamba-2's SSD, the paper's minimal chunked listing.

    x: (B, L, H, P) inputs already times dt; a: (B, L, H) log-decays (A dt);
    b, c: (B, L, G, N), head h reads group h // (H / G).  Returns (B, L, H, P).
    """
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    nc = l // chunk
    x = x.reshape(bsz, nc, chunk, h, p)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    a = jnp.moveaxis(a.reshape(bsz, nc, chunk, h), 3, 1)          # (B,H,C,Q)
    a_cs = jnp.cumsum(a, axis=-1)

    # within each chunk: (C B^T o L) X
    lmat = jnp.exp(segsum(a))                                     # (B,H,C,Q,Q)
    cb = dot("bclgn,bcsgn->bcgls", c, b)                          # (B,C,G,Q,Q)
    cb = jnp.repeat(cb, r, axis=2) * jnp.moveaxis(lmat, 1, 2)     # (B,C,H,Q,Q)
    y_diag = dot("bchls,bcshp->bclhp", cb, x)

    # each chunk's contribution to the state at its end
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)                 # (B,H,C,Q)
    bh = jnp.repeat(b, r, axis=3) * jnp.moveaxis(decay_states, 1, 3)[..., None]
    states = dot("bclhn,bclhp->bchpn", bh, x)                     # (B,C,H,P,N)

    # the state entering each chunk
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    a_tot = jnp.pad(a_cs[..., -1], ((0, 0), (0, 0), (1, 0)))      # (B,H,C+1)
    decay_chunk = jnp.exp(segsum(a_tot))                          # (B,H,C+1,C+1)
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states,
                        precision=HIGHEST)[:, :-1]

    # what the entering state adds to each position
    ch = jnp.repeat(c, r, axis=3) * jnp.moveaxis(jnp.exp(a_cs), 1, 3)[..., None]
    y_off = dot("bclhn,bchpn->bclhp", ch, states)
    return (y_diag + y_off).reshape(bsz, l, h, p)


def gated_rms_norm(y, z, scale, groups, eps):
    """RMSNorm of ``y * silu(z)`` over each of ``groups`` equal groups of the
    last axis apart, then times ``scale`` over the whole axis.  One group is
    ``rms_norm`` itself, without the reshape, whose compiled gradient rounds
    differently."""
    x = y * jax.nn.silu(z)
    if groups == 1:
        return rms_norm(x, scale, eps)
    *lead, width = x.shape
    x = x.reshape(*lead, groups, width // groups)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x.reshape(*lead, width) * scale


def apply(p, x, model, dot):
    m = _dims(model)
    d_in, h, hp, n, g, w = m["d_in"], m["h"], m["p"], m["n"], m["g"], m["w"]
    bsz, s, _ = x.shape
    proj = dot("bsd,de->bse", x, p["in_proj"])
    z, xr, braw, craw, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + g * n, 2 * d_in + 2 * g * n], axis=-1)
    u = jnp.concatenate([xr, braw, craw], axis=-1)
    up = jnp.pad(u, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(up[:, i:i + s] * p["conv_w"][i] for i in range(w)) + p["conv_b"]
    u = jax.nn.silu(conv)
    xr, braw, craw = jnp.split(u, [d_in, d_in + g * n], axis=-1)
    xs = xr.reshape(bsz, s, h, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"])                       # (B,S,H)
    a = -jnp.exp(p["A_log"])                                      # (H,)
    y = ssd(xs * dt[..., None], a * dt, braw.reshape(bsz, s, g, n),
            craw.reshape(bsz, s, g, n), min(SSD_CHUNK, s), dot)
    y = (y + p["D"][:, None] * xs).reshape(bsz, s, d_in)
    y = gated_rms_norm(y, z, p["norm_scale"], g, model["norm_eps"])
    return dot("bse,ed->bsd", y, p["out_proj"]), 0
