"""Decoder-only model family: embeddings + scanned block stack + LM head.

One code path serves all 10 assigned architectures through the config's
``pattern`` (a repeating tuple of LayerSpec), covering dense GQA transformers,
MoE variants, pure-SSM (mamba2), the Jamba hybrid interleave and the VLM /
audio stub-frontend models.

The layer stack lowers as ``lax.scan`` over ``n_blocks`` copies of the pattern
(stacked params) with configurable activation checkpointing — this keeps HLO
size O(pattern) instead of O(layers) so 52B-param graphs compile quickly in
the 512-device dry-run, and the remat policy is a §Perf knob.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import mamba2 as M
from . import moe as MOE
from .sharding import shard

__all__ = [
    "init_model", "forward", "train_loss", "init_caches", "decode_step",
    "count_params", "model_flops_per_token", "FRONTEND_DIM",
]

FRONTEND_DIM = {"vision": 1024, "audio": 128}   # stub encoder output dims


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(key, spec, cfg, dtype) -> Dict[str, Any]:
    kmix, kmlp, kn1, kn2 = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": L.init_rms_norm(cfg.d_model, dtype)}
    if spec.mixer == "attn":
        p["mixer"] = L.init_attention(kmix, cfg, dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = M.init_mamba(kmix, cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.mlp != "none":
        p["norm2"] = L.init_rms_norm(cfg.d_model, dtype)
        p["mlp"] = MOE.init_moe(kmlp, cfg, dtype) if spec.mlp == "moe" else L.init_mlp(kmlp, cfg, dtype)
    return p


def init_model(cfg, key) -> Dict[str, Any]:
    dtype = cfg.param_dtype
    keys = jax.random.split(key, 8)
    vpad, d = cfg.padded_vocab, cfg.d_model

    params: Dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (vpad, d)) * 0.02).astype(dtype),
        "final_norm": L.init_rms_norm(d, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(keys[1], (d, vpad)) * 0.02).astype(dtype)
    if cfg.frontend != "none":
        fdim = FRONTEND_DIM[cfg.frontend]
        params["frontend_proj"] = {
            "w": (jax.random.normal(keys[2], (fdim, d)) / math.sqrt(fdim)).astype(dtype),
            "b": jnp.zeros((d,), dtype),
        }

    # stacked per-pattern-position params: leading dim n_blocks
    def init_block(bkey):
        lkeys = jax.random.split(bkey, len(cfg.pattern))
        return {
            f"layer{i}": _init_layer(lkeys[i], spec, cfg, dtype)
            for i, spec in enumerate(cfg.pattern)
        }

    bkeys = jax.random.split(keys[3], cfg.n_blocks)
    blocks = [init_block(k) for k in bkeys]
    params["blocks"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return params


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int, *, window: Optional[int] = None):
    """Stacked (over n_blocks) tuple-of-pattern-position caches."""
    def one_block():
        caches = []
        for spec in cfg.pattern:
            if spec.mixer == "attn":
                caches.append(
                    L.init_attn_cache(cfg, batch, max_len, cfg.compute_dtype, window=window)
                )
            else:
                caches.append(M.init_mamba_cache(cfg, batch, cfg.compute_dtype))
        return tuple(caches)

    blocks = [one_block() for _ in range(cfg.n_blocks)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _block_apply(bparams, x, cfg, positions, bcaches, window):
    """Apply one pattern block. bcaches: tuple aligned with cfg.pattern or None."""
    aux = jnp.zeros((), jnp.float32)
    new_caches = []
    for i, spec in enumerate(cfg.pattern):
        lp = bparams[f"layer{i}"]
        cache_i = bcaches[i] if bcaches is not None else None
        with jax.named_scope("model.mixer"):
            h = L.rms_norm(lp["norm1"], x, cfg.norm_eps)
            if spec.mixer == "attn":
                mix, nc = L.attention(lp["mixer"], h, cfg, positions, cache=cache_i, window=window)
            else:
                mix, nc = M.mamba_layer(lp["mixer"], h, cfg, cache=cache_i)
            x = x + mix
        if spec.mlp != "none":
            with jax.named_scope("model.mlp"):
                h2 = L.rms_norm(lp["norm2"], x, cfg.norm_eps)
                if spec.mlp == "moe":
                    y, a = MOE.moe_layer(lp["mlp"], h2, cfg)
                    aux = aux + a
                else:
                    y = L.mlp(lp["mlp"], h2, cfg)
                x = x + y
        new_caches.append(nc)
    return x, aux, (tuple(new_caches) if bcaches is not None else None)


def _embed_inputs(params, batch, cfg):
    """Assemble the input embedding sequence (frontend stubs prepended)."""
    parts = []
    if cfg.frontend == "vision" and "vision_embeds" in batch:
        fp = params["frontend_proj"]
        v = batch["vision_embeds"].astype(cfg.compute_dtype)
        parts.append(v @ fp["w"].astype(cfg.compute_dtype) + fp["b"].astype(cfg.compute_dtype))
    if cfg.frontend == "audio" and "audio_embeds" in batch:
        fp = params["frontend_proj"]
        a = batch["audio_embeds"].astype(cfg.compute_dtype)
        parts.append(a @ fp["w"].astype(cfg.compute_dtype) + fp["b"].astype(cfg.compute_dtype))
    if "tokens" in batch:
        emb = params["embed"].astype(cfg.compute_dtype)
        parts.append(emb[batch["tokens"]])
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    return shard(x, "batch", None, None)


def forward(
    params,
    batch: Dict[str, jax.Array],
    cfg,
    *,
    caches=None,
    window: Optional[int] = None,
    positions: Optional[jax.Array] = None,
    last_token_only: bool = False,
    return_hidden: bool = False,
):
    """Returns (logits (B, S, padded_vocab) f32, aux_loss, new_caches).

    ``last_token_only`` computes logits for the final position only — the
    serving prefill path, which avoids materialising the (B, S, V) tensor.
    ``return_hidden`` skips the LM head and returns the final hidden states
    (the chunked-CE training path computes logits per sequence chunk).

    Named scopes (``model.embed``, ``model.blocks``, ``model.head_loss``,
    and ``model.mixer`` / ``model.mlp`` per layer) label the compiled
    operations for the profiler; they are metadata only."""
    with jax.named_scope("model.embed"):
        x = _embed_inputs(params, batch, cfg)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    body = partial(_block_apply, cfg=cfg, window=window)
    if cfg.remat == "full":
        body = jax.checkpoint(body, static_argnums=())
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )

    def scan_fn(carry, xs):
        x, aux = carry
        if caches is None:
            bparams = xs
            x, a, _ = body(bparams, x, positions=positions, bcaches=None)
            return (x, aux + a), None
        bparams, bcaches = xs
        x, a, ncaches = body(bparams, x, positions=positions, bcaches=bcaches)
        return (x, aux + a), ncaches

    xs = params["blocks"] if caches is None else (params["blocks"], caches)
    with jax.named_scope("model.blocks"):
        (x, aux), new_caches = jax.lax.scan(
            scan_fn,
            (x, jnp.zeros((), jnp.float32)),
            xs,
            unroll=cfg.n_blocks if getattr(cfg, "scan_unroll", False) else 1,
        )

    with jax.named_scope("model.head_loss"):
        if last_token_only:
            x = x[:, -1:]
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        if return_hidden:
            return x, aux, new_caches
        head = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ).astype(cfg.compute_dtype)
        logits = (x @ head).astype(jnp.float32)
        logits = shard(logits, "batch", None, "model")
    return logits, aux, new_caches


# ---------------------------------------------------------------------------
# Training loss / decode step
# ---------------------------------------------------------------------------

CE_SEQ_CHUNK = 512


def train_loss(params, batch, cfg, *, window: Optional[int] = None):
    """Next-token CE (+ MoE aux). For frontend models the loss covers the token
    span only (frontend positions are context).

    The CE is computed per SEQUENCE CHUNK over the final hidden states so the
    (B, S, V) logits are never materialised — at nemotron's 256k vocab they
    are ~17 GiB/device even sharded.  Within a chunk, masked-sum CE replaces
    take_along_axis (a gather into the model-sharded vocab dim crashes XLA's
    SPMD partitioner under manual subgroups); the (B, cs, V) intermediates
    are constrained to keep the vocab dim sharded."""
    x, aux, _ = forward(params, batch, cfg, window=window, return_hidden=True)
    with jax.named_scope("model.head_loss"):
        if "labels" in batch:
            labels = batch["labels"]
        else:
            labels = batch["tokens"][:, 1:]
            x = x[:, :-1]
        if cfg.frontend != "none" and "tokens" in batch and x.shape[1] != labels.shape[1]:
            x = x[:, -labels.shape[1]:]                      # drop frontend positions
        head = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ).astype(cfg.compute_dtype)

        def ce_chunk(args):
            xc, lc = args                                    # (B, cs, D), (B, cs)
            logits = shard((xc @ head).astype(jnp.float32), "batch", None, "model")
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            vocab_iota = jnp.arange(logits.shape[-1], dtype=lc.dtype)
            mask = shard(lc[..., None] == vocab_iota, "batch", None, "model")
            picked = jnp.sum(jnp.where(mask, logits, 0.0), axis=-1)
            return jnp.sum(logz - picked)

        b, s, d = x.shape
        cs = CE_SEQ_CHUNK
        if s > cs and s % cs == 0:
            nc = s // cs
            xb = jnp.moveaxis(x.reshape(b, nc, cs, d), 1, 0)
            lb = jnp.moveaxis(labels.reshape(b, nc, cs), 1, 0)
            fn = jax.checkpoint(ce_chunk)
            if getattr(cfg, "scan_unroll", False):
                total = sum(fn((xb[i], lb[i])) for i in range(nc))
            else:
                total = jnp.sum(jax.lax.map(fn, (xb, lb)))
        else:
            total = ce_chunk((x, labels))
        return total / labels.size + aux


def decode_step(params, tokens, caches, cfg, *, window: Optional[int] = None):
    """One decode step: tokens (B, 1) int32 -> (logits (B,1,V), new_caches)."""
    # position comes from a cache counter (all layers stay in sync)
    pos = _extract_pos(caches)
    b = tokens.shape[0]
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    logits, _, new_caches = forward(
        params, {"tokens": tokens}, cfg, caches=caches, window=window, positions=positions
    )
    return logits, new_caches


def _extract_pos(caches):
    """All per-layer caches carry a synchronized 'pos' scalar; grab one."""
    def first_cache(t):
        if isinstance(t, (L.AttnCache, M.MambaCache)):
            return t
        if isinstance(t, tuple):
            for e in t:
                c = first_cache(e)
                if c is not None:
                    return c
        return None

    c = first_cache(caches)
    # caches are stacked over blocks -> pos has leading dim n_blocks
    return c.pos[0] if c.pos.ndim else c.pos


# ---------------------------------------------------------------------------
# Accounting
# ---------------------------------------------------------------------------

def count_params(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def count_active_params(cfg, params) -> int:
    """Active params per token (MoE: top_k of n_experts)."""
    total = count_params(params)
    if cfg.moe is None:
        return total
    moe_leaves = 0
    blocks = params["blocks"]
    for i, spec in enumerate(cfg.pattern):
        if spec.mlp == "moe":
            lp = blocks[f"layer{i}"]["mlp"]
            moe_leaves += sum(
                x.size for k, x in _flat_items(lp) if k != "router"
            )
    frac = cfg.moe.top_k / cfg.moe.n_experts
    return int(total - moe_leaves * (1 - frac))


def _flat_items(d, prefix=""):
    for k, v in d.items():
        if isinstance(v, dict):
            yield from _flat_items(v, prefix + k + "/")
        else:
            yield k, v


def model_flops_per_token(cfg, params) -> float:
    """MODEL_FLOPS = 6 * N_active per token (dense) — roofline §."""
    return 6.0 * count_active_params(cfg, params)
