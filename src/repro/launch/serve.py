"""Serving: batched prefill + single-token decode steps under pure GSPMD.

decode_32k: 128 sequences, KV/SSM caches sharded over the batch dim.
long_500k:  batch=1 — the KV cache shards its *sequence* dim over the data
axes; distributed softmax (max/sum all-reduces) falls out of GSPMD, i.e.
flash-decoding-style sequence parallelism without manual collectives.
Attention-only archs run their sliding-window variant (ring-buffer cache of
``cfg.sliding_window``), SSM/hybrid archs use their native O(1) state.

DIANA is a training-time technique; serve steps do not compress (paper scope).
"""

from __future__ import annotations

import argparse
import functools
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_shape
from repro.models import decode_step, forward, init_caches, init_model
from repro.models.sharding import GSPMDPolicy, sharding_policy

from .mesh import make_mesh
from .sharding_rules import cache_specs, param_specs

__all__ = ["decode_window", "build_serve_step", "build_prefill", "serve_cache_shardings"]


def decode_window(cfg, shape) -> Optional[int]:
    """long_500k engages the sliding window on attention archs (hybrids keep
    full attention — their mamba layers carry the long context)."""
    if shape.name == "long_500k" and not cfg.has_mamba():
        return cfg.sliding_window
    return None


def serve_cache_shardings(cfg, mesh, shape):
    window = decode_window(cfg, shape)
    caches_shape = jax.eval_shape(
        lambda: init_caches(cfg, shape.global_batch, shape.seq_len, window=window)
    )
    specs = cache_specs(caches_shape, cfg, mesh, batch=shape.global_batch)
    return (
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs),
        caches_shape,
        window,
    )


def build_serve_step(cfg, mesh, shape):
    """jitted decode: (params, caches, tokens (B,1)) -> (logits, new_caches).

    bf16 caches are stored as bit-equal uint16 (see models.layers.AttnCache):
    integer dynamic-update-slice avoids the XLA-CPU bf16->f32 promotion that
    would otherwise triple the measured decode memory in the dry-run.
    """
    window = decode_window(cfg, shape)

    def step(params, caches, tokens):
        with sharding_policy(GSPMDPolicy(mesh)):
            logits, new_caches = decode_step(params, tokens, caches, cfg, window=window)
        return logits, new_caches

    return jax.jit(step, donate_argnums=(1,))


def build_prefill(cfg, mesh, shape):
    """jitted prefill forward returning next-token logits (B, 1, V) — full
    (B, S, V) logits would be ~0.5 TB at prefill_32k scale and no serving
    path needs them."""

    def step(params, batch):
        with sharding_policy(GSPMDPolicy(mesh)):
            logits, aux, _ = forward(params, batch, cfg, last_token_only=True)
        return logits

    return jax.jit(step)


# ---------------------------------------------------------------------------
# CLI: batched-request serving demo
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description="DIANA-framework serving demo")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--tokens", type=int, default=16, help="tokens to decode")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    args = ap.parse_args(argv)

    from repro.configs import reduced as make_reduced
    from repro.configs.base import ShapeConfig

    from .compile_cache import use_compile_cache

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
        shape = ShapeConfig("reduced-decode", args.cache_len, args.batch, "decode")
    else:
        shape = get_shape(args.shape)

    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    key = jax.random.PRNGKey(0)
    params = init_model(cfg, key)
    window = decode_window(cfg, shape)
    caches = init_caches(cfg, shape.global_batch, shape.seq_len, window=window)
    step_fn = build_serve_step(cfg, mesh, shape)

    tokens = jax.random.randint(key, (shape.global_batch, 1), 0, cfg.vocab)
    t0 = time.perf_counter()
    for i in range(args.tokens):
        logits, caches = step_fn(params, caches, tokens)
        tokens = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32) % cfg.vocab
    dt = time.perf_counter() - t0
    print(f"decoded {args.tokens} tokens x {shape.global_batch} seqs in {dt:.2f}s "
          f"({args.tokens * shape.global_batch / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
