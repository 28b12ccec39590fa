"""Operations and bytes the benchmark's work requires, computed from shapes.

These are the yardstick's counts, kept with the benchmark so that no change
to the program can change them.  They count what the algorithm requires,
not what an implementation happens to do: recomputation (remat), vocabulary
padding, capacity slots left empty and the upper triangle of a causal
product are not counted.
"""

from __future__ import annotations

import math
from typing import Dict


def _mixer_params(model, spec) -> int:
    d = model["d_model"]
    if spec["mixer"] == "mamba":
        s = model["ssm"]
        d_in = s["expand"] * d
        h = d_in // s["head_dim"]
        gn = s["n_groups"] * s["d_state"]
        conv = s["conv_width"] * (d_in + 2 * gn)          # depthwise MACs
        return d * (2 * d_in + 2 * gn + h) + conv + d_in * d
    h, hkv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    return d * (h + 2 * hkv) * dh + h * dh * d


def _mlp_params_active(model, spec) -> int:
    d = model["d_model"]
    if spec["mlp"] == "moe":
        m = model["moe"]
        return d * m["n_experts"] + m["top_k"] * 3 * d * m["d_ff"]
    if spec["mlp"] == "dense":
        return 3 * d * model["d_ff"]
    return 0


def matmul_params_per_token(model) -> int:
    """Weights each token multiplies once in the forward pass: every layer's
    projections (experts: only the top-k it is routed to, plus the router)
    and the head over the published vocabulary.  The embedding lookup is a
    gather and is not counted."""
    period = model["pattern"]
    reps = model["n_layers"] // len(period)
    per_block = sum(_mixer_params(model, s) + _mlp_params_active(model, s) for s in period)
    return reps * per_block + model["vocab"] * model["d_model"]


def sequence_flops_per_token(model, seq_len: int) -> float:
    """Forward operations per token of the sequence mixers beyond their
    projections: causal attention (a query at position t reads t + 1 keys)
    and Mamba-2's chunked SSD (causal within a chunk of ``chunk_size``)."""
    period = model["pattern"]
    reps = model["n_layers"] // len(period)
    total = 0.0
    for spec in period:
        if spec["mixer"] == "mamba":
            s = model["ssm"]
            d_in = s["expand"] * model["d_model"]
            h, p, n, g = d_in // s["head_dim"], s["head_dim"], s["d_state"], s["n_groups"]
            q = min(s["chunk_size"], seq_len)
            # C B^T per group and (C B^T o L) X per head, causal in the chunk;
            # each chunk's state B^T X and its readout C h, per head.
            total += g * n * (q + 1) + h * p * (q + 1) + 4 * h * n * p
        else:
            h, dh = model["n_heads"], model["head_dim"]
            total += 2 * h * dh * (seq_len + 1)           # QK^T and PV
    return reps * total


def train_flops_per_token(model, seq_len: int) -> float:
    """Forward and backward operations required per token: three times the
    forward pass (the backward pass takes two products per forward one)."""
    fwd = 2.0 * matmul_params_per_token(model) + sequence_flops_per_token(model, seq_len)
    return 3.0 * fwd


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's peaks from ``bench/peaks.json``; a kind not in the table is
    an error, never a default."""
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def param_count(model) -> int:
    """Parameters of the model as the program holds them (padded embedding)."""
    from .reference import Leaf, param_layout

    import jax

    return sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(
        param_layout(model), is_leaf=lambda x: isinstance(x, Leaf)))


def diana_kernel_bytes(n_params: int, workers: int, block: int) -> Dict[str, float]:
    """HBM bytes one chip's DIANA kernels must move in a step, by shape.

    encode: the float32 difference in, the payload out (2 bits a
    coordinate and one float32 scale a block); decode_sum_apply: every
    worker's payload in, the server memory in and out, the direction out.
    """
    d = float(n_params)
    payload = d / 4.0 + 4.0 * d / block
    encode = 4.0 * d + payload
    decode = workers * payload + 4.0 * d + 4.0 * d + 4.0 * d
    return {"encode": encode, "decode_sum_apply": decode, "total": encode + decode}
