"""Operations and bytes the benchmark's work requires, computed from shapes.

These are the yardstick's counts, kept with the benchmark so that no change
to the program can change them.  They count what the algorithm requires,
not what an implementation happens to do: recomputation (remat), vocabulary
padding, capacity slots left empty and the upper triangle of a causal
product are not counted.

A layer kind's counts come from its own file, ``bench/layers/<kind>.py``,
where it defines ``matmul_params(model)`` (the weights each token multiplies
once in one layer's forward pass, at this chip's share) and
``sequence_flops(model, seq_len)`` (one layer's forward operations per token
beyond those products); ``attn``, ``moe`` and ``dense`` keep the built-in
counts below where their file defines none.
"""

from __future__ import annotations

import math
import os
from typing import Dict, Optional


def _attn_params(model) -> int:
    d = model["d_model"]
    h, hkv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    return d * (h + 2 * hkv) * dh + h * dh * d


def _attn_sequence_flops(model, seq_len: int) -> int:
    """Causal attention: a query at position t reads t + 1 keys (QK^T, PV)."""
    return 2 * model["n_heads"] * model["head_dim"] * (seq_len + 1)


def _moe_params(model) -> int:
    """The router and the top-k SwiGLU experts a token is routed to."""
    d, m = model["d_model"], model["moe"]
    return d * m["n_experts"] + m["top_k"] * 3 * d * m["d_ff"]


def _dense_params(model) -> int:
    return 3 * model["d_model"] * model["d_ff"]


# the counts of the kinds that have no file of their own, or whose file
# defines none: (matmul_params, sequence_flops)
BUILT_IN = {"attn": (_attn_params, _attn_sequence_flops),
            "moe": (_moe_params, lambda model, seq_len: 0),
            "dense": (_dense_params, lambda model, seq_len: 0)}


def kind_counts(kind: str, layers: Optional[str] = None):
    """(matmul_params(model), sequence_flops(model, seq_len)) of one layer
    of a kind: those its file ``<layers>/<kind>.py`` defines, else the
    built-in ones.  A kind with neither is an error that names the file."""
    from . import cells

    layers = layers or os.path.join(cells.BENCH, "layers")
    path = os.path.join(layers, kind + ".py")
    mod = cells.load_module(path) if os.path.exists(path) else None
    built_in = BUILT_IN.get(kind, (None, None))
    counts = tuple(getattr(mod, name, None) or fn for name, fn in
                   zip(("matmul_params", "sequence_flops"), built_in))
    if None in counts:
        raise ValueError(f"no count for layer kind {kind!r}: define matmul_params and "
                         f"sequence_flops in {os.path.relpath(path, cells.ROOT)}")
    return counts


def _kinds(model):
    return [k for spec in model["pattern"] for k in (spec["mixer"], spec["mlp"]) if k != "none"]


def matmul_params_per_token(model, layers: Optional[str] = None) -> int:
    """Weights each token multiplies once in the forward pass: every layer's
    projections (experts: only the top-k it is routed to, plus the router)
    and the head over the published vocabulary.  The embedding lookup is a
    gather and is not counted."""
    reps = model["n_layers"] // len(model["pattern"])
    per_block = sum(kind_counts(k, layers)[0](model) for k in _kinds(model))
    return reps * per_block + model["vocab"] * model["d_model"]


def sequence_flops_per_token(model, seq_len: int, layers: Optional[str] = None) -> float:
    """Forward operations per token of the layers beyond their projections,
    such as causal attention's products or Mamba-2's chunked SSD."""
    reps = model["n_layers"] // len(model["pattern"])
    total = 0.0
    for k in _kinds(model):
        total += kind_counts(k, layers)[1](model, seq_len)
    return reps * total


def train_flops_per_token(model, seq_len: int, layers: Optional[str] = None) -> float:
    """Forward and backward operations required per token: three times the
    forward pass (the backward pass takes two products per forward one)."""
    fwd = (2.0 * matmul_params_per_token(model, layers)
           + sequence_flops_per_token(model, seq_len, layers))
    return 3.0 * fwd


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's peaks from ``bench/peaks.json``; a kind not in the table is
    an error, never a default."""
    import json

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
