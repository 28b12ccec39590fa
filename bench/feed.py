"""The training feed: synthetic token batches made from the seed.

The language is the trainer's synthetic one (``LMStream`` in the program's
data pipeline): each row follows ``x[t] = (a * x[t-1] + c) mod V`` with
``a = 3 + 2w``, ``c = 7 + 11w`` for the row's grammar ``w``, and a fraction
``noise`` of positions replaced by uniform tokens.  This copy draws the same
numbers in the same order, and computes a row in closed form instead of
position by position: from the last restart ``r`` (position 0 or a noise
token), ``x[r + k] = (a^k x[r] + c (1 + a + ... + a^(k-1))) mod V``.
Labels are the tokens shifted left by one, wrapping, as the trainer's.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def _affine_tables(a: int, c: int, v: int, s: int):
    """a^k mod v and c * (1 + ... + a^(k-1)) mod v for k = 0..s-1."""
    pw = np.empty(s, np.int64)
    geo = np.empty(s, np.int64)
    p, g = 1, 0
    for k in range(s):
        pw[k], geo[k] = p, g
        g = (g + c * p) % v
        p = (p * a) % v
    return pw, geo


def lm_tokens(vocab: int, seq: int, rows: int, seed: int, step: int,
              noise: float = 0.1, grammars: int = 1) -> np.ndarray:
    """(rows, seq) int32 tokens of one batch, deterministic in (seed, step)."""
    rng = np.random.default_rng(seed * 1_000_003 + step)
    worker = rng.integers(0, grammars, size=(rows, 1))
    first = rng.integers(0, vocab, size=rows)
    noise_mask = rng.random((rows, seq)) < noise
    noise_tok = rng.integers(0, vocab, size=(rows, seq))
    noise_mask[:, 0] = True
    noise_tok[:, 0] = first
    pos = np.arange(seq)
    last = np.maximum.accumulate(np.where(noise_mask, pos, 0), axis=1)
    k = pos[None, :] - last
    start = np.take_along_axis(noise_tok, last, axis=1)
    out = np.empty((rows, seq), np.int64)
    for w in np.unique(worker[:, 0]):
        sel = worker[:, 0] == w
        pw, geo = _affine_tables(3 + 2 * int(w), 7 + 11 * int(w), vocab, seq)
        out[sel] = (pw[k[sel]] * start[sel] + geo[k[sel]]) % vocab
    return out.astype(np.int32)


def worker_batches(traffic: Dict, vocab: int, seed: int, index: int) -> List[Dict[str, np.ndarray]]:
    """Batch ``index`` of the pool, one ``{"tokens", "labels"}`` per worker.
    Every worker and every index draws its own rows."""
    out = []
    n = traffic["workers"]
    for i in range(n):
        toks = lm_tokens(vocab, traffic["seq_len"], traffic["batch_per_worker"],
                         seed, index * n + i, traffic["noise"], traffic["grammars"])
        out.append({"tokens": toks, "labels": np.roll(toks, -1, axis=1)})
    return out


def global_batch(per_worker: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """Workers' rows stacked in worker order (the data axis shards rows)."""
    return {k: np.concatenate([b[k] for b in per_worker]) for k in per_worker[0]}
