"""The feed: the trainer's synthetic language, drawn from the seed."""

import numpy as np
import pytest

from bench import feed


def _by_position(vocab, seq, rows, seed, step, noise, grammars):
    """The language position by position, as its definition reads."""
    rng = np.random.default_rng(seed * 1_000_003 + step)
    worker = rng.integers(0, grammars, size=(rows, 1))
    a, c = 3 + 2 * worker, 7 + 11 * worker
    toks = np.empty((rows, seq), dtype=np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=rows)
    noise_mask = rng.random((rows, seq)) < noise
    noise_tok = rng.integers(0, vocab, size=(rows, seq))
    for t in range(1, seq):
        nxt = (toks[:, t - 1] * a[:, 0] + c[:, 0]) % vocab
        toks[:, t] = np.where(noise_mask[:, t], noise_tok[:, t], nxt)
    return toks.astype(np.int32)


@pytest.mark.parametrize("vocab,seq,rows,seed,step,grammars", [
    (50_280, 512, 3, 7, 0, 1), (49_155, 300, 2, 2**31 + 99, 5, 1), (1_000, 257, 5, 3, 2, 3)])
def test_closed_form_equals_the_recurrence(vocab, seq, rows, seed, step, grammars):
    want = _by_position(vocab, seq, rows, seed, step, 0.1, grammars)
    got = feed.lm_tokens(vocab, seq, rows, seed, step, 0.1, grammars)
    np.testing.assert_array_equal(got, want)


def test_pool_rows_all_differ_and_labels_shift():
    traffic = {"workers": 4, "seq_len": 128, "batch_per_worker": 2, "noise": 0.1,
               "grammars": 1}
    rows = []
    for j in range(3):
        per_worker = feed.worker_batches(traffic, 1000, 5, j)
        for b in per_worker:
            np.testing.assert_array_equal(b["labels"], np.roll(b["tokens"], -1, axis=1))
            rows += [tuple(r) for r in b["tokens"]]
        g = feed.global_batch(per_worker)
        assert g["tokens"].shape == (8, 128)
    assert len(set(rows)) == len(rows) == 24
    again = feed.worker_batches(traffic, 1000, 5, 2)[3]["tokens"]
    np.testing.assert_array_equal(again, feed.worker_batches(traffic, 1000, 5, 2)[3]["tokens"])
