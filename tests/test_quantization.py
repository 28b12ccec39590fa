"""Unit tests for the p-quantization operators against the paper's theory:
unbiasedness + variance (Lemma 2), expected sparsity (Theorem 1), alpha_p
closed forms (Lemma 1)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    alpha_p,
    dequantize_blocks,
    expected_sparsity,
    lp_norm,
    quantization_variance,
    quantize_blocks,
)

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("p", [1.0, 2.0, math.inf])
@pytest.mark.parametrize("block", [64, 128, 1000])
def test_unbiased_and_moments(p, block):
    d = 1000
    x = jax.random.normal(KEY, (d,))
    n_samples = 3000
    ks = jax.random.split(jax.random.PRNGKey(1), n_samples)
    f = jax.jit(jax.vmap(
        lambda k: dequantize_blocks(quantize_blocks(x, k, p=p, block_size=block), shape=(d,))
    ))
    samp = np.asarray(f(ks))
    xv = np.asarray(x)

    # unbiasedness: per-coordinate CLT bound using the THEORETICAL variance
    # |x_j| (scale_l - |x_j|) from Lemma 2 (sample variance is 0 for coords
    # whose Bernoulli never fires, which breaks an empirical z-test).
    from repro.core.quantization import pad_to_blocks

    blocks = np.asarray(pad_to_blocks(x, block))
    scales = np.asarray(lp_norm(jnp.asarray(blocks), p, axis=-1))
    theo_var = (np.abs(blocks) * (scales[:, None] - np.abs(blocks))).reshape(-1)[:d]
    # floor the variance: near-deterministic coords (prob ~ 0 or ~ 1) break
    # the CLT normal approximation at this sample size
    z = np.abs(samp.mean(0) - xv) / np.sqrt(np.maximum(theo_var, 1e-3) / n_samples)
    assert np.max(z) < 6.0, f"bias z-score {np.max(z)}"

    # total variance matches Psi (Lemma 2, second claim) within 5%
    emp = float(((samp - xv) ** 2).sum(-1).mean())
    theo = float(quantization_variance(x, p, block))
    assert abs(emp - theo) / theo < 0.05

    # expected sparsity matches Theorem 1 within 5%
    emp_nnz = float((samp != 0).sum(-1).mean())
    theo_nnz = float(expected_sparsity(x, p, block))
    assert abs(emp_nnz - theo_nnz) / theo_nnz < 0.05


def test_sparsity_bound_thm1():
    """E||qhat||_0 = ||x||_1/||x||_p <= d^{1-1/p} (Thm 1, eq. 7)."""
    d = 512
    x = jax.random.normal(KEY, (d,))
    for p, bound in [(1.0, 1.0), (2.0, math.sqrt(d)), (math.inf, d)]:
        assert float(expected_sparsity(x, p, d)) <= bound + 1e-3


def test_values_are_ternary_times_scale():
    x = jax.random.normal(KEY, (256,))
    q = quantize_blocks(x, KEY, p=math.inf, block_size=64)
    assert q.signs.dtype == jnp.int8
    assert set(np.unique(np.asarray(q.signs))) <= {-1, 0, 1}
    dense = np.asarray(dequantize_blocks(q, shape=(256,)))
    scales = np.repeat(np.asarray(q.scales), 64)
    mask = dense != 0
    np.testing.assert_allclose(np.abs(dense[mask]), scales[mask], rtol=1e-6)


def test_zero_vector():
    q = quantize_blocks(jnp.zeros(128), KEY, p=2, block_size=64)
    assert float(jnp.abs(dequantize_blocks(q, shape=(128,))).max()) == 0.0


def test_infty_prob_is_valid():
    """p=inf: |x_j|/||x||_inf <= 1 always — all-equal blocks fire every coord."""
    x = jnp.ones(64)
    q = quantize_blocks(x, KEY, p=math.inf, block_size=64)
    assert int((q.signs != 0).sum()) == 64  # prob exactly 1 everywhere


def test_alpha_p_closed_forms():
    """Lemma 1: alpha_1 = 1/d, alpha_2 = 1/sqrt(d), alpha_inf = 2/(1+sqrt(d))."""
    for d in (2, 16, 100, 4096):
        assert alpha_p(1, d) == pytest.approx(1 / d)
        assert alpha_p(2, d) == pytest.approx(1 / math.sqrt(d))
        assert alpha_p(math.inf, d) == pytest.approx(2 / (1 + math.sqrt(d)))
        # monotone in p (Lemma 1)
        assert alpha_p(1, d) <= alpha_p(2, d) <= alpha_p(math.inf, d)
    # decreasing in d
    assert alpha_p(2, 10) > alpha_p(2, 100)
    assert alpha_p(math.inf, 10) > alpha_p(math.inf, 100)


def test_alpha_inf_is_tight():
    """The minimiser x = (1, a*, ..., a*) with a* = 1/(1+sqrt(d)) attains
    alpha_inf(d) (see the paper's Lemma 1 proof)."""
    d = 37
    a = 1.0 / (1.0 + math.sqrt(d))
    x = jnp.concatenate([jnp.ones(1), jnp.full((d - 1,), a)])
    ratio = float(jnp.sum(x * x) / (lp_norm(x, 1) * lp_norm(x, math.inf)))
    assert ratio == pytest.approx(alpha_p(math.inf, d), rel=1e-6)


def test_block_padding_roundtrip():
    """Non-multiple lengths zero-pad: dequant returns the original shape."""
    x = jax.random.normal(KEY, (7, 13))
    q = quantize_blocks(x, KEY, p=2, block_size=32)
    y = dequantize_blocks(q, shape=(7, 13))
    assert y.shape == (7, 13)


def test_pow2_is_exact_over_the_whole_f32_range():
    """Natural compression decodes through ``pow2``: every integer exponent
    from below the subnormals to past overflow gives numpy's exact value."""
    from repro.core.quantization import pow2

    e = np.arange(-160, 140, dtype=np.int32)
    with np.errstate(over="ignore"):
        want = np.ldexp(np.float32(1.0), e).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(jax.jit(pow2)(jnp.asarray(e))), want)
