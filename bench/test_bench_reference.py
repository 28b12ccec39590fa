"""The reference against the program at test size in float32, and its parts
against their definitions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import feed, program, tiny
from bench import reference as R


@pytest.mark.parametrize("family", ["mamba"])
def test_reference_matches_the_program_in_float32(family):
    spec = tiny.spec(family, f32=True)
    model = spec["config_doc"]["model"]
    cfg = program.model_config(spec["config_doc"], spec["traffic"])
    program.check_layout(cfg, R.param_layout(model))
    from repro.models import train_loss

    params = R.make_params(model, 2**31 + 17)
    b = feed.worker_batches(spec["traffic"], model["vocab"], 5, 0)[0]
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: train_loss(p, b, cfg))(params)
    lr, gr = R.loss_and_grad(params, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]), model)
    assert abs(float(lp) - float(lr)) < 1e-4
    for path, a, c in zip(R.leaf_paths(gp), jax.tree_util.tree_leaves(gp),
                          jax.tree_util.tree_leaves(gr)):
        rel = float(jnp.linalg.norm(a - c) / jnp.linalg.norm(c))
        assert rel < 1e-4, (path, rel)


def test_ssd_equals_the_recurrence():
    """h_t = exp(a_t) h_{t-1} + B_t x_t^T, y_t = C_t h_t, step by step."""
    rng = np.random.default_rng(0)
    b, l, h, p, g, n = 2, 64, 4, 3, 2, 5
    x = jnp.asarray(rng.standard_normal((b, l, h, p)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.01, 0.5, (b, l, h)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, l, g, n)), jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, l, g, n)), jnp.float32)
    got = R.ssd(x, a, bm, cm, 16, R.make_dot("f32"))
    bh, ch = jnp.repeat(bm, h // g, axis=2), jnp.repeat(cm, h // g, axis=2)

    def step(state, inp):
        xt, at, bt, ct = inp
        state = state * jnp.exp(at)[..., None, None] + xt[..., None] * bt[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    _, want = jax.lax.scan(step, jnp.zeros((b, h, p, n)),
                           tuple(jnp.moveaxis(t, 1, 0) for t in (x, a, bh, ch)))
    np.testing.assert_allclose(got, jnp.moveaxis(want, 0, 1), rtol=1e-4, atol=1e-4)


def test_quantize_is_ternary_per_block_and_unbiased():
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 700))
    q = R.quantize(x, jax.random.PRNGKey(2), 256)
    blocks = jnp.pad(x.reshape(-1), (0, 256 * 9 - 2100)).reshape(9, 256)
    qb = jnp.pad(q.reshape(-1), (0, 256 * 9 - 2100)).reshape(9, 256)
    scale = jnp.max(jnp.abs(blocks), 1, keepdims=True)
    assert bool(jnp.all((qb == 0) | (jnp.abs(qb) == scale)))
    assert bool(jnp.all((qb == 0) | (jnp.sign(qb) == jnp.sign(blocks))))
    keys = jax.random.split(jax.random.PRNGKey(3), 2000)
    mean = jnp.mean(jax.vmap(lambda k: R.quantize(x, k, 256))(keys), 0)
    assert float(jnp.max(jnp.abs(mean - x))) < 0.25
    assert abs(R.alpha_inf(1024) / 2 - 1 / 33) < 1e-12


def test_fp8_control_rounds_forward_and_backward():
    dot = R.make_dot("fp8")
    a = jnp.linspace(-1.0, 1.0, 64).reshape(8, 8) * 1e-4
    b = jnp.eye(8) * 3.0
    y, vjp = jax.vjp(lambda a_, b_: dot("ij,jk->ik", a_, b_), a, b)
    assert float(jnp.max(jnp.abs(y - 3 * a))) > 0          # rounded, not exact
    assert float(jnp.max(jnp.abs(y - 3 * a))) < 0.07 * float(jnp.max(jnp.abs(3 * a)))
    ga, gb = vjp(jnp.full((8, 8), 1e-6))
    assert float(jnp.min(jnp.abs(ga))) > 0                  # scaled: no flush to 0


def test_weights_are_the_seeds():
    model = tiny.spec("mamba")["config_doc"]["model"]
    one, two = R.make_params(model, 2**31 + 5), R.make_params(model, 2**31 + 5)
    other = R.make_params(model, 5)
    for a, b, c in zip(*(jax.tree_util.tree_leaves(t) for t in (one, two, other))):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    assert any(not bool(jnp.all(a == c)) for a, c in zip(
        jax.tree_util.tree_leaves(one), jax.tree_util.tree_leaves(other)))
