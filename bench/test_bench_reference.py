"""The reference against the program at test size in float32, its parts
against their definitions, and its lookup of layer kinds by file."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import feed, program, tiny
from bench import reference as R

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
with open(os.path.join(DATA, "reference_tiny_mamba.json")) as _f:
    GOLDEN = json.load(_f)


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
    got = R.layer_kind("mamba").ssd(x, a, bm, cm, 16, R.make_dot("f32"))
    bh, ch = jnp.repeat(bm, h // g, axis=2), jnp.repeat(cm, h // g, axis=2)

    def step(state, inp):
        xt, at, bt, ct = inp
        state = state * jnp.exp(at)[..., None, None] + xt[..., None] * bt[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    _, want = jax.lax.scan(step, jnp.zeros((b, h, p, n)),
                           tuple(jnp.moveaxis(t, 1, 0) for t in (x, a, bh, ch)))
    np.testing.assert_allclose(got, jnp.moveaxis(want, 0, 1), rtol=1e-4, atol=1e-4)


def _gate_inputs(width):
    rng = np.random.default_rng(4)
    y, z = (jnp.asarray(rng.standard_normal((2, 5, width)), jnp.float32) for _ in range(2))
    scale = jnp.asarray(rng.uniform(0.5, 1.5, width), jnp.float32)
    return y, z, scale


def test_gated_norm_per_group():
    """Two groups: each normalised by its own mean square (a direct formula
    per group), then scaled over the whole width; not the whole-width norm."""
    y, z, scale = _gate_inputs(24)
    got = R.layer_kind("mamba").gated_rms_norm(y, z, scale, 2, 1e-5)
    x = np.asarray(y * jax.nn.silu(z), np.float64)
    want = np.concatenate([g / np.sqrt(np.mean(g * g, -1, keepdims=True) + 1e-5)
                           for g in (x[..., :12], x[..., 12:])], -1) * np.asarray(scale)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    whole = R.rms_norm(y * jax.nn.silu(z), scale, 1e-5)
    assert float(jnp.max(jnp.abs(got - whole))) > 1e-3


def test_gated_norm_one_group_is_the_whole_width_norm():
    """One group is the RMSNorm over the whole width, bit for bit, eager and
    jitted."""
    y, z, scale = _gate_inputs(48)
    norm = R.layer_kind("mamba").gated_rms_norm
    whole = R.rms_norm(y * jax.nn.silu(z), scale, 1e-5)
    assert bool(jnp.all(norm(y, z, scale, 1, 1e-5) == whole))
    jitted = jax.jit(lambda *a: norm(*a, 1, 1e-5))(y, z, scale)
    assert bool(jnp.all(jitted == jax.jit(
        lambda y_, z_, s_: R.rms_norm(y_ * jax.nn.silu(z_), s_, 1e-5))(y, z, scale)))


def test_mamba_inner_width_from_its_heads():
    """Where the configuration gives ``num_heads``, the inner width is the
    heads' (not ``expand * d_model``): in the layout, the layer and the
    yardstick's count, here with three groups."""
    ssm = dict(tiny.MAMBA["ssm"], num_heads=3, n_groups=3, d_state=8)
    model = dict(tiny.MAMBA, d_model=40, ssm=ssm, param_dtype="float32")
    mamba = R.layer_kind("mamba")
    mixer = R.param_layout(model)["blocks"]["layer0"]["mixer"]
    assert mixer["out_proj"].shape == (2, 48, 40) and mixer["A_log"].shape == (2, 3)
    assert mixer["in_proj"].shape == (2, 40, 2 * 48 + 2 * 3 * 8 + 3)
    assert mamba.matmul_params(model) == 40 * (2 * 48 + 48 + 3) + 4 * (48 + 48) + 48 * 40
    p = jax.tree_util.tree_map(lambda a: a[0], R.make_params(model, 9)["blocks"]["layer0"])
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 40))
    y, _ = mamba.apply(p["mixer"], x, model, R.make_dot("f32"))
    assert y.shape == x.shape and bool(jnp.all(jnp.isfinite(y)))


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


@pytest.mark.parametrize("run", GOLDEN["runs"], ids=lambda r: f"w{r['workers']}-s{r['seed']}")
def test_reference_gives_the_recorded_readings(run):
    """Three steps of DIANA on the tiny Mamba model, 1 and 4 workers on one
    CPU device, equal to the readings recorded before the layer kinds moved
    to ``bench/layers/``: every loss and per-leaf norm, exactly."""
    spec = tiny.spec("mamba", workers=run["workers"])
    model, traffic = spec["config_doc"]["model"], spec["traffic"]
    seed = run["seed"]
    batches = [[(b["tokens"], b["labels"])
                for b in feed.worker_batches(traffic, model["vocab"], seed, t)]
               for t in range(3)]
    got = R.diana_reference(model, traffic, R.make_params(model, seed), batches, seed,
                            devices=[jax.devices()[0]] * run["workers"])
    assert got._asdict() == run["readings"]


def test_a_layer_kind_without_a_file_names_the_file():
    model = dict(tiny.MAMBA, pattern=[{"mixer": "attn", "mlp": "moe"}])
    with pytest.raises(ValueError, match="no reference for layer kind 'attn': "
                                         "add bench/layers/attn.py"):
        R.param_layout(model)
    model["pattern"] = [{"mixer": "mamba", "mlp": "moe"}]
    with pytest.raises(ValueError, match="bench/layers/moe.py"):
        R.param_layout(model)


def test_a_layer_kind_from_its_own_file():
    """A toy kind in ``bench/testdata/layers/``, as mixer and mlp: its
    leaves, its own initialiser, and its term in the loss, over the whole
    batch at once (``WHOLE_BATCH``)."""
    where = os.path.join(DATA, "layers")
    model = dict(tiny.MAMBA, pattern=[{"mixer": "toy", "mlp": "toy"}], n_layers=2,
                 param_dtype="float32")
    layout = R.param_layout(model, where)
    assert layout["blocks"]["layer0"]["mixer"]["w"] == R.Leaf((2, 64, 64), "float32",
                                                               "toy_uniform")
    assert set(layout["blocks"]["layer0"]) == {"norm1", "mixer", "norm2", "mlp"}
    params = R.make_params(model, 2**31 + 3, where)
    w = params["blocks"]["layer0"]["mlp"]["w"]
    assert w.dtype == jnp.float32 and 0.09 < float(jnp.max(jnp.abs(w))) <= 0.1

    b = feed.worker_batches(tiny.spec("mamba", batch=4)["traffic"], model["vocab"], 7, 0)[0]
    tok, lab = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
    ce, aux = R.loss_terms(params, tok, lab, model, layers=where)
    loss, grads = R.loss_and_grad(params, tok, lab, model, layers=where)
    assert float(aux) > 0
    # the term of all four rows at once; blocks of two rows would differ
    assert float(loss) == pytest.approx(float(ce) / tok.size + float(aux), rel=1e-6)
    halves = [R.loss_terms(params, tok[r:r + 2], lab[r:r + 2], model, layers=where)[1]
              for r in (0, 2)]
    assert abs(float(sum(halves)) / 2 - float(aux)) > 1e-3 * float(aux)
    # the term has a gradient of its own: without it the gradient differs
    grad_ce = jax.grad(lambda p: R.loss_terms(p, tok, lab, model, layers=where)[0]
                       / tok.size)(params)
    w_ce = grad_ce["blocks"]["layer0"]["mixer"]["w"]
    assert float(jnp.max(jnp.abs(grads["blocks"]["layer0"]["mixer"]["w"] - w_ce))) > 0


def test_a_pattern_without_terms_adds_none():
    model = tiny.spec("mamba", f32=True)["config_doc"]["model"]
    params = R.make_params(model, 5)
    b = feed.worker_batches(tiny.spec("mamba")["traffic"], model["vocab"], 5, 0)[0]
    ce, aux = R.loss_terms(params, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]), model)
    assert aux is None and float(ce) > 0


def test_a_tiny_model_of_its_own():
    """``tiny.spec`` takes an ``(arch, model)`` pair; the program's layout
    of that model is checked against the reference's, found by the lookup."""
    own = dict(tiny.MAMBA, n_layers=3)
    spec = tiny.spec(("mamba2-130m", own), workers=2)
    assert spec["cell"] == {"name": "test.mamba2-130m", "chips": 2}
    assert spec["config_doc"] == {"arch": "mamba2-130m", "model": own}
    assert spec["config_doc"]["model"] is not own
    cfg = program.model_config(spec["config_doc"], spec["traffic"])
    program.check_layout(cfg, R.param_layout(own))
    assert R.param_layout(own)["blocks"]["layer0"]["mixer"]["A_log"].shape == (3, 8)
