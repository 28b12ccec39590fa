"""The fused Mamba-2 SSD chunk-scan kernels (``repro.kernels.ssd``) against
their oracle, the model's XLA ``_ssd_chunked`` (``ref.ref_ssd_chunk_scan``),
in interpret mode; and where ``mamba_layer`` takes them.

Tolerances.  The kernels' matrix products take bf16 operands with f32
accumulation, as XLA's DEFAULT precision does on a TPU; the oracle here runs
in f32 on the CPU.  With u = 2^-9 (bf16's unit roundoff), a path from the
inputs to any output crosses at most two such products, each rounding two
operands, so the RMS error relative to the oracle's RMS stays within 8u and
the largest error relative to the oracle's largest value within 16u.  With
the bf16 rounding switched off the kernels must agree with the oracle to f32
rounding (1e-4), which checks every term of the forward and backward math.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ssd as ssd
import repro.models.mamba2 as M
from repro.configs import get_config
from repro.kernels.ref import ref_ssd_chunk_scan

U = 2.0 ** -9
RMS_TOL, MAX_TOL = 8 * U, 16 * U
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC")


def _inputs(b, l, h, p, g, n, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, l, h * p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)) - 3.0)
    a = -jnp.linspace(1.0, 16.0, h)
    bm = jax.random.normal(ks[2], (b, l, g * n)).astype(dtype)
    cm = jax.random.normal(ks[3], (b, l, g * n)).astype(dtype)
    dy = jax.random.normal(ks[4], (b, l, h * p))
    return (x, dt, a, bm, cm), dy


def _vjp(fn, args, dy):
    y, back = jax.vjp(fn, *args)
    return (y, *back(dy))


def _errors(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    e = got - want
    return (float(np.sqrt(np.mean(e * e) / np.mean(want * want))),
            float(np.max(np.abs(e)) / np.max(np.abs(want))))


def _kernel_and_oracle(args, dy, q, g):
    kern = _vjp(lambda *a: ssd.ssd_chunk_scan(*a, q, g, True), args, dy)
    want = _vjp(lambda *a: ref_ssd_chunk_scan(*a, chunk=q, n_groups=g), args, dy)
    return kern, want


# (chunk, heads, head dim, groups): B=2, L=512, N=128 throughout.  Programs
# span 1, 2 and 4 128-lane tiles of x (ssd._tiles).
SHAPES = [(128, 4, 64, 1), (256, 4, 64, 1), (128, 4, 64, 2), (256, 4, 64, 2),
          (256, 8, 64, 1), (128, 2, 128, 1)]


@pytest.mark.parametrize("q,h,p,g", SHAPES)
def test_forward_and_vjp_match_oracle(q, h, p, g):
    assert ssd.supports(seq=512, chunk=q, n_heads=h, head_dim=p, d_state=128,
                        n_groups=g)
    args, dy = _inputs(2, 512, h, p, g, 128)
    kern, want = _kernel_and_oracle(args, dy, q, g)
    for name, k, w, a in zip(NAMES, kern, want, (None, *args)):
        assert k.shape == w.shape and k.dtype == w.dtype, name
        if a is not None:
            assert k.dtype == a.dtype, name      # cotangent dtype = primal's
        rms, mx = _errors(k, w)
        assert rms <= RMS_TOL and mx <= MAX_TOL, (name, rms, mx)


@pytest.mark.parametrize("q,g", [(128, 2), (256, 1)])
def test_math_is_exact_without_bf16_rounding(q, g, monkeypatch):
    """Every product at f32: kernel == oracle to f32 rounding, forward and
    backward (a dropped or wrong term shows here, not only at 1 %)."""
    monkeypatch.setattr(ssd, "_bf", lambda v: v)
    jax.clear_caches()
    try:
        args, dy = _inputs(2, 512, 4, 64, g, 128, seed=1, dtype=jnp.float32)
        kern, want = _kernel_and_oracle(args, dy, q, g)
    finally:
        jax.clear_caches()
    for name, k, w in zip(NAMES, kern, want):
        rms, mx = _errors(k, w)
        assert rms <= 1e-4 and mx <= 1e-4, (name, rms, mx)


@pytest.mark.parametrize("shape,ok", [
    (dict(seq=4096, chunk=256, n_heads=24, head_dim=64, d_state=128, n_groups=1), True),
    (dict(seq=4096, chunk=256, n_heads=16, head_dim=128, d_state=128, n_groups=2), True),
    # jamba-v0.1-52b's mamba layers: d_state 16 is not a lane multiple
    (dict(seq=4096, chunk=256, n_heads=128, head_dim=64, d_state=16, n_groups=1), False),
    (dict(seq=4096, chunk=256, n_heads=24, head_dim=32, d_state=128, n_groups=1), False),
    (dict(seq=4096, chunk=64, n_heads=24, head_dim=64, d_state=128, n_groups=1), False),
    (dict(seq=4000, chunk=256, n_heads=24, head_dim=64, d_state=128, n_groups=1), False),
    # one head per group: a 128-lane block of P=64 would straddle two groups
    (dict(seq=4096, chunk=256, n_heads=24, head_dim=64, d_state=128, n_groups=24), False),
])
def test_shape_predicate(shape, ok):
    assert ssd.supports(**shape) is ok


# -- the model's route ---------------------------------------------------------

def _kernel_sized_cfg():
    """mamba2-130m's SSD widths (P=64, N=128, G=1) at a CPU size: 8 heads."""
    cfg = get_config("mamba2-130m")
    return dataclasses.replace(cfg, d_model=256,
                               ssm=dataclasses.replace(cfg.ssm, chunk_size=128))


def _pallas_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    out += _pallas_names(getattr(inner, "jaxpr", inner))
    return out


def _layer_grad_kernels(cfg, s, cache=None):
    params = jax.eval_shape(lambda k: M.init_mamba(k, cfg, jnp.bfloat16),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    x = jax.ShapeDtypeStruct((1, s, cfg.d_model), jnp.bfloat16)

    def loss(p, x):
        out, _ = M.mamba_layer(p, x, cfg, cache=cache)
        return jnp.sum(out.astype(jnp.float32))

    return _pallas_names(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr)


def test_layer_takes_kernels_on_tpu_by_shape(monkeypatch):
    cfg = get_config("mamba2-130m")
    assert _layer_grad_kernels(cfg, 512) == []                  # CPU: XLA path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sorted(set(_layer_grad_kernels(cfg, 512))) == [
        "ssd_chunk_scan", "ssd_chunk_scan_bwd"]
    jamba = get_config("jamba-v0.1-52b")
    jamba = dataclasses.replace(jamba, d_model=512)            # d_state 16
    assert _layer_grad_kernels(jamba, 512) == []


def test_decode_step_never_takes_kernel(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("mamba2-130m")
    cache = M.init_mamba_cache(cfg, 1, jnp.bfloat16)
    params = M.init_mamba(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    x = jnp.zeros((1, 1, cfg.d_model), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, x, c: M.mamba_layer(p, x, cfg, cache=c))(
        params, x, cache)
    assert _pallas_names(jaxpr.jaxpr) == []


def test_layer_on_kernel_matches_xla_path(monkeypatch):
    """The whole layer, output and every gradient, through the kernels
    (interpret mode) against the XLA path.  Past the scan the layer rounds
    to bf16, and the per-head leaves (A_log, dt_bias, D) are sums whose terms
    cancel, so the yardstick is measured, not assumed: the change that
    rounding the XLA scan's output to bf16 once makes.  The kernels round at
    most four times on any path (two products, two operands each), so each
    leaf may move at most four times as far."""
    cfg = _kernel_sized_cfg()
    params = M.init_mamba(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(1), (1, 256, cfg.d_model))
         ).astype(jnp.bfloat16)

    def run():
        def loss(p, x):
            out, _ = M.mamba_layer(p, x, cfg)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                             has_aux=True)(params, x)
        return jax.tree_util.tree_leaves_with_path((out, grads))

    want = run()
    scan = M._ssd_chunked
    with monkeypatch.context() as m:
        m.setattr(M, "_ssd_chunked", lambda *a, **k: scan(*a, **k).astype(
            jnp.bfloat16).astype(jnp.float32))
        once = run()
    monkeypatch.setattr(M, "_fused_ssd", lambda cfg, s, chunk: True)
    got = run()
    for (path, w), (_, o), (_, g) in zip(want, once, got):
        rms, yardstick = _errors(g, w)[0], _errors(o, w)[0]
        assert 0 < yardstick and rms <= 4 * yardstick, (
            jax.tree_util.keystr(path), rms, yardstick)


def _xla_layer(params, x, cfg):
    """``mamba_layer``'s training path as it stood before the kernel, kept
    verbatim: the route every shape the kernel does not take still runs."""
    sc, d_in, h, p, n, g = M._dims(cfg)
    bsz, s, _ = x.shape
    proj = x @ params["in_proj"].astype(cfg.compute_dtype)
    z, xr, braw, craw, dt_raw = M._split_proj(proj, cfg)
    conv_out = M._conv_full(params, jnp.concatenate([xr, braw, craw], axis=-1), cfg)
    xr, braw, craw = jnp.split(conv_out, [d_in, d_in + g * n], axis=-1)
    xt = xr.reshape(bsz, s, h, p)
    bh = jnp.repeat(braw.reshape(bsz, s, g, n), h // g, axis=2)
    ch = jnp.repeat(craw.reshape(bsz, s, g, n), h // g, axis=2)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["A_log"])
    y = M._ssd_chunked(xt.astype(jnp.float32) * dt[..., None], a * dt, bh, ch,
                       min(sc.chunk_size, s))
    y = (y + params["D"][:, None] * xt.astype(jnp.float32)).reshape(bsz, s, d_in)
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(gated * gated, axis=-1, keepdims=True)
    yn = gated * jax.lax.rsqrt(var + cfg.norm_eps) * params["norm_scale"].astype(jnp.float32)
    return yn.astype(cfg.compute_dtype) @ params["out_proj"].astype(cfg.compute_dtype)


@pytest.mark.parametrize("arch", ["mamba2-130m", "jamba-v0.1-52b"])
def test_xla_path_is_unchanged_bit_for_bit(arch):
    """On the CPU (and for every shape the kernel does not take) the layer
    returns exactly what the pre-kernel code returned."""
    from repro.configs import reduced

    cfg = reduced(get_config(arch))
    params = M.init_mamba(jax.random.PRNGKey(2), cfg, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, cfg.d_model)).astype(jnp.bfloat16)
    got, _ = jax.jit(lambda p, x: M.mamba_layer(p, x, cfg))(params, x)
    want = jax.jit(lambda p, x: _xla_layer(p, x, cfg))(params, x)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
