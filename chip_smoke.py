#!/usr/bin/env python3
"""Smoke test of the DIANA trainer's main path on a TPU.

Run from the root of the checkout, on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips, each one DIANA worker

One chip, in order:

1. the device line (platform, kind, count);
2. every Pallas kernel the TPU route takes, against its ``jax.numpy`` oracle
   (``repro.kernels.ref``) on seeded inputs of mamba2-130m's flat size with
   4 stacked worker rows: the encodes that take pre-drawn bits and every
   decode must match bit for bit; the encodes that draw their bits in the
   kernel must be unbiased;
3. the Mamba-2 SSD chunk-scan kernel pair (``repro.kernels.ssd``) at
   mamba2-130m's widths (batch 2, seq 4096), forward and vector-Jacobian
   product, against its oracle run at float32 HIGHEST precision: each
   output's error relative to the oracle's largest value must stay within
   the bf16-operand bound its CPU test states, and the oracle at the
   default precision (the XLA path the model takes without the kernel) is
   printed beside it;
4. the trainer CLI (``repro.launch.train.main``) on the whole mamba2-130m,
   at its published widths, for 5 steps: once with its default ``diana``
   operator and once with ``--compression natural``.  The compiled step must
   hold a Pallas kernel (``tpu_custom_call``), and the loss and the served
   direction's norm must stay finite (the norm also non-zero).

With ``--chips 4`` only two things run: the trainer on a 4x1 mesh (4
workers, global batch 32), and one aggregation round on the model's real
parameter tree, where ``aggregate_shardmap`` over the 4 chips must equal
``reference_step`` over the same 4 rows on one chip, bit for bit.

Times are printed as information.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every check passed.
Without a TPU, or outside the checkout, the script exits non-zero and prints
no such line.  It runs in one process and starts none.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time
import traceback

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.abspath(__file__))
ARCH = "mamba2-130m"
N_ROWS = 4            # stacked worker rows in the kernel checks
PRNG_DRAWS = 8        # encodes averaged in each unbiasedness check
SEED = 0              # weights, data and every drawn input


class Checks:
    """Prints each check on its own line and remembers the failures."""

    def __init__(self):
        self.failed = []

    def record(self, name, ok, detail):
        print(f"{'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)

    def phase(self, name, fn, *args):
        """Run one phase; an exception fails it (and the script), and the
        phases after it still run."""
        t0 = time.perf_counter()
        try:
            fn(self, *args)
        except Exception:
            traceback.print_exc()
            self.record(name, False, "raised")
        print(f"phase {name} took {time.perf_counter() - t0}s", flush=True)


# ---------------------------------------------------------------------------
# Kernels vs their oracles
# ---------------------------------------------------------------------------

@jax.jit
def _ndiff(a, b):
    """Elements whose bit patterns differ (floats compared as bits, so -0.0
    and NaN payloads count too)."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        a = jax.lax.bitcast_convert_type(a, jnp.int32)
        b = jax.lax.bitcast_convert_type(b, jnp.int32)
    return jnp.sum(a != b)


def _bitwise(checks, name, got, want):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    bad = sum(int(_ndiff(g, w)) for g, w in zip(got, want))
    size = sum(int(w.size) for w in want)
    checks.record(name, bad == 0, f"{bad} of {size} elements differ")


def _unbiased(checks, name, x, mean, var_sum, draws):
    """Mean of ``draws`` independent unbiased encodes vs the input: the
    summed error, scaled by its standard deviation, is about N(0, 1), and the
    summed squared error is about its expectation ``var_sum / draws``."""
    err = jax.jit(lambda m, x: m - x)(mean, x)
    expected = float(var_sum) / draws
    z = float(jnp.sum(err, dtype=jnp.float32)) / math.sqrt(expected)
    ratio = float(jnp.sum(err * err, dtype=jnp.float32)) / expected
    checks.record(name, abs(z) < 6.0 and 0.95 < ratio < 1.05,
                  f"z={z} (|z| < 6), squared-error ratio={ratio} (0.95..1.05) "
                  f"over {draws} draws of {x.size} elements")


def _flat_size(method):
    """Padded length of mamba2-130m's bucketed buffer under ``method`` and
    the trainer's flat compression config for it."""
    from dataclasses import replace

    from repro.configs import get_config
    from repro.core.diana import bucket_layout
    from repro.launch.train import make_optimizer
    from repro.models import init_model

    cfg = replace(get_config(ARCH), compression=method)
    comp = make_optimizer(cfg).compression
    params = jax.eval_shape(lambda k: init_model(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return bucket_layout(comp, params).padded_size, comp


def ternary_kernels(checks):
    from repro.kernels import ops, ref
    from repro.kernels.quantize_pack import quantize_pack
    from repro.kernels.unpack_reduce import (
        unpack_reduce, unpack_reduce_apply, unpack_reduce_mean,
    )

    d, comp = _flat_size("diana")
    b, p = comp.block_size, comp.p
    m = d // b
    alpha = comp.make().memory_alpha(d)
    print(f"ternary: {N_ROWS} rows of {m} blocks x {b} (d={d}), p={p}, "
          f"alpha={alpha}", flush=True)
    kx, kb, kh, kp = jax.random.split(jax.random.PRNGKey(SEED), 4)
    delta = jax.jit(lambda k: jax.random.normal(k, (N_ROWS, m, b)))(kx)
    bits = jax.jit(lambda k: jax.random.bits(k, (N_ROWS, m, b), jnp.uint32))(kb)

    ref_encode = jax.jit(ref.ref_quantize_pack, static_argnums=2)
    rows = [quantize_pack(delta[i], bits[i], p=p, interpret=False)
            for i in range(N_ROWS)]
    want = [ref_encode(delta[i], bits[i], p) for i in range(N_ROWS)]
    _bitwise(checks, "quantize_pack", rows, want)
    del bits, want
    packed = jnp.stack([pk for pk, _ in rows])
    scales = jnp.stack([sc for _, sc in rows])
    del rows

    total = jax.jit(ref.ref_unpack_reduce)(packed, scales)
    _bitwise(checks, "unpack_reduce",
             unpack_reduce(packed, scales, interpret=False), total)
    _bitwise(checks, "unpack_reduce_mean",
             unpack_reduce_mean(packed, scales, interpret=False),
             jax.jit(lambda s: s / jnp.float32(N_ROWS))(total))
    h = jax.jit(lambda k: jax.random.normal(k, (d,)))(kh)
    _bitwise(checks, "unpack_reduce_apply",
             unpack_reduce_apply(packed, scales, h, alpha=alpha,
                                 interpret=False),
             jax.jit(ref.ref_apply_server, static_argnums=(1, 3))(
                 total.reshape(-1), N_ROWS, h, alpha))
    del packed, scales, total, h

    # In-kernel PRNG: unbiased around row 0.  Each coordinate is s*sign(x)
    # with probability |x|/s (s its block's p-norm), so Var = |x| s - x^2.
    x = delta[0]
    del delta
    decode = jax.jit(lambda pk, sc: ref.ref_unpack_reduce(pk[None], sc[None]))
    acc = jnp.zeros_like(x)
    for k in jax.random.split(kp, PRNG_DRAWS):
        pk, sc = ops.quantize_pack_prng_op(x, k, p=p)
        acc = acc + decode(pk, sc)
    var_sum = jax.jit(lambda x, s: jnp.sum(jnp.abs(x) * s - x * x))(x, sc)
    _unbiased(checks, "quantize_pack_prng unbiased", x, acc / PRNG_DRAWS,
              var_sum, PRNG_DRAWS)


def natural_kernels(checks):
    from repro.kernels import ops, ref
    from repro.kernels.nat_pack import (
        nat_decode_sum, nat_decode_sum_apply, nat_decode_sum_mean, nat_pack,
    )

    d, comp = _flat_size("natural")
    alpha = comp.make().memory_alpha(d)
    print(f"natural: {N_ROWS} rows of d={d}, alpha={alpha}", flush=True)
    kx, ke, kb, kh, kp = jax.random.split(jax.random.PRNGKey(SEED + 1), 5)

    @jax.jit
    def heavy_tailed(kx, ke):
        # Magnitudes over 200 binades, and exact zeros.
        e = jax.random.uniform(ke, (N_ROWS, d), minval=-100.0, maxval=100.0)
        x = jax.random.normal(kx, (N_ROWS, d)) * jnp.exp2(jnp.floor(e))
        return x.at[:, ::1009].set(0.0)

    x = heavy_tailed(kx, ke)
    bits = jax.jit(lambda k: jax.random.bits(k, (N_ROWS, d), jnp.uint32))(kb)
    ref_encode = jax.jit(ref.ref_nat_pack)
    codes = jnp.stack([nat_pack(x[i], bits[i], interpret=False)
                       for i in range(N_ROWS)])
    want = [ref_encode(x[i], bits[i]) for i in range(N_ROWS)]
    _bitwise(checks, "nat_pack", list(codes), want)
    del x, bits, want

    total = jax.jit(ref.ref_nat_decode_sum)(codes)
    _bitwise(checks, "nat_decode_sum",
             nat_decode_sum(codes, interpret=False), total)
    _bitwise(checks, "nat_decode_sum_mean",
             nat_decode_sum_mean(codes, interpret=False),
             jax.jit(lambda s: s / jnp.float32(N_ROWS))(total))
    h = jax.jit(lambda k: jax.random.normal(k, (d,)))(kh)
    _bitwise(checks, "nat_decode_sum_apply",
             nat_decode_sum_apply(codes, h, alpha=alpha, interpret=False),
             jax.jit(ref.ref_apply_server, static_argnums=(1, 3))(
                 total, N_ROWS, h, alpha))
    del codes, total, h

    # In-kernel PRNG: unbiased on normal inputs.  |x| in [lo, 2 lo) rounds
    # to 2 lo with probability |x|/lo - 1, so Var = (2 lo - |x|)(|x| - lo).
    x = jax.jit(lambda k: jax.random.normal(k, (d,)))(kx)
    decode = jax.jit(lambda c: ref.ref_nat_decode_sum(c[None]))
    acc = jnp.zeros_like(x)
    for k in jax.random.split(kp, PRNG_DRAWS):
        acc = acc + decode(ops.nat_pack_prng_op(x, k))

    @jax.jit
    def var_sum(x):
        a = jnp.abs(x)
        _, e = jnp.frexp(a)
        lo = jnp.ldexp(jnp.float32(0.5), e)
        return jnp.sum(jnp.where(a > 0, (2 * lo - a) * (a - lo), 0.0))

    _unbiased(checks, "nat_pack_prng unbiased", x, acc / PRNG_DRAWS,
              var_sum(x), PRNG_DRAWS)


# bf16 unit roundoff u = 2^-9; tests/test_ssd_kernel.py states the bounds:
# error RMS <= 8u and largest error <= 16u, each relative to the oracle's
SSD_RMS_TOL, SSD_MAX_TOL = 8 * 2.0 ** -9, 16 * 2.0 ** -9


def ssd_kernels(checks):
    from repro.configs import get_config
    from repro.kernels import ref
    from repro.kernels.ops import ssd_chunk_scan_op

    cfg = get_config(ARCH)
    sc = cfg.ssm
    h, p, n, g = sc.n_heads(cfg.d_model), sc.head_dim, sc.d_state, sc.n_groups
    b, l, q = 2, 4096, sc.chunk_size
    print(f"ssd: batch {b}, seq {l}, {h} heads of {p}, d_state {n}, "
          f"{g} group(s), chunk {q}", flush=True)
    ks = jax.random.split(jax.random.PRNGKey(SEED + 3), 6)

    @jax.jit
    def inputs(ks):
        x = jax.random.normal(ks[0], (b, l, h * p)).astype(jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)) - 3.0)
        a = -jnp.linspace(1.0, 16.0, h)
        bm = jax.random.normal(ks[2], (b, l, g * n)).astype(jnp.bfloat16)
        cm = jax.random.normal(ks[3], (b, l, g * n)).astype(jnp.bfloat16)
        dy = jax.random.normal(ks[4], (b, l, h * p))
        return (x, dt, a, bm, cm), dy

    args, dy = inputs(ks)

    def vjp(fn, precision):
        @jax.jit
        def run(args, dy):
            with jax.default_matmul_precision(precision):
                y, back = jax.vjp(fn, *args)
                return (y, *back(dy))
        return run(args, dy)

    kern = vjp(lambda *a: ssd_chunk_scan_op(*a, chunk=q, n_groups=g), "default")
    oracle = lambda *a: ref.ref_ssd_chunk_scan(*a, chunk=q, n_groups=g)
    want = vjp(oracle, "highest")
    xla = vjp(oracle, "default")

    @jax.jit
    def errors(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        e = got - want
        return (jnp.sqrt(jnp.mean(e * e) / jnp.mean(want * want)),
                jnp.max(jnp.abs(e)) / jnp.max(jnp.abs(want)))

    for name, k, w, x in zip(("y", "dx", "ddt", "dA", "dB", "dC"), kern, want, xla):
        rms, mx = (float(v) for v in errors(k, w))
        xrms, xmx = (float(v) for v in errors(x, w))
        checks.record(f"ssd_chunk_scan {name}",
                      rms <= SSD_RMS_TOL and mx <= SSD_MAX_TOL,
                      f"kernel rms {rms} max {mx}; XLA default rms {xrms} "
                      f"max {xmx}")


# ---------------------------------------------------------------------------
# The trainer
# ---------------------------------------------------------------------------

def trainer(checks, label, argv):
    from repro.launch.train import main as train_main

    print(f"trainer {label}: {' '.join(argv)}", flush=True)
    run = train_main(argv)
    steady = run.step_s[1:]
    print(f"trainer {label}: compile {run.compile_s}s, step times "
          f"{run.step_s}, steady median {statistics.median(steady)}s",
          flush=True)
    text = run.compiled.as_text()
    kernels = text.count('custom_call_target="tpu_custom_call"')
    checks.record(f"trainer {label} kernel route", kernels > 0,
                  f"{kernels} tpu_custom_call in the compiled step")
    finite = (all(math.isfinite(v) for v in run.losses)
              and all(math.isfinite(v) and v > 0 for v in run.ghat_norms))
    checks.record(f"trainer {label} finite", finite,
                  f"losses {run.losses}, ghat norms {run.ghat_norms}")


def _trainer_argv(*extra):
    return ["--arch", ARCH, "--shape", "train_4k", "--steps", "5",
            "--seed", str(SEED), *extra]


# ---------------------------------------------------------------------------
# Four chips: distributed round == reference round
# ---------------------------------------------------------------------------

def distributed_round(checks):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.compat import shard_map
    from repro.configs import get_config
    from repro.core import DianaState, aggregate_shardmap
    from repro.core.diana import ReferenceState, bucket_layout, reference_step
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_optimizer
    from repro.models import init_model

    n = 4
    cfg = get_config(ARCH)
    comp = make_optimizer(cfg).compression
    params = jax.eval_shape(lambda k: init_model(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    dp = bucket_layout(comp, params).padded_size
    mesh = make_mesh((n,), ("data",))
    rows = NamedSharding(mesh, P("data"))
    one = SingleDeviceSharding(jax.devices()[0])
    print(f"round: {comp.method} block {comp.block_size}, {n} workers, "
          f"Dp={dp}", flush=True)

    kg, kh, key = jax.random.split(jax.random.PRNGKey(SEED + 2), 3)
    leaves, treedef = jax.tree_util.tree_flatten(params)

    @jax.jit
    def make_grads(kg):
        return treedef.unflatten([
            1e-2 * jax.random.normal(jax.random.fold_in(kg, i),
                                     (n,) + leaf.shape, jnp.float32)
            for i, leaf in enumerate(leaves)])

    grads = make_grads(kg)
    h_w = jax.jit(lambda k: 1e-3 * jax.random.normal(k, (n, dp)))(kh)
    h_s = jax.jit(lambda h: jnp.mean(h, axis=0))(h_w)

    def body(g, h_worker, h_server, key):
        g_own = jax.tree_util.tree_map(lambda a: a[0], g)
        wkey = jax.random.fold_in(key, jax.lax.axis_index("data"))
        ghat, new = aggregate_shardmap(g_own, DianaState(h_worker, h_server),
                                       wkey, comp, axis_names=("data",),
                                       n_workers=n)
        return ghat, new.h_worker, new.h_server

    tree = lambda spec, t: jax.tree_util.tree_map(lambda _: spec, t)
    dist = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(tree(P("data"), grads), P("data"), P(), P()),
        out_specs=(tree(P(), params), P("data"), P()), check_vma=False))
    t0 = time.perf_counter()
    got = jax.block_until_ready(dist(
        jax.device_put(grads, rows), jax.device_put(h_w, rows), h_s, key))
    print(f"round: distributed {time.perf_counter() - t0}s (with compile)",
          flush=True)

    state = ReferenceState(
        h_worker=h_w, h_server=h_s,
        v=jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32),
                                 params))
    t0 = time.perf_counter()
    v, new = jax.block_until_ready(jax.jit(
        lambda g, s, k: reference_step(g, s, k, comp))(
            *jax.device_put((grads, state, key), one)))
    print(f"round: reference on one chip {time.perf_counter() - t0}s "
          f"(with compile)", flush=True)
    got = jax.device_put(got, one)
    _bitwise(checks, "round ghat == reference", got[0], v)
    _bitwise(checks, "round h_worker == reference", got[1], new.h_worker)
    _bitwise(checks, "round h_server == reference", got[2], new.h_server)


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))

    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}", flush=True)

    checks = Checks()
    if args.chips == 1:
        checks.phase("ternary kernels", ternary_kernels)
        checks.phase("natural kernels", natural_kernels)
        checks.phase("ssd kernels", ssd_kernels)
        checks.phase("trainer diana", trainer, "diana",
                     _trainer_argv("--batch", "8"))
        checks.phase("trainer natural", trainer, "natural",
                     _trainer_argv("--batch", "8",
                                   "--compression", "natural"))
    else:
        checks.phase("distributed round", distributed_round)
        checks.phase("trainer diana 4 workers", trainer, "diana 4 workers",
                     _trainer_argv("--batch", "32", "--mesh", "4x1"))

    if checks.failed:
        print(f"chip_smoke: failed: {checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
