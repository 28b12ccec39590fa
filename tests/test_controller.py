"""Adaptive bit-budget controller: telemetry + budget-driven reallocation.

The laws this file pins (DESIGN.md §Controller):

* **Pure-observer law** — ``telemetry=True`` on ``reference_step`` /
  ``aggregate_shardmap`` returns BITWISE-identical ``(ghat, state)`` to the
  untelemetered call, for all five operators x layouts x participation,
  including on a real 4-worker mesh — so a controller-disabled run IS the
  static-policy run.
* **Budget law** — every policy the controller emits satisfies
  ``policy_bits_per_dim(policy, tree) <= budget`` (the repo's ground-truth
  wire accounting, not the solver's internal tables).
* **Reallocation law** — when the group statistics shift mid-run, the
  emitted policy changes (within the pre-declared lattice, only after the
  dwell window), and group identity (names, state keys, PRNG folds)
  survives every switch.
* **Convergence law** — the adaptive policy at budget B matches/beats the
  best STATIC uniform lattice policy at the same B on a quadratic with
  skewed per-group gradient energy.
"""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BudgetController,
    ChannelSpec,
    CompressionConfig,
    CompressionPolicy,
    ControllerState,
    GroupTelemetry,
    ParticipationSpec,
    allocate,
    controller_metadata,
    default_lattice,
    init_controller_state,
    init_state,
    maybe_reallocate,
    migrate_diana_state,
    observe,
    parse_rules,
    partition_for,
    policy_bits_per_dim,
    reference_init,
    reference_step,
    state_from_metadata,
)
from repro.core.telemetry import group_dims, measure, telemetry_group_names

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

METHODS = ["diana", "natural", "randk", "topk_ef", "identity"]
KEY = jax.random.PRNGKey(3)


def tree_eq(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def mesh_params():
    return {"emb": jnp.ones((32, 8)), "w1": jnp.ones((16, 16)),
            "w2": jnp.ones((16, 16)), "norm": jnp.ones((16,)),
            "b": jnp.ones((8,))}


def stacked_grads(params, n, key):
    return {k: jax.random.normal(jax.random.fold_in(key, i), (n,) + v.shape)
            for i, (k, v) in enumerate(params.items())}


# ---------------------------------------------------------------------------
# Pure-observer law, reference path (in-process grid)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("bucketed", [False, True], ids=["perleaf", "bucketed"])
@pytest.mark.parametrize("elastic", ["none", "sampled", "degraded"])
def test_telemetry_pure_observer_reference(method, bucketed, elastic):
    """telemetry=True is a pure observer: (v, state) bitwise-identical, for
    all five operators x both layouts x participation off / sampled /
    force-degraded."""
    n = 4
    params = mesh_params()
    grads = stacked_grads(params, n, KEY)
    spec = {"none": None,
            "sampled": ParticipationSpec(q=0.5, dropout=0.2),
            # dropout=0.9 with min_workers=4: |S| < 4 w.h.p. -> ghat = 0
            "degraded": ParticipationSpec(dropout=0.9, min_workers=4)}[elastic]
    cfg = CompressionConfig(method=method, p=math.inf, k=16, block_size=16,
                            bucketed=bucketed, participation=spec)
    st0 = reference_init(params, cfg, n)
    v_a, s_a = reference_step(grads, st0, KEY, cfg, step=0)
    v_b, s_b, telem = reference_step(grads, st0, KEY, cfg, step=0,
                                     telemetry=True)
    tree_eq(v_a, v_b)
    tree_eq(s_a, s_b)
    # Flat config -> one "all" group; fixed shape regardless of elastic mode.
    assert telem.m2.shape == (1,) and telem.m2.dtype == jnp.float32
    assert telem.var.shape == (1,) and telem.ok.shape == ()
    served_zero = all(not np.asarray(l).any()
                      for l in jax.tree_util.tree_leaves(v_a))
    if elastic == "degraded" and served_zero:
        # Degraded step: the sample must carry ok=False so EMAs freeze.
        import dataclasses

        assert not bool(telem.ok)
        ema = observe(BudgetController(
            base=CompressionPolicy.uniform(
                dataclasses.replace(cfg, participation=None)),
            budget_bits_per_dim=32.0),
            ControllerState(ema_m2=(1.0,), ema_var=(1.0,), count=3), telem)
        assert ema.ema_m2 == (1.0,) and ema.count == 3  # frozen
    if elastic == "none":
        assert bool(telem.ok)


def test_telemetry_grouped_reference_values():
    """Grouped policy: (n_groups,) arrays in partition order, and m2 is
    EXACTLY ||v_g||^2 / d_g of the served direction (beta=0 -> v == ghat)."""
    n = 4
    params = mesh_params()
    grads = stacked_grads(params, n, KEY)
    pol = CompressionPolicy(rules=parse_rules(
        "^norm$|^b$=natural,^emb$=topk_ef:k=16,*=diana:block=16"))
    v, _, telem = reference_step(grads, reference_init(params, pol, n), KEY,
                                 pol, telemetry=True)
    part = partition_for(pol, params)
    assert telem.m2.shape == (part.n_groups,)
    assert telemetry_group_names(pol, params) == part.group_names
    dims = group_dims(pol, params)
    assert sum(dims) == sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
    leaves = jax.tree_util.tree_leaves(v)
    for g, ids in enumerate(part.group_leaf_ids):
        d = sum(int(leaves[i].size) for i in ids)
        m2 = sum(float(jnp.sum(jnp.square(leaves[i].astype(jnp.float32))))
                 for i in ids) / d
        assert dims[g] == d
        np.testing.assert_allclose(float(telem.m2[g]), m2, rtol=1e-5)


def test_measure_matches_direct_recompute():
    """measure() on an arbitrary tree: flat spec = one group; values exact."""
    tree = {"a": jnp.arange(6, dtype=jnp.float32),
            "b": -jnp.ones((3,), jnp.float32)}
    t = measure(None, tree)
    x = np.concatenate([np.arange(6, dtype=np.float32), -np.ones(3, np.float32)])
    np.testing.assert_allclose(float(t.m2[0]), float((x ** 2).mean()), rtol=1e-6)
    np.testing.assert_allclose(
        float(t.var[0]), float((x ** 2).mean() - x.mean() ** 2), rtol=1e-5)
    assert bool(t.ok)


# ---------------------------------------------------------------------------
# Pure-observer law, distributed (real 4-worker mesh, subprocess)
# ---------------------------------------------------------------------------

def run_py(code: str, timeout=900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # The trainer CLI turns on the checkout's compile cache; tests write none.
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=REPO)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_telemetry_distributed_bitwise_all_operators():
    """On a real 4-worker mesh, for a 5-group policy holding ALL FIVE
    operators, per-leaf and bucketed, participation off and on:
    aggregate_shardmap with telemetry=True returns bitwise-identical
    (ghat, state) to telemetry=False, and its telemetry matches the
    reference path's to f32 reduction tolerance (the inputs are bitwise
    identical, but the two programs may order the jnp.sum reductions
    differently)."""
    code = """
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.core import (CompressionPolicy, DianaState, ParticipationSpec,
                        aggregate_shardmap, init_state, parse_rules,
                        reference_init, reference_step)
from repro.core.diana import PART_FOLD
from repro.launch.mesh import make_mesh

n = 4
params = {"emb": jnp.ones((32, 8)), "w1": jnp.ones((16, 16)),
          "w2": jnp.ones((16, 16)), "norm": jnp.ones((16,)), "b": jnp.ones((8,))}
key = jax.random.PRNGKey(3)
grads = {k: jax.random.normal(jax.random.fold_in(key, i), (n,) + v.shape)
         for i, (k, v) in enumerate(params.items())}
tmap = jax.tree_util.tree_map
mesh = make_mesh((n, 1), ("data", "model"))
RULES = ("^norm$=natural,^emb$=topk_ef:k=16,^w2$=randk:k=8,"
         "^w1$=diana:block=16,*=identity")

report = {}
for bucketed in (False, True):
    for part_on in (False, True):
        spec = ParticipationSpec(q=0.5, dropout=0.2) if part_on else None
        pol = CompressionPolicy(rules=parse_rules(RULES), bucketed=bucketed,
                                participation=spec)
        st = init_state(params, pol, n)

        def body(gs, h_w, h_s, k, telemetry):
            g_local = tmap(lambda g: g[0], gs)
            widx = jax.lax.axis_index("data")
            wkey = jax.random.fold_in(k, widx)
            kw = {}
            if part_on:
                kw = dict(part_key=jax.random.fold_in(k, PART_FOLD),
                          step=0, worker_index=widx)
            out = aggregate_shardmap(
                g_local, DianaState(h_w, h_s, None, None), wkey, pol,
                axis_names=("data",), n_workers=n, telemetry=telemetry, **kw)
            if telemetry:
                ghat, ns, tel = out
                return ghat, ns.h_worker, ns.h_server, tel.m2, tel.var, tel.ok
            ghat, ns = out
            z = jnp.zeros((len(st.h_worker),), jnp.float32)
            return ghat, ns.h_worker, ns.h_server, z, z, jnp.asarray(True)

        def run(telemetry):
            fn = shard_map(lambda gs, hw, hs, k: body(gs, hw, hs, k, telemetry),
                mesh=mesh,
                in_specs=(tmap(lambda _: P("data"), grads),
                          tmap(lambda _: P("data"), st.h_worker),
                          tmap(lambda _: P(), st.h_server), P()),
                out_specs=(tmap(lambda _: P(), params),
                           tmap(lambda _: P("data"), st.h_worker),
                           tmap(lambda _: P(), st.h_server), P(), P(), P()),
                axis_names={"data"}, check_vma=False)
            return jax.jit(fn)(grads, st.h_worker, st.h_server, key)

        g0, hw0, hs0, _, _, _ = run(False)
        g1, hw1, hs1, m2, var, ok = run(True)
        v_ref, _, tel_ref = reference_step(
            grads, reference_init(params, pol, n), key, pol, step=0,
            telemetry=True)

        def maxerr(a, b):
            return max(float(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32)).max())
                       for x, y in zip(jax.tree_util.tree_leaves(a),
                                       jax.tree_util.tree_leaves(b)))
        report[f"{'bucketed' if bucketed else 'perleaf'}/"
               f"{'elastic' if part_on else 'full'}"] = {
            "ghat": maxerr(g0, g1), "hw": maxerr(hw0, hw1),
            "hs": maxerr(hs0, hs1),
            "m2_vs_ref": maxerr(m2, tel_ref.m2),
            "var_vs_ref": maxerr(var, tel_ref.var),
            "ok_agree": int(bool(ok) == bool(tel_ref.ok)),
            "n_groups": int(m2.shape[0]),
        }
print(json.dumps(report))
"""
    report = json.loads(run_py(code).strip().splitlines()[-1])
    assert len(report) == 4
    for pairing, errs in report.items():
        assert errs["n_groups"] == 5, (pairing, errs)
        assert errs["ok_agree"] == 1, (pairing, errs)
        for k in ("ghat", "hw", "hs"):  # the pure-observer law is BITWISE
            assert errs[k] == 0.0, (pairing, k, errs)
        for k in ("m2_vs_ref", "var_vs_ref"):
            assert errs[k] < 1e-6, (pairing, k, errs)


# ---------------------------------------------------------------------------
# Budget law
# ---------------------------------------------------------------------------

def _two_group_controller(budget=18.0, **kw):
    params = {"loud": jnp.zeros((256,)), "quiet": jnp.zeros((256,))}
    pol = CompressionPolicy(
        rules=parse_rules("^loud$=diana:block=64,*=diana:block=64"),
        bucketed=False)
    return params, BudgetController(base=pol, budget_bits_per_dim=budget, **kw)


def test_budget_law_energy_sweep():
    """Every allocator emission obeys policy_bits_per_dim <= budget — over
    random energy profiles and a budget ladder (ground-truth accounting)."""
    params = mesh_params()
    pol = CompressionPolicy(rules=parse_rules(
        "^norm$|^b$=natural,^emb$=topk_ef:k=32,*=diana:block=16"))
    rng = np.random.default_rng(0)
    for budget in (3.0, 6.0, 12.0, 24.0):
        ctl = BudgetController(base=pol, budget_bits_per_dim=budget)
        n = partition_for(pol, params).n_groups
        for trial in range(8):
            energy = tuple(float(x) for x in rng.lognormal(0, 3, n))
            state = ControllerState(step=1, ema_m2=energy, ema_var=energy,
                                    count=5)
            choice = allocate(ctl, state, params)
            emitted = ctl.policy_for(choice)
            bits = policy_bits_per_dim(emitted, params)
            assert bits <= budget + 1e-6, (budget, trial, choice, bits)


def test_budget_infeasible_raises():
    """A budget below the lattice floor must raise, never emit over-budget."""
    params, ctl = _two_group_controller(budget=0.05)
    state = ControllerState(step=1, ema_m2=(1.0, 1.0), ema_var=(0.0, 0.0),
                            count=1)
    with pytest.raises(ValueError, match="infeasible"):
        allocate(ctl, state, params)


def test_allocator_spends_budget_on_loud_group():
    """Skewed energy buys exactness where the energy is: at budget 18 the
    loud group gets identity (omega 0), the quiet group a cheap quantizer."""
    params, ctl = _two_group_controller(budget=18.0)
    state = ControllerState(step=1, ema_m2=(100.0, 0.01), ema_var=(0.0, 0.0),
                            count=5)
    choice = allocate(ctl, state, params)
    emitted = ctl.policy_for(choice)
    assert emitted.rules[0].spec.method == "identity"
    assert emitted.rules[1].spec.method != "identity"
    assert policy_bits_per_dim(emitted, params) <= 18.0 + 1e-6
    # Swapped energies -> swapped allocation (the allocator follows m2).
    state2 = ControllerState(step=1, ema_m2=(0.01, 100.0), ema_var=(0.0, 0.0),
                             count=5)
    emitted2 = ctl.policy_for(allocate(ctl, state2, params))
    assert emitted2.rules[1].spec.method == "identity"
    assert emitted2.rules[0].spec.method != "identity"


# ---------------------------------------------------------------------------
# Reallocation law: dwell, hysteresis, warmup, mid-run stats shift
# ---------------------------------------------------------------------------

def _sample(m2, ok=True):
    n = len(m2)
    return GroupTelemetry(m2=jnp.asarray(m2, jnp.float32),
                          var=jnp.zeros((n,), jnp.float32),
                          ok=jnp.asarray(ok))


def test_stats_shift_triggers_reallocation():
    """ISSUE acceptance: group statistics shift mid-run -> the emitted policy
    CHANGES (within the lattice, only after the dwell window) while every
    emitted policy stays at/under budget and the skeleton is preserved."""
    # ema_decay=0.5: a few post-shift samples flip the energy ranking hard
    # enough that the allocator's optimum actually moves (with slow EMAs the
    # near-tie region legitimately prefers natural-on-both).
    params, ctl = _two_group_controller(budget=18.0, interval=5,
                                        hysteresis=0.05, ema_decay=0.5)
    state = init_controller_state(ctl, params)
    lattice_policies = set()
    for ri in range(len(ctl.base.rules)):
        for c in range(len(ctl.lattice[ri])):
            lattice_policies.add(ctl.base.with_rule_specs(
                [ctl.lattice[i][c if i == ri else 0]
                 for i in range(len(ctl.base.rules))]))

    emitted = []  # (step, policy)
    for t in range(30):
        m2 = (100.0, 0.01) if t < 15 else (0.01, 100.0)
        state = observe(ctl, state, _sample(m2))
        state, pol = maybe_reallocate(ctl, state, params)
        if pol is not None:
            emitted.append((state.step, pol))

    assert len(emitted) >= 2, "the mid-run stats shift must trigger a switch"
    first_pol, second_pol = emitted[0][1], emitted[-1][1]
    assert first_pol != second_pol
    # Phase 1: loud group 0 -> identity; phase 2 the allocation swaps.
    assert first_pol.rules[0].spec.method == "identity"
    assert second_pol.rules[1].spec.method == "identity"
    names = [r.label() for r in ctl.base.rules]
    for step, pol in emitted:
        # budget law on every emission
        assert policy_bits_per_dim(pol, params) <= 18.0 + 1e-6
        # within the pre-declared lattice (per-rule candidate membership)
        for i, r in enumerate(pol.rules):
            assert r.spec in ctl.lattice[i], (i, r.spec)
        # skeleton preserved: labels and patterns never change
        assert [r.label() for r in pol.rules] == names
        assert [r.pattern for r in pol.rules] == \
            [r.pattern for r in ctl.base.rules]
    # dwell: consecutive switches at least `interval` steps apart
    steps = [s for s, _ in emitted]
    assert all(b - a >= ctl.interval for a, b in zip(steps, steps[1:])), steps


def test_dwell_and_hysteresis_stable_under_constant_stats():
    """Constant statistics: exactly ONE emission (the first allocation);
    re-solving after every dwell window finds the same choice and stays."""
    params, ctl = _two_group_controller(budget=18.0, interval=3,
                                        hysteresis=0.1)
    state = init_controller_state(ctl, params)
    emissions = 0
    for t in range(20):
        state = observe(ctl, state, _sample((100.0, 0.01)))
        state, pol = maybe_reallocate(ctl, state, params)
        emissions += pol is not None
    assert emissions == 1


def test_warmup_dense_then_budgeted():
    """No allocation during warmup; the warmup mutation is all-identity on
    the SAME skeleton; the first post-warmup emission obeys the budget."""
    params, ctl = _two_group_controller(budget=18.0, interval=2,
                                        warmup_dense_steps=5)
    warm = ctl.warmup_policy()
    assert all(r.spec.method == "identity" for r in warm.rules)
    assert [r.label() for r in warm.rules] == \
        [r.label() for r in ctl.base.rules]
    state = init_controller_state(ctl, params)
    for t in range(10):
        state = observe(ctl, state, _sample((1.0, 1.0)))
        state, pol = maybe_reallocate(ctl, state, params)
        if t + 1 < 5:  # observe() advanced the clock to t+1
            assert pol is None, f"allocated during warmup at step {t + 1}"
        if pol is not None:
            assert policy_bits_per_dim(pol, params) <= 18.0 + 1e-6
    assert state.choice is not None


def test_degraded_samples_freeze_emas():
    params, ctl = _two_group_controller()
    state = init_controller_state(ctl, params)
    state = observe(ctl, state, _sample((4.0, 2.0)))
    frozen = observe(ctl, state, _sample((999.0, 999.0), ok=False))
    assert frozen.ema_m2 == state.ema_m2
    assert frozen.count == state.count
    assert frozen.step == state.step + 1  # the clock still advances


# ---------------------------------------------------------------------------
# Convergence law: adaptive at budget B vs best static uniform at B
# ---------------------------------------------------------------------------

def test_adaptive_matches_or_beats_static_uniform_at_budget():
    """Quadratic with skewed group energy (loud 10x, quiet 1x): the adaptive
    policy at budget B reaches a final error no worse than the best STATIC
    uniform lattice policy whose bits/dim fits the same B.  Deterministic
    (seeded draws); the adaptive run's telemetry drives real reallocation."""
    n, d, T, gamma, budget = 4, 256, 40, 0.06, 18.0
    key = jax.random.PRNGKey(5)
    xstar = {"loud": jax.random.normal(jax.random.fold_in(key, 0), (d,)),
             "quiet": jax.random.normal(jax.random.fold_in(key, 1), (d,))}
    scales = {"loud": 10.0, "quiet": 1.0}
    # Worker heterogeneity: fixed zero-mean offsets b_i (DIANA's h absorbs).
    offs = {g: (lambda o: o - o.mean(0))(
        jax.random.normal(jax.random.fold_in(key, 10 + i), (n, d)))
        for i, g in enumerate(xstar)}

    def grads_at(x):
        return {g: scales[g] * (x[g][None, :] - xstar[g][None, :]) + offs[g]
                for g in xstar}

    def final_err(pol, controller=None):
        x = {g: jnp.zeros((d,)) for g in xstar}
        params = x
        state = reference_init(params, pol, n)
        cstate = init_controller_state(controller, params) if controller else None
        emitted_bits = []
        for t in range(T):
            out = reference_step(grads_at(x), state, jax.random.fold_in(key, t),
                                 pol, telemetry=controller is not None)
            if controller is not None:
                v, state, telem = out
                cstate = observe(controller, cstate, telem)
                cstate, new_pol = maybe_reallocate(controller, cstate, params)
                if new_pol is not None:
                    # per-leaf skeleton-preserving switch: the state template
                    # (group names, leaf shapes) is unchanged — memories carry
                    emitted_bits.append(policy_bits_per_dim(new_pol, params))
                    pol = new_pol
            else:
                v, state = out
            x = {g: x[g] - gamma * v[g] for g in x}
        err = sum(float(jnp.sum(jnp.square(x[g] - xstar[g]))) for g in x)
        return err, emitted_bits

    base = CompressionPolicy(
        rules=parse_rules("^loud$=diana:block=64,*=diana:block=64"),
        bucketed=False)
    ctl = BudgetController(base=base, budget_bits_per_dim=budget, interval=5,
                           hysteresis=0.05)
    params0 = {g: jnp.zeros((d,)) for g in xstar}

    # Static uniform competitors: every lattice candidate applied to BOTH
    # rules whose uniform policy fits the budget (identity-both = 32 > 18
    # is correctly excluded).
    statics = {}
    for c, cand in enumerate(ctl.lattice[0]):
        uni = base.with_rule_specs([cand, cand])
        bits = policy_bits_per_dim(uni, params0)
        if bits <= budget + 1e-6:
            statics[f"cand{c}:{cand.method}"] = final_err(uni)[0]
    assert statics, "no feasible static uniform candidate — lattice rot"

    err_adaptive, emitted_bits = final_err(base, controller=ctl)
    assert emitted_bits, "the controller never reallocated"
    assert all(b <= budget + 1e-6 for b in emitted_bits)
    best_static = min(statics.values())
    assert err_adaptive <= best_static * 1.05, (err_adaptive, statics)


# ---------------------------------------------------------------------------
# size-adaptive constructor + trainer surface
# ---------------------------------------------------------------------------

def test_size_adaptive_constructor():
    tree = {"emb": jnp.zeros((64, 64)), "w": jnp.zeros((128, 32)),
            "norm": jnp.zeros((16,)), "b": jnp.zeros((8,))}
    pol = CompressionPolicy.size_adaptive(tree, threshold_dims=1024)
    assert [r.label() for r in pol.rules] == ["small", "bulk"]
    assert pol.rules[0].spec.method == "identity"
    assert pol.rules[1].spec.method == "diana"
    part = partition_for(pol, tree)
    split = part.split(tree)
    small_sizes = [int(l.size) for l in split[0]]
    bulk_sizes = [int(l.size) for l in split[1]]
    assert all(s < 1024 for s in small_sizes) and len(small_sizes) == 2
    assert all(s >= 1024 for s in bulk_sizes) and len(bulk_sizes) == 2
    # A valid controller skeleton: with_rule_specs round-trips it.
    assert pol.with_rule_specs([None, None]).rules[0].label() == "small"
    # No small leaves -> single catch-all rule, still lintable.
    big_only = CompressionPolicy.size_adaptive(
        {"w": jnp.zeros((64, 64))}, threshold_dims=8)
    assert [r.label() for r in big_only.rules] == ["bulk"]


def test_size_adaptive_trainer_resolution():
    """--comp-policy size-adaptive resolves against the model's own tree
    with the model-wide fields carried from the config."""
    from repro.configs import get_config, reduced
    from repro.launch.train import resolve_policy_arg

    cfg = reduced(get_config("llama3.2-1b"))
    pol = resolve_policy_arg(cfg, "size-adaptive")
    assert [r.label() for r in pol.rules] == ["small", "bulk"]
    assert pol.rules[1].spec.method == cfg.compression
    assert pol.bucketed == cfg.comp_bucketed
    # The small rule actually owns the model's norm/bias leaves.
    import jax as _jax

    from repro.models import init_model

    shapes = _jax.eval_shape(lambda k: init_model(cfg, k),
                             _jax.ShapeDtypeStruct((2,), "uint32"))
    part = partition_for(pol, shapes)
    assert part.n_groups == 2
    small_leaves = part.split(shapes)[0]
    assert small_leaves and all(
        int(l.size) < 2 ** 16 for l in small_leaves)


# ---------------------------------------------------------------------------
# Skeleton mutation + state migration
# ---------------------------------------------------------------------------

def test_with_rule_specs_contract():
    pol = CompressionPolicy(rules=parse_rules(
        "^emb$=topk_ef:k=16,*=diana:block=16"))
    out = pol.with_rule_specs([ChannelSpec("natural"), None])
    assert out.rules[0].spec.method == "natural"
    assert out.rules[1].spec == pol.rules[1].spec
    assert [r.label() for r in out.rules] == [r.label() for r in pol.rules]
    with pytest.raises(ValueError, match="rule"):
        pol.with_rule_specs([None])  # wrong length
    with pytest.raises(ValueError, match="h_down"):
        pol.with_rule_specs([None, None], downs=[ChannelSpec("natural"), None])


def test_migrate_diana_state_carries_and_rezeroes():
    """Unchanged groups carry their memories; a group whose flat layout
    changes re-inits to zeros on BOTH sides (h_server = mean h_i holds)."""
    n = 4
    params = mesh_params()
    pol = CompressionPolicy(rules=parse_rules(
        "^emb$=topk_ef:k=16,*=diana:block=16"), bucketed=True)
    old = init_state(params, pol, n)
    old = jax.tree_util.tree_map(lambda l: jnp.ones_like(l), old)
    # Swap only the catch-all group's operator; block 16 -> 64 changes the
    # bucketed group's padded flat size on this tree -> re-zero; the topk_ef
    # group's layout is untouched -> carried.
    new_pol = pol.with_rule_specs(
        [None, ChannelSpec("diana", block_size=64)])
    new = migrate_diana_state(old, params, new_pol, n)
    tmpl = init_state(params, new_pol, n)
    assert jax.tree_util.tree_structure(new) == jax.tree_util.tree_structure(tmpl)
    names = sorted(old.h_worker)
    assert sorted(new.h_worker) == names  # group names pinned
    for g in names:
        ow, nw = old.h_worker[g], new.h_worker[g]
        carried = all(
            tuple(a.shape) == tuple(b.shape)
            for a, b in zip(jax.tree_util.tree_leaves(ow),
                            jax.tree_util.tree_leaves(nw)))
        for hw, hs in zip(jax.tree_util.tree_leaves(new.h_worker[g]),
                          jax.tree_util.tree_leaves(new.h_server[g])):
            if carried:
                assert float(jnp.abs(hw).sum()) > 0
                assert float(jnp.abs(hs).sum()) > 0
            else:
                # re-zeroed on BOTH sides -> invariant holds trivially
                assert float(jnp.abs(hw).sum()) == 0
                assert float(jnp.abs(hs).sum()) == 0
    changed = [g for g in names
               if any(tuple(a.shape) != tuple(b.shape) for a, b in zip(
                   jax.tree_util.tree_leaves(old.h_worker[g]),
                   jax.tree_util.tree_leaves(new.h_worker[g])))]
    assert changed, "the block swap should have re-laid-out the bucket group"
    assert len(changed) < len(names), "the untouched group must carry"


# ---------------------------------------------------------------------------
# Checkpoint round-trip + restore hints
# ---------------------------------------------------------------------------

def test_controller_metadata_round_trip():
    params, ctl = _two_group_controller(interval=7, warmup_dense_steps=2)
    state = init_controller_state(ctl, params)
    for t in range(9):
        state = observe(ctl, state, _sample((3.0, 0.5)))
        state, _ = maybe_reallocate(ctl, state, params)
    doc = controller_metadata(ctl, state)
    back = state_from_metadata(json.loads(json.dumps(doc)))
    assert back == state
    assert doc["budget_bits_per_dim"] == ctl.budget_bits_per_dim
    assert doc["interval"] == 7 and doc["warmup_dense_steps"] == 2


def test_controller_restore_hints(tmp_path):
    from repro.checkpoint import controller_restore_hint, save_checkpoint

    params, ctl = _two_group_controller()
    state = init_controller_state(ctl, params)
    arr = {"x": jnp.zeros((3,))}

    # pre-controller checkpoint + live controller -> "starts fresh" hint
    save_checkpoint(str(tmp_path), 0, arr, metadata={"policy": {}})
    hint = controller_restore_hint(str(tmp_path), ctl)
    assert hint is not None and "predates" in hint
    # no controller either side -> silent
    assert controller_restore_hint(str(tmp_path), None) is None

    save_checkpoint(str(tmp_path), 1, arr,
                    metadata={"controller": controller_metadata(ctl, state)})
    # matching budget -> no hint
    assert controller_restore_hint(str(tmp_path), ctl) is None
    # checkpoint carries controller state, resume without one -> inverse hint
    hint = controller_restore_hint(str(tmp_path), None)
    assert hint is not None and "without a controller" in hint
    # budget changed -> hint
    import dataclasses

    other = dataclasses.replace(ctl, budget_bits_per_dim=4.0)
    hint = controller_restore_hint(str(tmp_path), other)
    assert hint is not None and "budget changed" in hint


# ---------------------------------------------------------------------------
# tools/check_controller.py linter
# ---------------------------------------------------------------------------

def test_check_controller_repo_defaults_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_controller
        assert check_controller.main(["--no-models"]) == 0
    finally:
        sys.path.pop(0)


def test_check_controller_catches_rot():
    """A lattice candidate that flips a rule's layout (schedule hijack) or
    fails to resolve must be flagged."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_controller

        pol = CompressionPolicy(rules=parse_rules(
            "^emb$=topk_ef:k=16,*=diana:block=16"), bucketed=True)
        good = BudgetController(base=pol, budget_bits_per_dim=32.0)
        assert check_controller.lattice_errors("good", good) == []
        bad_lat = list(good.lattice)
        bad_lat[0] = tuple(bad_lat[0]) + (
            ChannelSpec("natural", layout="perleaf"),)
        bad = BudgetController(base=pol, budget_bits_per_dim=32.0,
                               lattice=tuple(bad_lat))
        errs = check_controller.lattice_errors("bad", bad)
        assert errs and any("layout" in e for e in errs)
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# Trainer CLI end-to-end (4-worker mesh, subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_trainer_cli_controller_end_to_end(tmp_path):
    """--budget-bits-per-dim on the real trainer: warmup -> first allocation
    -> (possible) switches, telemetry metrics flowing, checkpoint metadata
    carrying the controller doc; every printed allocation under budget."""
    code = f"""
from repro.launch.train import main
main(["--arch", "llama3.2-1b", "--reduced", "--steps", "6",
      "--comp-policy", "default", "--budget-bits-per-dim", "6",
      "--controller-interval", "2", "--warmup-dense-steps", "1",
      "--batch", "4", "--seq", "32",
      "--checkpoint-dir", {str(tmp_path)!r}])
"""
    out = run_py(code)
    assert "controller: switching policy" in out, out
    from repro.checkpoint import load_metadata

    meta = load_metadata(str(tmp_path))
    assert meta is not None and "controller" in meta
    doc = meta["controller"]
    assert doc["budget_bits_per_dim"] == 6.0
    assert doc["step"] >= 5 and doc["choice"] is not None
    back = state_from_metadata(doc)
    assert back.count > 0  # telemetry actually accumulated
