"""``correct`` at test size on the CPU: a sound run passes; the control (the
reference in float8 put in the program's place) and each fault the cells
can have, planted underneath the timed path, are refused.

The runs drive the harness whole except its look for a chip and the
persistent compile cache.  The limits here are the test size's own, set
from CPU readings of these seeds (sound runs read at most a half of each)."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from bench import compare, harness, tiny
from bench.reference import Readings

LIMITS = {"compared": {
    "loss_gap": {"limit": 5e-4},           # sound <= 2.4e-4, control >= 6.4e-4
    "grad_gap_median": {"limit": 0.1},     # sound <= 0.03, half batch >= 0.15
    "change_gap_median": {"limit": 0.15},  # sound <= 0.04, state unchanged 1.0
}}
SEED = 2**31 + 5


def _run(spec, wrap=None, seed=SEED):
    return harness.run(spec, seed, 0.3, False, t_start=time.perf_counter(),
                       require_tpu=False, compile_cache=False, wrap_step=wrap)


def _half(batch):
    return jax.tree_util.tree_map(lambda a: a[: a.shape[0] // 2], batch)


def test_sound_run_is_correct_and_reports():
    r = _run(tiny.spec("mamba", limits=LIMITS))
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks" and set(r["checks"]) == set(LIMITS["compared"])
    assert r["attempted"] >= 1 and r["failed"] == 0
    for name in ("tokens_per_s", "setup_s"):
        assert r["metrics"][name]["value"] > 0
    assert r["device"]["count"] == 1


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_refused(fault):
    if fault == "state_unchanged":
        wrap = lambda compiled, cell: tiny.state_unchanged(compiled)
    else:
        wrap = lambda compiled, cell: (lambda p, o, b, k: cell.step(p, o, _half(b), k))
    r = _run(tiny.spec("mamba", limits=LIMITS), wrap)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("seed", [11, 977, 123456789])
def test_control_is_refused(seed):
    cell = harness.Cell(tiny.spec("mamba", limits=LIMITS), require_tpu=False,
                        compile_cache=False)
    pool = cell.host_pool(seed, harness.PROOF_STEPS)
    ref = cell.reference(seed, pool, stream=seed)
    control = cell.reference(seed, pool, stream=seed + 2, precision="fp8")
    draws = cell.reference(seed, pool, stream=seed + 1)
    assert compare.decide(compare.readings(draws, ref), LIMITS)[0]
    assert not compare.decide(compare.readings(control, ref), LIMITS)[0]


FOUR = """
import json, sys, time
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from bench import harness, tiny
limits = {limits!r}
spec = tiny.spec("mamba", workers=4, limits=limits)
out = {{}}
out["sound"] = harness.run(spec, 977, 0.3, False, t_start=time.perf_counter(),
                           require_tpu=False, compile_cache=False)["correct"]
real = jax.lax.all_gather
def own_only(x, axis_name, **kw):
    # each worker's own payload, as if the exchange had been left out
    n = jax.lax.psum(1, axis_name)
    return jnp.broadcast_to(x[None], (n,) + x.shape)
jax.lax.all_gather = own_only
try:
    out["no_exchange"] = harness.run(spec, 977, 0.3, False, t_start=time.perf_counter(),
                                     require_tpu=False, compile_cache=False)["correct"]
finally:
    jax.lax.all_gather = real
print(json.dumps(out))
"""


def test_four_workers_sound_and_no_exchange_refused():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", FOUR.format(root=root, limits=LIMITS)],
                         capture_output=True, text=True, timeout=600, env=env, cwd=root)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False}


def test_a_compared_number_from_its_own_file():
    """``bench/testdata/readings/first_loss_gap.py`` is read beside the
    built-in numbers and decided on; a compared name with neither a
    built-in nor a file is an error that names the file to add."""
    where = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata", "readings")
    norms = {"a": 1.0, "b": 2.0}
    ref = Readings([3.0, 2.5, 2.0], norms, norms, norms)
    prog = Readings([3.25, 2.5, 2.0], norms, norms)
    limits = {"compared": {"loss_gap": {"limit": 1.0}, "first_loss_gap": {"limit": 0.5}}}
    values = compare.readings(prog, ref, where)
    assert values["first_loss_gap"] == 0.25 and "first_loss_gap" not in compare.readings(prog, ref)
    assert compare.decide(values, limits)[0]
    builtin = compare.readings(prog, ref)
    ok, checks = compare.decide(builtin, limits, prog, ref, where)
    assert ok and checks["first_loss_gap"] == {"value": 0.25, "limit": 0.5}
    limits["compared"]["first_loss_gap"]["limit"] = 0.2
    assert not compare.decide(builtin, limits, prog, ref, where)[0]
    assert not compare.decide(builtin, limits, Readings([], norms, norms), ref, where)[0]
    limits["compared"]["absent_gap"] = {"limit": 1.0}
    with pytest.raises(ValueError, match="bench/testdata/readings/absent_gap.py"):
        compare.decide(builtin, limits, prog, ref, where)
    with pytest.raises(ValueError, match="add bench/readings/absent_gap.py"):
        compare.decide(builtin, {"compared": {"absent_gap": {"limit": 1.0}}}, prog, ref)
