"""``BENCHMARK.json``: every workload finds its files by name, every name
and unit keeps to the allowed characters, and ``bench/run.py`` refuses to
report without a TPU or outside a checkout."""

import os
import shutil
import subprocess
import sys

import pytest

from bench import cells

ROOT = cells.ROOT
BENCH = cells.BENCH
SPEC = cells.benchmark()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_to_its_files(workload):
    spec = cells.resolve(workload)
    cell, doc, traffic = spec["cell"], spec["config_doc"], spec["traffic"]
    assert doc["name"] == cell["config"]
    assert spec["config"]["file"] == f"bench/configs/{cell['config']}.json"
    assert traffic["workers"] == cell["chips"]
    assert spec["limits"]["compared"], "a cell compares at least one number"
    for name, lim in spec["limits"]["compared"].items():
        assert lim["lower"] < lim["limit"] < lim["upper"], name
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(cells.metric_path(m["name"]))


def test_config_files_state_their_cuts():
    for c in SPEC["configs"]:
        doc = cells.load_json(os.path.join(ROOT, c["file"]))
        assert doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert key in doc["model"] and doc["reduced"][key] != doc["model"][key]


def test_names_units_and_references():
    names = ([c["name"] for c in SPEC["configs"]] + WORKLOADS
             + [m["name"] for m in METRICS]
             + [w["config"] for w in SPEC["workloads"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    for n in names:
        assert cells.NAME_RE.match(n), n
    for kind in (SPEC["configs"], SPEC["workloads"], METRICS):
        assert len({x["name"] for x in kind}) == len(kind)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in METRICS:
        assert cells.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in WORKLOADS
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


def _run(args, cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], capture_output=True, text=True,
                          timeout=300, env=env, cwd=cwd)


def _no_result(out):
    assert out.returncode != 0, out.stdout[-2000:]
    assert '"correct"' not in out.stdout


def test_run_refuses_without_a_tpu():
    out = _run(["bench/run.py", "--workload", WORKLOADS[0], "--seed", "3000000001",
                "--seconds", "1", "--trace", "0"], ROOT)
    _no_result(out)
    assert "no TPU" in out.stderr


def test_run_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(["bench/run.py", "--workload", WORKLOADS[0], "--seed", "1",
                "--seconds", "1", "--trace", "0"], tmp_path)
    _no_result(out)
