"""The program's named scopes reach the compiled train step, and the
trainer's ``--profile-dir`` writes its host spans.

The scopes (``model.*``, ``train.*``, ``diana.*``) are what the benchmark's
trace reduction (``bench/scopes.py``) reads from each instruction's
``op_name`` metadata.  Each compile runs in a subprocess so the virtual
device count never leaks into this process."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL = {"model.embed", "model.blocks", "model.head_loss", "model.mixer"}
TRAIN = {"train.optimizer", "train.metrics"}
DIANA = {"diana.round", "diana.flatten", "diana.encode", "diana.decode_own",
         "diana.memory", "diana.decode_sum_apply", "diana.unflatten"}

COMPILE = """
import json, re, sys
from dataclasses import replace
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
arch, n, scoped = sys.argv[1], int(sys.argv[2]), sys.argv[3] == "scoped"
if not scoped:
    import contextlib

    @contextlib.contextmanager
    def no_scope(name):
        yield

    jax.named_scope = no_scope      # before the program is imported
from repro.configs import get_config, reduced
from repro.launch.mesh import make_mesh
from repro.launch.train import build_train_step, make_optimizer, train_state_shardings
from repro.models import init_model

cfg = replace(reduced(get_config(arch)), compression="diana", remat="full",
              tie_embeddings=True)
mesh = make_mesh((n, 1), ("data", "model"))
opt = make_optimizer(cfg, lr=3e-4, inner="momentum", beta=0.9)
ps = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.PRNGKey(0))
os_ = jax.eval_shape(lambda p: opt.init(p, n), ps)
p_sh, o_sh = train_state_shardings(cfg, opt, mesh, ps, os_)
shaped = lambda t, sh: jax.tree_util.tree_map(
    lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), t, sh)
rows = NamedSharding(mesh, P("data"))
batch = {k: jax.ShapeDtypeStruct((2 * n, 64), jnp.int32, sharding=rows)
         for k in ("tokens", "labels")}
key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=NamedSharding(mesh, P()))
text = build_train_step(cfg, opt, mesh).lower(
    shaped(ps, p_sh), shaped(os_, o_sh), batch, key).compile().as_text()
op_name = re.compile(r'op_name="([^"]*)"')
names = op_name.findall(text)
gathers = [l for l in text.splitlines()
           if re.search(r"\\sall-gather(-start)?\\(", l.split("=", 1)[-1])]
print(json.dumps({
    "text": text,
    "scopes": sorted({s for o in names
                      for s in re.findall(r"(?:^|[/(])((?:model|train|diana)\\.[a-z_]+)(?=[/)]|$)", o)}),
    "gathers": [(op_name.search(l) or [None, ""])[1] for l in gathers],
    "decode": [o for o in names if "diana.decode_sum_apply" in o],
    "backward": any("transpose(" in o and "model.mixer" in o for o in names),
    "recompute": any("rematted_computation" in o and "model.mixer" in o for o in names),
}))
"""


def _compile(arch: str, n: int, scoped: bool = True) -> dict:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
    out = subprocess.run([sys.executable, "-c", COMPILE, arch, str(n),
                          "scoped" if scoped else "bare"], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("n", [1, 4])
def test_train_step_carries_every_scope(n):
    got = _compile("mamba2-130m", n)
    assert MODEL | TRAIN | DIANA <= set(got["scopes"]), sorted(
        (MODEL | TRAIN | DIANA) - set(got["scopes"]))
    assert got["backward"] and got["recompute"]
    assert got["decode"] and all("diana.round/" in o for o in got["decode"])
    if n > 1:
        # the round's one exchange: every all-gather of the step is DIANA's
        assert got["gathers"] and "diana.allgather" in got["scopes"]
        for o in got["gathers"]:
            assert "diana.round/" in o and "diana.allgather" in o, o


def test_scopes_are_metadata_only():
    """The four-worker step compiles to the same program with its scopes and
    without them, once metadata is stripped (``tools/strip_hlo.py``)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from strip_hlo import strip

    scoped, bare = _compile("mamba2-130m", 4), _compile("mamba2-130m", 4, scoped=False)
    assert bare["scopes"] == [] and scoped["scopes"]
    assert strip(scoped["text"]) == strip(bare["text"])


def test_mlp_scope_on_a_model_with_an_mlp():
    assert "model.mlp" in _compile("llama3.2-1b", 1)["scopes"]


def test_profile_flags_write_the_step_spans(tmp_path):
    """``--profile-dir``/``--profile-steps`` trace the named steps with the
    trainer's host spans; without the flags nothing is written."""
    code = f"""
import glob, json
from jax.profiler import ProfileData
from repro.launch.train import main
args = ["--arch", "mamba2-130m", "--reduced", "--steps", "3", "--batch", "2",
        "--seq", "32"]
main(args + ["--profile-dir", {str(tmp_path / "on")!r}, "--profile-steps", "0:3"])
main(args + ["--profile-steps", "0:3"])
(path,) = glob.glob({str(tmp_path / "on")!r} + "/**/*.xplane.pb", recursive=True)
names = [e.name for p in ProfileData.from_file(path).planes for l in p.lines
         for e in l.events]
print(json.dumps({{n: names.count(n) for n in set(names) if n.startswith("train")}}))
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    counts = json.loads(out.stdout.strip().splitlines()[-1])
    assert counts["train.step"] == 3
    assert counts["train.feed"] == 3 and counts["train.block"] == 3
    assert counts["train"] == 3                      # one StepTraceAnnotation a step
    assert sorted(os.listdir(tmp_path)) == ["cache", "on"]


def test_profile_steps_are_checked():
    from repro.launch.train import _profile_window

    assert _profile_window(None, "1:4", 3) is None
    assert _profile_window("prof", "1:4", 3) == range(1, 3)   # cut at --steps
    for bad in ("4:6", "2:1", "2", "a:b"):
        with pytest.raises(SystemExit):
            _profile_window("prof", bad, 3)
