"""``chip_smoke.py`` refuses to report without a TPU, and the entry points'
compile cache lands where ``repro.launch.compile_cache`` says.

Each case runs in a subprocess on the CPU, so no cache setting leaks into
the test process (which must write no cache entries).
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, drop=(), cwd=REPO):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=300, env=env, cwd=cwd)


def _assert_no_result(out):
    assert out.returncode != 0, out.stdout
    assert '"ok"' not in out.stdout, out.stdout


def test_chip_smoke_fails_without_tpu():
    out = _run([os.path.join(REPO, "chip_smoke.py")])
    _assert_no_result(out)
    assert "no TPU" in out.stderr


def test_chip_smoke_fails_outside_the_checkout(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run([str(tmp_path / "chip_smoke.py")], drop=("PYTHONPATH",),
               cwd=tmp_path)
    _assert_no_result(out)


CACHE_PROBE = """
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
where = use_compile_cache()
if {compile}:
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({{"where": where,
                  "config": jax.config.jax_compilation_cache_dir}}))
"""


def test_compile_cache_follows_the_environment(tmp_path):
    cache = tmp_path / "cache"
    out = _run(["-c", CACHE_PROBE.format(compile=True)], env_extra={
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"where": str(cache), "config": str(cache)}
    assert any(cache.iterdir()), "no cache entry was written"


def test_compile_cache_defaults_to_the_checkout():
    out = _run(["-c", CACHE_PROBE.format(compile=False)],
               drop=("JAX_COMPILATION_CACHE_DIR",))
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    want = os.path.join(REPO, ".jax_cache")
    assert got == {"where": want, "config": want}
