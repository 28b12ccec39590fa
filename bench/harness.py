"""One run of one cell: set-up, the measured window, the check of ``correct``.

Set-up (``setup_s``, from process start to the first timed step): the
compile cache, the mesh ``(chips, 1)`` over ``("data", "model")`` (each chip
one DIANA worker), the weights from the seed on the device, the trainer's
optimizer state and its jitted step compiled for the cell's one shape, the
pool of token batches on the device, and three proof steps.  The proof steps
go through the window's own call and feed; their losses, the momentum after
the first and the parameters after the third are what the comparison reads.

Window: the compiled step on the next batch of the pool, each step ended in
``block_until_ready``, for ``seconds`` seconds, under host spans
(``bench.step``, ``bench.feed``, ``bench.block``) that a traced run
attributes idle gaps to.  A traced run reduces its trace twice, once by
operation (``bench/trace.py``, the context's ``trace``) and once by the
program's named scopes (``bench/scopes.py``, its ``scopes``), for the
per-layer metrics' readers.

Afterwards: the peak device memory is read, the program's state is freed,
and the reference runs the same three steps from the same weights (made
again from the seed) on the same batches.
"""

from __future__ import annotations

import math
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from . import cells, compare, feed, flops, program
from . import reference as R

PROOF_STEPS = 3
N_KEYS = 512
TRACE_MAX_SECONDS = 4.0       # the traced window: at most this long ...
TRACE_MIN_STEPS = 3           # ... and at least this many steps


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class _CompileCounter:
    """Counts JAX's tracing and compilation events while it is armed."""

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event.startswith("/jax/core/compile/"):
            self.count += 1


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def check_devices(chips: int, require_tpu: bool = True):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips and JAX finds {len(devs)}")
    return devs


def use_compile_cache():
    """The program's own choice of directory: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``; every program is cached."""
    from repro.launch.compile_cache import use_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def _device_keys(seed: int, mesh):
    """Per-step PRNG keys, replicated on the mesh."""
    host = np.asarray(jax.device_get(jax.vmap(
        lambda j: jax.random.fold_in(R.seed_key(seed), j))(jnp.arange(N_KEYS))))
    rep = NamedSharding(mesh, P())
    return [jax.device_put(host[j], rep) for j in range(N_KEYS)]


def _free(*trees):
    for t in trees:
        for a in jax.tree_util.tree_leaves(t):
            if isinstance(a, jax.Array) and not a.is_deleted():
                a.delete()


class Cell:
    """The program built once for a cell: configuration, mesh, trainer, and
    the step compiled for the cell's one shape (the same executable serves
    every seed)."""

    def __init__(self, spec: Dict, *, require_tpu: bool = True,
                 compile_cache: bool = True):
        self.spec = spec
        self.chips = spec["cell"]["chips"]
        self.traffic, self.doc = spec["traffic"], spec["config_doc"]
        self.model = self.doc["model"]
        if self.traffic["workers"] != self.chips:
            raise ValueError("the traffic's workers must equal the cell's chips")
        check_devices(self.chips, require_tpu)
        program.import_program()
        if compile_cache:
            use_compile_cache()
        cfg = program.model_config(self.doc, self.traffic)
        program.check_layout(cfg, R.param_layout(self.model))
        self.mesh = program.make_mesh(self.chips)
        self.devices = list(self.mesh.devices.reshape(-1))
        _, self.p_shard, self.init_opt, self.step = program.trainer(cfg, self.traffic, self.mesh)
        self.compiled = None

    def host_pool(self, seed: int, size: Optional[int] = None):
        return [feed.worker_batches(self.traffic, self.model["vocab"], seed, j)
                for j in range(size or self.traffic["pool"])]

    def state(self, seed: int, host_pool):
        """Weights from the seed (made by the same program as the
        reference's, so bit for bit the same, and kept on the host for the
        proof's change), fresh optimizer state, the pool and the per-step
        keys on the device; compiles the step on first use."""
        t0 = time.perf_counter()
        theta0 = R.make_params(self.model, seed)
        self.theta0_host = jax.device_get(theta0)
        params = jax.device_put(theta0, self.p_shard)
        del theta0
        opt_state = self.init_opt(params)
        jax.block_until_ready((params, opt_state))
        t1 = time.perf_counter()
        bsh = program.batch_shardings(feed.global_batch(host_pool[0]), self.mesh)
        pool = [jax.device_put(feed.global_batch(b), bsh) for b in host_pool]
        keys = _device_keys(seed, self.mesh)
        jax.block_until_ready((pool, keys))
        log(f"weights and optimizer state {t1 - t0:.3f}s, pool and keys "
            f"{time.perf_counter() - t1:.3f}s")
        if self.compiled is None:
            t0 = time.perf_counter()
            self.compiled = self.step.lower(params, opt_state, pool[0], keys[0]).compile()
            log(f"compiled the step in {time.perf_counter() - t0:.3f}s")
        return params, opt_state, pool, keys

    def proof(self, call, seed, params, opt_state, pool, keys):
        """The first steps through the window's call and feed: their losses,
        the momentum after the first, the parameters' change after the last."""
        losses, grad_norms = [], None
        for j in range(PROOF_STEPS):
            params, opt_state, m = call(params, opt_state, pool[j], keys[j])
            losses.append(m["loss"])
            if j == 0:
                grad_norms = R.tree_norms(program.momentum(opt_state))
        change = R.host_change_norms(jax.device_get(params), self.theta0_host)
        self.theta0_host = None
        prog = R.Readings([float(x) for x in jax.device_get(losses)], grad_norms, change)
        return params, opt_state, prog

    def reference(self, seed: int, host_pool, *, stream: int, precision: str = "f32",
                  fault: Optional[str] = None) -> R.Readings:
        """The reference's three steps from the seed's weights, made again."""
        params0 = R.make_params(self.model, seed)
        batches = [[(b["tokens"], b["labels"]) for b in host_pool[t]]
                   for t in range(PROOF_STEPS)]
        out = R.diana_reference(self.model, self.traffic, params0, batches, stream,
                                precision=precision, fault=fault, devices=self.devices)
        _free(params0)
        return out


def run(spec: Dict, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, compile_cache: bool = True,
        wrap_step: Optional[Callable] = None) -> Dict:
    """One run; returns the result object (see ``run.py``).  Tests run it
    without a TPU and without the persistent compile cache, and may break
    the timed path: ``wrap_step(compiled, cell)`` returns the call the proof
    steps and the window make."""
    if not spec.get("limits"):
        raise ValueError(f"no limits for {spec['cell']['name']} (bench/limits/)")
    def phase(name):
        log(f"set-up: {name} done at {time.perf_counter() - t_start:.3f}s")

    phase("imports")
    cell = Cell(spec, require_tpu=require_tpu, compile_cache=compile_cache)
    phase("devices and trainer")
    counter = _CompileCounter()
    traffic, model, devices, chips = cell.traffic, cell.model, cell.devices, cell.chips
    host_pool = cell.host_pool(seed)
    phase("host pool")
    params, opt_state, pool, keys = cell.state(seed, host_pool)
    phase("state and step")
    call = wrap_step(cell.compiled, cell) if wrap_step else cell.compiled
    params, opt_state, prog = cell.proof(call, seed, params, opt_state, pool, keys)
    jax.block_until_ready((params, opt_state))
    setup_s = time.perf_counter() - t_start
    phase("proof steps")

    # the measured window
    window_s_target = seconds
    tdir = None
    if trace:
        window_s_target = min(seconds, TRACE_MAX_SECONDS)
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
    step_times, win_losses = [], []
    i = PROOF_STEPS
    counter.armed = True
    w0 = time.perf_counter()
    with TraceAnnotation("bench.window"):
        while True:
            with TraceAnnotation("bench.step"):
                with TraceAnnotation("bench.feed"):
                    batch, key = pool[i % len(pool)], keys[i % N_KEYS]
                ts = time.perf_counter()
                params, opt_state, m = call(params, opt_state, batch, key)
                with TraceAnnotation("bench.block"):
                    jax.block_until_ready((params, opt_state, m))
                te = time.perf_counter()
            step_times.append(te - ts)
            win_losses.append(m["loss"])
            i += 1
            if te - w0 >= window_s_target and (not trace or len(step_times) >= TRACE_MIN_STEPS):
                break
    window_s = te - w0
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
    print(f"compilations in the window: {counter.count}")
    print(f"window: {len(step_times)} steps in {window_s}s; step ms: "
          + " ".join(f"{1e3 * t:.3f}" for t in step_times))

    mem = [d.memory_stats() or {} for d in devices]
    log("memory_stats: " + " ".join(f"{k}={v}" for k, v in sorted(mem[0].items())))
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in mem)
    loss_vals = [float(x) for x in jax.device_get(win_losses)]
    failed = sum(1 for x in loss_vals if not math.isfinite(x))
    hlo = cell.compiled.as_text() if trace else ""
    _free(params, opt_state, pool, keys, m, win_losses)

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    ref = cell.reference(seed, host_pool, stream=seed)
    log(f"reference took {time.perf_counter() - t_ref:.3f}s")
    values = compare.readings(prog, ref)
    print("readings: " + " ".join(f"{k}={v}" for k, v in values.items()))
    print(f"losses: program {prog.losses} reference {ref.losses}")
    correct, checks = compare.decide(values, spec["limits"])

    ctx = dict(
        setup_s=setup_s, window_s=window_s, step_times=step_times,
        steps=len(step_times), chips=chips, workers=traffic["workers"],
        tokens_per_step=traffic["workers"] * traffic["batch_per_worker"] * traffic["seq_len"],
        memory_peak_bytes=peak, model=model, traffic=traffic,
        peaks=_peaks(devices[0]), param_count=flops.param_count(model),
        train_flops_per_token=flops.train_flops_per_token(model, traffic["seq_len"]),
        trace=None, scopes=None,
    )
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(step_times), "failed": failed}
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    if trace:
        from . import scopes as SC
        from . import trace as TR

        t_red = time.perf_counter()
        pd = TR.load(TR.find_xplane(tdir))
        ctx["trace"] = TR.reduce(pd, TR.classify_hlo(hlo))
        ctx["scopes"] = SC.reduce_scopes(pd, hlo)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t_red:.3f}s")
        device.update(busy_s=ctx["trace"]["busy_s"], window_s=ctx["trace"]["window_s"])
    metrics = {}
    for m_ in wanted:
        v = cells.load_metric_reader(m_["name"])(ctx)
        if v is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m_['name']} read nothing")
            continue
        metrics[m_["name"]] = {"value": float(v), "unit": m_["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = ctx["trace"]["breakdown"]
    result["checks"] = checks
    return result


def _peaks(device) -> Optional[Dict]:
    """The TPU's peaks; none for the CPU of the tests."""
    return flops.peaks_for(device.device_kind) if device.platform == "tpu" else None
