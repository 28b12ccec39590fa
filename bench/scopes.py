"""Per-scope reduction of a profiler trace: where the device's time goes by
the program's own named scopes, how much of the all-gather is exposed, and
what the host was doing in each idle gap.

The program names its work with ``jax.named_scope`` (``<layer>.<part>``:
``model.*``, ``train.*``, ``diana.*``).  A scope is metadata only: it lives
in each compiled HLO instruction's ``metadata={op_name="..."}``, which the
trace does not carry, so the scope of a device operation is read from the
compiled program's text by instruction name.  An operation whose op_name
holds ``transpose(`` is backward; one that also holds
``rematted_computation`` is the recomputed forward of a checkpointed block.

Read beside ``bench/trace.py`` (which it reuses) from the same trace:

* ``scope_ms_per_step``: each ``XLA Ops`` event's self time, per chip and
  step, under its innermost program scope and phase (``unscoped`` where the
  op_name holds none);
* the all-gather's time in flight (its ``Async XLA Ops`` intervals, and on
  the ops line its own operations), and the part of it in which the ops line
  runs no other work: the exposed time;
* the bytes each chip receives a step by the all-gathers under
  ``diana.allgather``, from their HLO shapes (its own row excluded);
* ``idle_gap_causes``: the ten longest idle gaps, each with the benchmark's
  innermost span, the innermost runtime host event (any host thread) over
  its middle, and the scopes of the device operations on either side;
* ``clock_skew_us``: the most by which a step's ``XLA Modules`` event starts
  before the host call that launched it (0 when none does; None without
  module events): how far the device's clock can be trusted against the
  host spans; ``launch_to_run_us`` gives the range of run start less launch.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as TR

ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
PHASES = ("forward", "backward", "recompute")
# Host events that launch a step's program, outermost first.
LAUNCH_EVENTS = ("PjitFunction(", "PJRT_LoadedExecutable_Execute",
                 "CommonPjRtLoadedExecutable::Execute")

# a scope is a whole op_name path segment (``.../diana.encode/...``,
# ``transpose(jvp(model.blocks))``), never part of an argument's name
_SCOPE_RE = re.compile(r"(?:^|[/(])((?:model|train|diana)\.[a-z_]+)(?=[/)]|$)")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*\bop_name="([^"]*)"')
_CALLED_RE = re.compile(
    r"\b(?:calls|body|condition|to_apply|branch_computations|called_computations)"
    r"=(\{[^}]*\}|%?[\w.\-]+)")
_GATHER_OP_RE = re.compile(r"\s(all-gather(?:-start)?)\(")
_GATHER_ANY_RE = re.compile(r"\sall-gather(?:-start|-done)?\(")
_OPCODE_RE = re.compile(r"\s([a-z][\w\-]*)\(")
_CHANNEL_RE = re.compile(r"\bchannel_id=(\d+)")
# what a gather's own start, done or wrapper does beside the gather
_MOVES = {"all-gather", "all-gather-start", "all-gather-done", "async-start",
          "async-done", "custom-call", "fusion", "parameter", "tuple",
          "get-tuple-element", "bitcast", "copy", "constant"}
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_SHAPE_RE = re.compile(r"\b(pred|s4|u4|s8|u8|s16|u16|f16|bf16|s32|u32|f32|s64|u64|f64|"
                       r"f8e4m3fn|f8e5m2)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "f8e4m3fn": 1,
          "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4,
          "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


# ---------------------------------------------------------------------------
# The compiled program's text: each instruction's scope and phase
# ---------------------------------------------------------------------------

def scope_of(op_name: str) -> Tuple[str, str]:
    """(innermost program scope or ``unscoped``, phase) of one op_name."""
    found = _SCOPE_RE.findall(op_name)
    if "transpose(" not in op_name:
        phase = "forward"
    elif "rematted_computation" in op_name:
        phase = "recompute"
    else:
        phase = "backward"
    return (found[-1] if found else UNSCOPED), phase


def _parse(hlo_text: str):
    """({instruction: line}, {instruction: its computation},
    {computation: its instructions in order})."""
    lines, comp_of, body = {}, {}, defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = TR._INSTR_RE.match(line)
        if m and comp is not None:
            lines[m.group(1)] = line
            comp_of[m.group(1)] = comp
            body[comp].append(m.group(1))
            continue
        c = TR._COMP_RE.match(line)
        if c:
            comp = c.group(1)
    return lines, comp_of, body


def _callees(line: str) -> List[str]:
    return [c.strip().lstrip("%") for grp in _CALLED_RE.findall(line)
            for c in grp.strip("{}").split(",") if c.strip()]


def _opcode(line: str) -> str:
    m = _OPCODE_RE.search(line.split("=", 1)[1])
    return m.group(1) if m else ""


def instruction_scopes(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (scope, phase).  An instruction whose op_name
    names no scope (or that has none, as the copies and async wrappers XLA
    adds) takes the scope found in the computations it calls (their last
    scoped instruction), else that of the instruction calling its own
    computation."""
    lines, comp_of, body = _parse(hlo_text)
    own: Dict[str, Tuple[str, str]] = {}
    calls: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}
    for name, line in lines.items():
        m = _OP_NAME_RE.search(line)
        own[name] = scope_of(m.group(1)) if m else (UNSCOPED, "forward")
        calls[name] = _callees(line)
        for callee in calls[name]:
            caller.setdefault(callee, name)

    def inner(name):
        for comp in calls[name]:
            for n in reversed(body.get(comp, ())):
                if own[n][0] != UNSCOPED:
                    return own[n]
        return None

    def resolve(name, depth=0):
        scope = own[name]
        if scope[0] != UNSCOPED or depth > 32:
            return scope
        found = inner(name)
        if found:
            return found
        up = caller.get(comp_of[name])
        return resolve(up, depth + 1) if up in own else scope

    return {name: resolve(name) for name in own}


def pure_gathers(hlo_text: str) -> set:
    """The instructions that only move an all-gather's data: the gather's
    own start and done, and wrappers (async or fused) whose computations do
    nothing else.  A fusion that computes beside a gather it carries (the
    TPU compiler folds a gather's progress into compute fusions) is work."""
    lines, _, body = _parse(hlo_text)

    def only_moves(name, depth=0):
        line = lines[name]
        if _opcode(line) not in _MOVES or "tpu_custom_call" in line or depth > 8:
            return False
        return all(only_moves(n, depth + 1) for c in _callees(line) for n in body.get(c, ()))

    return {name for name, line in lines.items()
            if _GATHER_ANY_RE.search(line.split("=", 1)[1]) is not None
            or any(_GATHER_ANY_RE.search(lines[n].split("=", 1)[1])
                   for c in _callees(line) for n in body.get(c, ()))
            if only_moves(name)}


def _shape_bytes(text: str) -> List[float]:
    """Bytes of each array shape in ``text``; scalars are left out."""
    return [_BYTES[dt] * _prod(dims) for dt, dims in _SHAPE_RE.findall(text) if dims]


def _prod(dims: str) -> int:
    out = 1
    for d in dims.split(","):
        if d:
            out *= int(d)
    return out


def gathered_bytes(hlo_text: str, scopes: Dict[str, Tuple[str, str]],
                   scope: str = "diana.allgather") -> float:
    """Bytes one chip receives in a step by the all-gathers under ``scope``:
    each gather's result less its own operand.  Counts each gather once
    (one per channel where the compiler split it into phases); a gather
    inside a loop body is counted once, not per trip."""
    lines, _, _ = _parse(hlo_text)
    result_of = {name: line.split("=", 1)[1] for name, line in lines.items()}
    total, channels = 0.0, set()
    for name, rhs in result_of.items():
        m = _GATHER_OP_RE.search(rhs)
        if not m or scopes.get(name, (UNSCOPED,))[0] != scope:
            continue
        # a decomposed gather (start, continuations, done) keeps one channel
        channel = _CHANNEL_RE.search(rhs)
        if channel:
            if channel.group(1) in channels:
                continue
            channels.add(channel.group(1))
        out = _shape_bytes(rhs[:m.start()])
        operands = rhs[m.end():].split(")", 1)[0]
        # the compiled text may print an operand by name alone
        ins = _shape_bytes(operands) or [
            b for op in _OPERAND_RE.findall(operands)
            for b in _shape_bytes(result_of.get(op, "").split("(", 1)[0])[-1:]]
        if m.group(1) == "all-gather-start":
            # an async start's result holds its operands beside the result
            for b in ins:
                if b in out:
                    out.remove(b)
        total += sum(out) - sum(ins)
    return total


# ---------------------------------------------------------------------------
# The trace's events, runtime host events included
# ---------------------------------------------------------------------------

def trace_events(pd):
    """Every host event (``bench.*`` spans and the runtime's own), and per
    device plane its ``Async XLA Ops`` and ``XLA Modules`` events, as
    (name, start s, end s)."""
    host, async_ops, modules = [], defaultdict(list), defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events]
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                dest = {ASYNC_LINE: async_ops, MODULES_LINE: modules}.get(line.name)
                if dest is not None:
                    dest[plane.name] += [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
    return host, async_ops, modules


def _innermost(events: Sequence[Tuple[str, float, float]], t: float) -> Optional[str]:
    best = None
    for name, a, b in events:
        if a <= t <= b and b > a and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else None


def _measure(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _subtract(base, cut):
    """Sorted disjoint ``base`` less the union of sorted disjoint ``cut``."""
    out = []
    for a, b in base:
        t = a
        for c, d in cut:
            if d <= t or c >= b:
                continue
            if c > t:
                out.append((t, c))
            t = max(t, d)
        if t < b:
            out.append((t, b))
    return out


def _flights(gather_ops):
    """The intervals a gather is in flight by its ops on the ops line: from
    each start to the next done, and a gather's own op where it has neither."""
    out, opened = [], None
    for n, _, a, b in sorted(gather_ops, key=lambda o: o[2]):
        if re.search(r"start(\.\d+)?$", n):
            opened = a if opened is None else opened
        elif re.search(r"done(\.\d+)?$", n) and opened is not None:
            out.append((opened, b))
            opened = None
        else:
            out.append((a, b))
    return out


def _leaves(ops):
    """The events with no other event nested in them (a loop's event holds
    its body's on the same line)."""
    ordered = sorted(ops, key=lambda o: (o[2], -o[3]))
    out = []
    for i, op in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if not (nxt and nxt[2] < op[3] and nxt[3] <= op[3]):
            out.append(op)
    return out


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

def reduce_scopes(pd, hlo_text: str) -> Dict:
    """The per-scope numbers of one traced window (see the module
    docstring), per chip and per step where they are times."""
    spans, devices = TR.events(pd)
    host, async_ops, modules = trace_events(pd)
    win = [(a, b) for n, a, b in spans if n == TR.WINDOW_SPAN]
    if not win or not devices:
        raise ValueError("the trace holds no bench.window span or no device operations")
    lo, hi = win[0]
    steps = [(a, b) for n, a, b in spans if n == "bench.step" and a >= lo and b <= hi]
    n_steps, n_dev = max(len(steps), 1), len(devices)
    classes = TR.classify_hlo(hlo_text)
    scopes = instruction_scopes(hlo_text)
    moves = pure_gathers(hlo_text)
    runtime = [e for e in host if not e[0].startswith(TR.SPAN_PREFIX)]
    offsets = launch_to_run_us(steps, host, modules)

    by_scope: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    inflight_s = exposed_s = 0.0
    causes = []
    for plane, ops in sorted(devices.items()):
        inside = [(n, f, max(a, lo), min(b, hi)) for n, f, a, b in ops if b > lo and a < hi]
        for name, sec in TR.self_times(inside).items():
            scope, phase = scopes.get(name, (UNSCOPED, "forward"))
            by_scope[scope][phase] += 1e3 * sec / n_steps / n_dev

        leaves = _leaves(inside)
        # without the program's text, every operation named as a gather
        is_move = [n in moves or (not moves and TR.is_gather(n, f, classes))
                   for n, f, _, _ in leaves]
        work = TR.union(((a, b) for (_, _, a, b), mv in zip(leaves, is_move) if not mv),
                        lo, hi)
        flight = [(a, b) for n, a, b in async_ops.get(plane, ())
                  if TR.is_gather(TR.op_name(n), n, classes)]
        flight += _flights([op for op, mv in zip(leaves, is_move) if mv])
        inflight = TR.union(flight, lo, hi)
        inflight_s += _measure(inflight) / n_dev
        exposed_s += _measure(_subtract(inflight, work)) / n_dev

        busy = TR.union(((a, b) for _, _, a, b in inside), lo, hi)
        for a, b in TR.gaps(busy, lo, hi):
            causes.append((b - a, a, b, inside))

    causes.sort(key=lambda c: -c[0])
    gap_causes = [_cause(width, a, b, inside, spans, runtime, scopes)
                  for width, a, b, inside in causes[:TR.TOP]]
    return {
        "scope_ms_per_step": {s: dict(p) for s, p in sorted(by_scope.items())},
        "allgather_inflight_ms": 1e3 * inflight_s / n_steps,
        "allgather_exposed_ms": 1e3 * exposed_s / n_steps,
        "allgather_bytes_per_step": gathered_bytes(hlo_text, scopes),
        "idle_gap_causes": gap_causes,
        "clock_skew_us": max([0.0] + [-x for x in offsets]) if offsets else None,
        "launch_to_run_us": [min(offsets), max(offsets)] if offsets else None,
        "steps": len(steps),
    }


def _cause(width, a, b, ops, spans, runtime, scopes) -> Dict:
    mid = (a + b) / 2
    before = min((o for o in ops if abs(o[3] - a) < 1e-12),
                 key=lambda o: o[3] - o[2], default=None)
    after = min((o for o in ops if abs(o[2] - b) < 1e-12),
                key=lambda o: o[3] - o[2], default=None)

    def scope(o):
        return "/".join(scopes.get(o[0], (UNSCOPED, "forward"))) if o else None

    return {"ms": 1e3 * width, "bench_span": TR._label(spans, mid),
            "host_event": _innermost(runtime, mid),
            "scope_before": scope(before), "scope_after": scope(after)}


def launch_to_run_us(steps, host, modules) -> List[float]:
    """Pairs the k-th traced step's first launching host event with the k-th
    run of the step's program (its most frequent ``XLA Modules`` name) on
    each device; per pair, the run's start less its launch, in us (negative
    where the device's clock puts the run before its launch)."""
    launches = []
    for a, b in steps:
        starts = [s for n, s, _ in host if a <= s <= b and n.startswith(LAUNCH_EVENTS)]
        launches.append(min(starts) if starts else None)
    out = []
    for runs in modules.values():
        counts = defaultdict(int)
        for n, _, _ in runs:
            counts[n] += 1
        if not counts:
            continue
        main = max(counts, key=counts.get)
        starts = sorted(s for n, s, _ in runs if n == main)
        lo = steps[0][0] if steps else float("-inf")
        # a run that starts early by less than a step still pairs with it
        first = next((i for i, s in enumerate(starts)
                      if s >= lo - 0.5 * _step_len(steps)), len(starts))
        out += [1e6 * (start - launch) for launch, start in zip(launches, starts[first:])
                if launch is not None]
    return out


def _step_len(steps) -> float:
    return min((b - a for a, b in steps), default=0.0)


def layer_metrics(red: Dict) -> Dict[str, Optional[float]]:
    """The per-layer numbers the scopes give, per chip and step; None where
    the trace holds no program scope (a program without them), and for a
    sum whose scopes the trace does not hold (no DIANA round, say)."""
    by = red["scope_ms_per_step"]
    if not any(s != UNSCOPED for s in by):
        return {k: None for k in ("forward_ms", "backward_ms", "optimizer_ms",
                                  "diana_round_ms", "diana_decode_ms",
                                  "allgather_exposed_ms", "allgather_mb")}

    def total(pred, phases=PHASES):
        times = [v for s, p in by.items() if pred(s) for ph, v in p.items() if ph in phases]
        return sum(times) if times else None

    gathered = red["allgather_bytes_per_step"]
    return {
        "forward_ms": total(lambda s: s.startswith("model."), ("forward",)),
        "backward_ms": total(lambda s: s.startswith("model."), ("backward", "recompute")),
        "optimizer_ms": total(lambda s: s == "train.optimizer"),
        "diana_round_ms": total(lambda s: s.startswith("diana.")),
        "diana_decode_ms": total(lambda s: s == "diana.decode_sum_apply"),
        "allgather_exposed_ms": red["allgather_exposed_ms"] if gathered else None,
        "allgather_mb": gathered / 1e6 if gathered else None,
    }
