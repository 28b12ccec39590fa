"""Reduction of a profiler trace (``.xplane.pb``) to the per-layer numbers.

Read with ``jax.profiler.ProfileData``.  The device's operations are the
events on each TPU plane's ``XLA Ops`` line, each named by its whole HLO
instruction (a loop's event holds its body's events); the benchmark's host
spans (``bench.window``, ``bench.step``, ``bench.feed``, ``bench.block``)
are events on the host plane, on the same clock.  An operation is a kernel
if it is a ``tpu_custom_call``, and an all-gather if it is one or calls one
(by the compiled program's text).

* busy: the union of the device's operation intervals inside the window;
* kernel and all-gather time: the sum of their events' durations;
* idle gaps: the stretches of the window with no operation on the device,
  each labelled by the innermost host span that covers its middle.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_CALLS_RE = re.compile(r"\bcalls=\{?([^}]*?)\}?(?:,|$)")
_GATHER_RE = re.compile(r"\sall-gather(?:-start|-done)?\(")


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".pbtxt"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


# ---------------------------------------------------------------------------
# The compiled program's text: which instructions are kernels or gathers
# ---------------------------------------------------------------------------

def classify_hlo(hlo_text: str) -> Dict[str, set]:
    """Instruction names that are a Mosaic kernel, and that are an
    all-gather or a fusion (or async wrapper) around one.  Only ``calls=``
    is followed: a loop's event holds its body's, and is not itself one."""
    comps: Dict[str, List[Tuple[str, str]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m and cur is not None:
            comps[cur].append((m.group(1), line))
            continue
        c = _COMP_RE.match(line)
        if c:
            cur = c.group(1)
            comps[cur] = []

    def direct(line, kind):
        if kind == "kernel":
            return 'custom_call_target="tpu_custom_call"' in line
        return bool(_GATHER_RE.search(line.split("=", 1)[-1]))

    def callees(line):
        out = []
        for grp in _CALLS_RE.findall(line):
            out += [n.strip().lstrip("%") for n in grp.split(",") if n.strip()]
        return out

    memo: Dict[Tuple[str, str], bool] = {}

    def comp_has(name, kind, depth=0):
        if (name, kind) in memo:
            return memo[(name, kind)]
        memo[(name, kind)] = False
        hit = depth < 32 and any(
            direct(line, kind) or any(comp_has(c, kind, depth + 1) for c in callees(line))
            for _, line in comps.get(name, ()))
        memo[(name, kind)] = hit
        return hit

    out = {"kernel": set(), "gather": set()}
    for instrs in comps.values():
        for name, line in instrs:
            if direct(line, "kernel"):
                out["kernel"].add(name)
            if direct(line, "gather") or any(comp_has(c, "gather") for c in callees(line)):
                out["gather"].add(name)
    return out


# ---------------------------------------------------------------------------
# Intervals
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of intervals clipped to [lo, hi], as sorted disjoint ones."""
    clipped = sorted((max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi)
    out: List[Tuple[float, float]] = []
    for a, b in clipped:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        elif b > a:
            out.append((a, b))
    return out


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _label(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "outside bench spans"


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"^%?([\w.\-]+)\s*=")


def op_name(event_name: str) -> str:
    """A TPU trace names an operation by its whole HLO instruction; this is
    the instruction's name (``fusion.12``, ``quantize_pack_prng.1``)."""
    m = _NAME_RE.match(event_name)
    return m.group(1) if m else event_name


def events(pd):
    """(host spans, {device plane: [(op name, full name, start, end)]}), in
    seconds on the trace's one clock."""
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(op_name(e.name), e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return spans, devices


def self_times(ops) -> Dict[str, float]:
    """Seconds each operation ran minus the operations nested in it (a loop
    holds its body's operations on the same line)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []            # [name, end, child seconds]

    def close(item):
        name, a, b, child = item
        out[name] += max(0.0, (b - a) - child)

    for name, a, b in sorted(((n, a, b) for n, _, a, b in ops), key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


def is_kernel(name: str, full: str, classes) -> bool:
    return 'custom_call_target="tpu_custom_call"' in full or name in classes["kernel"]


def is_gather(name: str, full: str, classes) -> bool:
    return name in classes["gather"] or bool(_GATHER_RE.search(" " + full.split("=", 1)[-1]))


def reduce(pd, classes: Dict[str, set]) -> Dict:
    """Busy, kernel and all-gather seconds per chip over the traced window,
    and the breakdown of device operations (by self time) and idle gaps."""
    spans, devices = events(pd)
    win = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not win or not devices:
        raise ValueError("the trace holds no bench.window span or no device operations")
    lo, hi = win[0]
    steps = sum(1 for n, a, b in spans if n == "bench.step" and a >= lo and b <= hi)
    busy_s, kernel_s, gather_s = [], [], []
    by_op: Dict[str, float] = defaultdict(float)
    all_gaps = []
    n_dev = len(devices)
    for plane, ops in sorted(devices.items()):
        inside = [(n, f, max(a, lo), min(b, hi)) for n, f, a, b in ops if b > lo and a < hi]
        busy = union(((a, b) for _, _, a, b in inside), lo, hi)
        busy_s.append(sum(b - a for a, b in busy))
        kernel_s.append(sum(b - a for n, f, a, b in inside if is_kernel(n, f, classes)))
        gather_s.append(sum(b - a for n, f, a, b in inside if is_gather(n, f, classes)))
        for k, v in self_times(inside).items():
            by_op[k] += v / n_dev
        all_gaps += gaps(busy, lo, hi)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": hi - lo,
        "steps": steps,
        "chips_traced": n_dev,
        "busy_s": sum(busy_s) / n_dev,
        "kernel_s_per_chip": sum(kernel_s) / n_dev,
        "gather_s_per_chip": sum(gather_s) / n_dev,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[_label(spans, (a + b) / 2), b - a] for a, b in all_gaps[:TOP]],
        },
    }

