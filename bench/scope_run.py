#!/usr/bin/env python3
"""Run one benchmark cell once with ``--trace 1``, as ``bench/run.py``
does, and reduce the same trace by the program's named scopes
(``bench/scopes.py``).

    python3 bench/scope_run.py --workload <name> --seed <n> --seconds <s> [--keep DIR]

From the root of a checkout, on the cell's chips.  Standard output ends in
two JSON lines: the benchmark's result object, then the scope reduction
with its per-layer numbers (``layer_metrics``) and the checks of the
reduction against the benchmark's own busy time.  ``--keep DIR`` also
writes the trace (``trace.xplane.pb.gz``) and the compiled step's text
(``step.hlo.txt.gz``) to DIR, for ``--replay DIR``, which reduces them
again without a chip.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TRACE_FILE, HLO_FILE = "trace.xplane.pb.gz", "step.hlo.txt.gz"


def traced_run(spec, seed: int, seconds: float, keep=None, **harness_kw):
    """``harness.run`` with a trace; returns (result, ProfileData, HLO text).
    The trace and the HLO are the very ones the benchmark's reduction read
    (its loader and classifier are wrapped to keep them)."""
    from bench import harness
    from bench import trace as TR

    seen = {}
    load, classify = TR.load, TR.classify_hlo

    def keep_load(path):
        if keep:
            with open(path, "rb") as src, gzip.open(os.path.join(keep, TRACE_FILE), "wb") as dst:
                shutil.copyfileobj(src, dst)
        seen["pd"] = load(path)
        return seen["pd"]

    def keep_classify(hlo):
        seen["hlo"] = hlo
        if keep:
            with gzip.open(os.path.join(keep, HLO_FILE), "wt") as f:
                f.write(hlo)
        return classify(hlo)

    if keep:
        os.makedirs(keep, exist_ok=True)
    TR.load, TR.classify_hlo = keep_load, keep_classify
    try:
        result = harness.run(spec, seed, seconds, True, t_start=T_START, **harness_kw)
    finally:
        TR.load, TR.classify_hlo = load, classify
    return result, seen["pd"], seen["hlo"]


def reduction(pd, hlo: str, busy_s=None) -> dict:
    """The scope reduction, its per-layer numbers and, given the
    benchmark's busy seconds per chip, the checks: the scopes' self times
    sum to the busy time, and the unscoped share of it."""
    from bench import scopes

    red = scopes.reduce_scopes(pd, hlo)
    red["layer_metrics"] = scopes.layer_metrics(red)
    by = red["scope_ms_per_step"]
    total = sum(v for p in by.values() for v in p.values())
    unscoped = sum(by.get(scopes.UNSCOPED, {}).values())
    red["checks"] = {"scoped_ms_per_step": total,
                     "unscoped_share": unscoped / total if total else None}
    if busy_s:
        red["checks"]["scopes_over_busy"] = total * red["steps"] * 1e-3 / busy_s
    return red


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--replay", default=None,
                    help="reduce the trace and HLO a --keep run wrote, without a chip")
    args = ap.parse_args(argv)

    if args.replay:
        from bench import trace as TR
        from jax.profiler import ProfileData

        with gzip.open(os.path.join(args.replay, TRACE_FILE), "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
        with gzip.open(os.path.join(args.replay, HLO_FILE), "rt") as f:
            hlo = f.read()
        busy = TR.reduce(pd, TR.classify_hlo(hlo))["busy_s"]
        print(json.dumps(reduction(pd, hlo, busy)))
        return 0
    if args.workload is None or args.seed is None:
        ap.error("--workload and --seed are needed without --replay")

    from bench import cells, harness

    try:
        result, pd, hlo = traced_run(cells.resolve(args.workload), args.seed,
                                     args.seconds, keep=args.keep)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    print(json.dumps(reduction(pd, hlo, result["device"]["busy_s"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
