#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 12 --controls 3 \\
        --faults half_batch,no_exchange --out <file.json>

For each seed: the program's three proof steps (the cell's compiled step,
its feed) against the reference's, as a run reads them.  For the first
``--controls`` seeds also: the control (the reference in float8 put in the
program's place), each named fault planted in the reference put in the
program's place, and a second float32 reference with other quantizer draws
(the floor that the draws alone give).  Every reading goes to ``--out`` as
it is made.  The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--seed-base", type=int, default=2_000_000_011)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench import cells, compare, harness

    spec = cells.resolve(args.workload)
    cell = harness.Cell(spec)
    out = {"workload": args.workload, "runs": []}
    faults = [f for f in args.faults.split(",") if f]

    def save():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)

    def record(kind, seed, prog, ref, t0):
        vals = compare.readings(prog, ref)
        row = {"kind": kind, "seed": seed, "seconds": time.perf_counter() - t0,
               "values": vals, "losses": [prog.losses, ref.losses],
               "grad_norms": [prog.grad_norms, ref.grad_norms],
               "change_norms": [prog.change_norms, ref.change_norms],
               "true_grad_norms": ref.true_grad_norms}
        out["runs"].append(row)
        save()
        print(kind, seed, {k: v for k, v in vals.items() if k != "leaves_left_out"},
              f"{row['seconds']:.1f}s", flush=True)

    for k in range(args.seeds):
        seed = args.seed_base + 7919 * k
        t0 = time.perf_counter()
        host_pool = cell.host_pool(seed, harness.PROOF_STEPS)
        params, opt_state, pool, keys = cell.state(seed, host_pool)
        params, opt_state, prog = cell.proof(cell.compiled, seed, params, opt_state, pool, keys)
        harness._free(params, opt_state, pool, keys)
        t1 = time.perf_counter()
        ref = cell.reference(seed, host_pool, stream=seed)
        print(f"program {t1 - t0:.1f}s reference {time.perf_counter() - t1:.1f}s", flush=True)
        record("program", seed, prog, ref, t0)
        if k < args.controls:
            for kind, kw in ([("draws", dict(stream=seed + 1)),
                              ("control", dict(stream=seed + 2, precision="fp8"))]
                             + [(f, dict(stream=seed + 3, fault=f)) for f in faults]):
                t0 = time.perf_counter()
                record(kind, seed, cell.reference(seed, host_pool, **kw), ref, t0)
    print(f"total {time.perf_counter() - T_START:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
