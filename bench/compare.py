"""The comparison that decides ``correct``: the program's first three steps
against the reference's, from the same weights and the same batches.

Numbers (each a gap, 0 when the two agree):

* ``loss_gap``: the largest absolute difference of the loss over steps 1-3.
* ``grad_gap``: by the worst leaf, the gap between the norms of the first
  step's direction as each optimizer got it (the program's momentum after
  one step; the reference's served direction), over the reference's norm of
  that leaf or of the median leaf, whichever is larger.
* ``change_gap``: the same for the parameters' change after three steps.
* ``grad_gap_median`` and ``change_gap_median``: the median leaf's gap.

A leaf whose reference gradient is under a thousandth of the median leaf's
is left out of the leaf numbers: it moves by round-off alone.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Tuple

from .reference import Readings

EXCLUDE_BELOW = 1e-3


def counted_leaves(ref: Readings):
    med = statistics.median(ref.true_grad_norms.values())
    return sorted(k for k, v in ref.true_grad_norms.items() if v >= EXCLUDE_BELOW * med)


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], keys):
    med = statistics.median(ref[k] for k in keys)
    out = {}
    for k in keys:
        den = max(ref[k], med)
        out[k] = abs(prog[k] - ref[k]) / den if den > 0 else (0.0 if prog[k] == 0 else math.inf)
    return out


def readings(prog: Readings, ref: Readings) -> Dict[str, object]:
    keys = counted_leaves(ref)
    g = _leaf_gaps(prog.grad_norms, ref.grad_norms, keys)
    c = _leaf_gaps(prog.change_norms, ref.change_norms, keys)
    worst_g = max(g, key=g.get)
    worst_c = max(c, key=c.get)
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog.losses, ref.losses)),
        "grad_gap": g[worst_g],
        "grad_gap_median": statistics.median(g.values()),
        "change_gap": c[worst_c],
        "change_gap_median": statistics.median(c.values()),
        "worst_grad_leaf": worst_g,
        "worst_change_leaf": worst_c,
        "leaves_counted": len(keys),
        "leaves_left_out": sorted(set(ref.true_grad_norms) - set(keys)),
    }


def decide(values: Dict[str, object], limits: Dict[str, dict]) -> Tuple[bool, Dict[str, dict]]:
    """Every number named in the limits file against its limit; a number
    that is not finite fails."""
    checks, ok = {}, True
    for name, spec in limits["compared"].items():
        v = float(values[name])
        passed = math.isfinite(v) and v <= spec["limit"]
        ok = ok and passed
        checks[name] = {"value": v, "limit": spec["limit"]}
    return ok, checks
