"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against the plain reference after the same steps from
the same seed on the same batches:

* ``loss_gap``: the largest relative gap of a step's reported loss value;
* ``grad_gap``: the first step's gradient, as the optimizer holds it after
  one step (AdamW's first moment over ``1 - beta1``), by the worst leaf;
* ``update_gap``: the parameters' change after the checked steps, by the
  worst leaf, over the leaves whose reference gradient is not nought to
  rounding (at least ``MOVED`` of the median leaf's).

A leaf's gap is the distance between the program's norm and the
reference's, over the larger of the reference's norm of that leaf and of
the median leaf.

The drivers add ``narrow_dots``: the matrix products, convolutions and
custom calls of the program the window ran that take an operand narrower
than the float32 the configuration states (``program.narrow_dots``), and,
in the launcher cell, ``batch_gap``.  Both are exact: their limit is 0.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Mapping, Optional, Sequence

MOVED = 1e-3


def leaf_gap(prog: Mapping[str, float], ref: Mapping[str, float],
             keep: Optional[Sequence[str]] = None):
    """(worst gap, its leaf) over ``keep`` (default: every leaf)."""
    if set(prog) != set(ref):
        return math.inf, "leaves differ"
    med = statistics.median(ref.values())
    worst, where = -1.0, ""
    for k in (keep if keep is not None else ref):
        den = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / den if den > 0 else math.inf
        if not gap <= worst:       # also takes nan
            worst, where = gap, k
    return worst, where


def moved_leaves(ref_grad: Mapping[str, float]):
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= MOVED * med]


def numbers(prog: Dict, ref: Dict) -> Dict[str, Dict]:
    """``prog`` and ``ref`` hold ``loss`` (per step), ``grad`` and ``update``
    (leaf -> norm).  Returns name -> {value, leaf?}."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])]
    loss = (max(gaps) if gaps and len(prog["loss"]) == len(ref["loss"])
            and all(math.isfinite(x) for x in gaps) else math.inf)
    g, g_at = leaf_gap(prog["grad"], ref["grad"])
    u, u_at = leaf_gap(prog["update"], ref["update"],
                       moved_leaves(ref["grad"]))
    return {"loss_gap": {"value": loss},
            "grad_gap": {"value": g, "leaf": g_at},
            "update_gap": {"value": u, "leaf": u_at}}


def judge(found: Dict[str, Dict], limits: Mapping[str, float]):
    """(correct, checks): every number at or under its limit."""
    checks = {k: {"value": v["value"], "limit": limits[k]}
              for k, v in found.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
