"""The comparison that decides ``correct``.

Three numbers, each against its limit in ``bench/limits/<cell>.json``:

- ``route_mismatch``: vehicle-rounds whose cut differs from the
  reference's, plus RSU-rounds whose load differs, plus the difference in
  handover counts.  Mobility, association and cut selection are exact
  (limit 0).
- ``loss_gap``: the largest relative gap of a compared round's loss.
- ``delta_gap``: by the worst leaf, the gap between the program's and the
  reference's norm of the global model's change over the compared rounds,
  over the larger of the reference leaf's norm and the median leaf's.
  Leaves whose first gradient in the reference is under a thousandth of
  the median leaf's are left out: Adam moves them by round-off alone.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import numpy as np


def _leaves(units, head) -> List[np.ndarray]:
    return [np.asarray(a, np.float64)
            for a in jax.tree.leaves({"units": list(units), "head": head})]


def route_mismatch(records: List[Dict[str, Any]], states, n_rsus: int) -> int:
    bad = 0
    for rec, st in zip(records, states):
        cuts = np.asarray(rec["cuts"])
        bad += int(np.sum(cuts != st["cuts"]))
        sched = st["cuts"] > 0
        loads = np.bincount(st["serving"][sched], minlength=n_rsus)[:n_rsus]
        bad += int(np.sum(np.asarray(rec["rsu_loads"][:n_rsus]) != loads))
        bad += abs(int(rec["n_handover"]) - st["n_handover"])
    return bad


def loss_gap(records, ref_losses) -> float:
    gaps = [abs(r["loss"] - l) / max(abs(l), 1e-12)
            for r, l in zip(records, ref_losses)]
    return float(max(gaps)) if gaps else float("inf")


def delta_gap(p0, prog, ref, first_grad) -> float:
    a0, ap, ar = _leaves(*p0), _leaves(*prog), _leaves(*ref)
    g = np.array([np.linalg.norm(x) for x in _leaves(*first_grad)])
    keep = g >= 1e-3 * np.median(g)
    n_prog = np.array([np.linalg.norm(p - o) for p, o in zip(ap, a0)])
    n_ref = np.array([np.linalg.norm(r - o) for r, o in zip(ar, a0)])
    n_prog, n_ref = n_prog[keep], n_ref[keep]
    den = np.maximum(n_ref, np.median(n_ref))
    if not np.all(np.isfinite(n_prog)):
        return float("inf")
    return float(np.max(np.abs(n_prog - n_ref) / den))


def compare(records, ref, p0, prog_params) -> Dict[str, float]:
    return {
        "route_mismatch": route_mismatch(records, ref["states"],
                                         ref["n_rsus"]),
        "loss_gap": loss_gap(records, ref["losses"]),
        "delta_gap": delta_gap(p0, prog_params, (ref["units"], ref["head"]),
                               ref["first_grad"]),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
