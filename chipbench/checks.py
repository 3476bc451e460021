"""The numbers that decide ``correct``: each a gap between what the timed
path produced and what the plain reference computes, held to a limit set
in the cell's workload file (``correct``). ``PERF.md`` gives the readings
each limit was set from."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def leaf_gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """(N, L) gaps between two (N, L) tables of leaf norms, each over the
    reference's norm of that leaf or of the worker's median leaf,
    whichever is larger."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = np.median(ref, axis=1, keepdims=True)
    return np.abs(prog - ref) / np.maximum(np.maximum(ref, floor), 1e-30)


def norm_gap(prog: np.ndarray, ref: np.ndarray,
             keep: Optional[np.ndarray] = None) -> float:
    """Worst (worker, leaf) of ``leaf_gaps``; ``keep`` masks the leaves
    counted."""
    gap = leaf_gaps(prog, ref)
    if keep is not None:
        gap = gap[:, keep]
    return float(np.max(gap))


def moved_leaves(ref_moment: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient (its Adam first moment after round
    1, worst worker) is at least a thousandth of the median leaf's: the
    others move under Adam by round-off alone."""
    g = np.max(np.asarray(ref_moment, np.float64), axis=0)
    return g >= 1e-3 * np.median(g)


def train_checks(prog: Dict, ref: Dict) -> Dict[str, float]:
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    keep = moved_leaves(ref["mu_norms"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "moment_gap": norm_gap(prog["mu_norms"], ref["mu_norms"]),
        "update_gap": norm_gap(prog["update_norms"], ref["update_norms"],
                               keep),
        "dual_gap": norm_gap(prog["dual_norms"], ref["dual_norms"], keep),
        "tx_mismatch": float(np.sum(np.asarray(prog["tx"])
                                    != np.asarray(ref["tx"]))),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """``{name: {"value", "limit"}}``; ``correct`` iff every value is
    finite and at most its limit."""
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def all_within(checks: Dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
