"""The numbers that decide `correct`, each a gap between what the timed path
produced and the plain reference, and the limits they are held to.

A norm gap is taken leaf by leaf (and node by node): the gap between the
program's norm of a leaf and the reference's, over the larger of the
reference's norm of that leaf and the median leaf's, since some leaves are all
but zero. The worst leaf is the number. Leaves whose reference gradient is
under a thousandth of the median leaf's move by round-off alone and are left
out.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

import numpy as np

LIMITS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "limits")


def leaf_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              grad_ref: Optional[Dict[str, np.ndarray]] = None) -> Dict[str, float]:
    """{leaf: worst node's gap} over the leaves that count."""
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: program {sorted(set(prog) - set(ref))} "
                         f"reference {sorted(set(ref) - set(prog))}")
    keys = sorted(ref)
    if grad_ref is not None:
        g = np.array([float(np.max(grad_ref[k])) for k in keys])
        keys = [k for k, gk in zip(keys, g) if gk >= 1e-3 * np.median(g)]
    for k in keys:
        if np.shape(prog[k]) != np.shape(ref[k]):
            raise ValueError(f"node counts differ in {k}: program "
                             f"{np.shape(prog[k])} reference {np.shape(ref[k])}")
    med = np.median(np.concatenate([np.ravel(ref[k]) for k in keys]).astype(np.float64))
    out = {}
    for k in keys:
        r = np.ravel(ref[k]).astype(np.float64)
        p = np.ravel(prog[k]).astype(np.float64)
        out[k] = float(np.max(np.abs(p - r) / np.maximum(r, med)))
    return out


def norm_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             grad_ref: Optional[Dict[str, np.ndarray]] = None) -> float:
    return max(leaf_gaps(prog, ref, grad_ref).values())


def rel_gap(prog: float, ref: float) -> float:
    return float(abs(prog - ref) / abs(ref))


def limits(workload: str, directory: str = LIMITS_DIR) -> Dict[str, float]:
    with open(os.path.join(directory, f"{workload}.json")) as f:
        return {k: float(v["limit"]) for k, v in json.load(f)["limits"].items()}


def verdict(values: Dict[str, float], lim: Dict[str, float]) -> Dict[str, dict]:
    """{name: {"value", "limit", "ok"}}; a number without a limit, or a limit
    without a number, is a failure."""
    out = {}
    for name in sorted(set(values) | set(lim)):
        v, l = values.get(name), lim.get(name)
        ok = v is not None and l is not None and np.isfinite(v) and v <= l
        out[name] = {"value": v, "limit": l, "ok": bool(ok)}
    return out
