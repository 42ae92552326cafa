"""The comparison that decides `correct`: the program's first steps
against the plain reference's, each number beside its limit.

Gaps of norms are taken by the worst leaf: |program - reference| over
the larger of the reference's norm of that leaf and of the median leaf.
"""

from __future__ import annotations

import json
import os
import statistics


def load_limits(root: str, cfg: dict) -> dict:
    """`limits/<config>.json`, or `limits/default.json` where the
    configuration has none yet."""
    path = os.path.join(root, "limits", cfg["name"] + ".json")
    if not os.path.exists(path):
        path = os.path.join(root, "limits", "default.json")
    with open(path) as f:
        return json.load(f)["limits"]


def _worst_leaf_gap(prog: dict, ref: dict) -> float:
    med = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in ref)


def readings(prog: dict, ref: dict) -> dict:
    """Short plain names -> the number compared."""
    out = {}
    for k, (p, r) in enumerate(zip(prog["loss"], ref["loss"])):
        out[f"loss{k + 1}_gap"] = abs(p - r) / max(abs(r), 1e-30)
    out["grad_norm_gap"] = _worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])
    out["delta_norm_gap"] = _worst_leaf_gap(prog["delta_norm"], ref["delta_norm"])
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}); a number with no limit
    is an error, a non-finite number fails."""
    table, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        good = value == value and value <= limit
        ok = ok and good
        table[name] = {"value": value, "limit": limit}
    return ok, table
