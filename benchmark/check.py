"""The comparison that decides `correct`: numbers read from the timed path
beside the plain reference's, each held to a limit from the cell's file
under `benchmark/limits/`. Pure numpy; the drivers hand in what they read.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def flat_leaves(norms: dict) -> tuple[list, np.ndarray]:
    """{name: scalar or per-layer vector} -> (leaf names, values)."""
    names, vals = [], []
    for n in sorted(norms):
        v = np.atleast_1d(np.asarray(norms[n], np.float64))
        if v.size == 1 and not n.startswith("blocks."):
            names.append(n)
        else:
            names += [f"{n}[{i}]" for i in range(v.size)]
        vals.append(v)
    return names, np.concatenate(vals)


def leaf_gaps(got: dict, want: dict) -> tuple[list, np.ndarray]:
    """Per leaf, |norm_got - norm_want| against the reference's norm of that
    leaf or of the median leaf, whichever is larger (some gradients are all
    but zero)."""
    names, g = flat_leaves(got)
    names_w, w = flat_leaves(want)
    if names != names_w:
        raise ValueError("program and reference disagree on the leaves")
    gap = np.abs(g - w) / np.maximum(w, np.median(w))
    return names, np.where(np.isfinite(gap), gap, np.inf)


def worst_leaf_gap(got: dict, want: dict, keep=None) -> tuple[float, str]:
    """The largest of `leaf_gaps`; ``keep`` masks the leaves that count."""
    names, gap = leaf_gaps(got, want)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), names[i]


def moved_leaves(ref_grad: dict) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's. The others (a key's bias under
    softmax) move under Adam by round-off alone and are left out of the
    parameters' change."""
    _, g = flat_leaves(ref_grad)
    return g >= 1e-3 * np.median(g)


def train_numbers(prog: dict, ref: dict) -> dict:
    """The numbers a training cell compares. ``prog`` and ``ref`` hold
    `losses` (one a step), `grad1` and `dparam` (per-leaf norms)."""
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError("program and reference followed different steps")
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    g_gap, g_leaf = worst_leaf_gap(prog["grad1"], ref["grad1"])
    d_gap, d_leaf = worst_leaf_gap(prog["dparam"], ref["dparam"],
                                   keep=moved_leaves(ref["grad1"]))
    return {
        "loss_gap": {"value": loss_gap,
                     "program": prog["losses"], "reference": ref["losses"]},
        "grad1_leaf_gap": {"value": g_gap, "leaf": g_leaf},
        "dparam_leaf_gap": {"value": d_gap, "leaf": d_leaf},
    }


def judge(numbers: dict, limits: dict, extra_ok: bool = True) -> tuple:
    """Each number beside its limit. Every number compared needs a limit
    and every limit a number: a file that leaves one out, or misspells its
    name, is an error and never a pass (`calibrate.py`, which reads numbers
    before there are limits, does not come through here). Returns
    (correct, rows)."""
    if set(limits) != set(numbers) or not all(
            isinstance(v, (int, float)) and not isinstance(v, bool)
            for v in limits.values()):
        raise KeyError(
            f"the cell's limits {sorted(limits.items())} do not give one "
            f"number for each of {sorted(numbers)}")
    rows, ok = {}, bool(extra_ok)
    for name, rec in numbers.items():
        limit = limits[name]
        value = float(rec["value"])
        passed = bool(np.isfinite(value) and value <= limit)
        ok = ok and passed
        rows[name] = {**{k: v for k, v in rec.items()
                         if isinstance(v, (int, float, str))},
                      "value": value, "limit": limit, "ok": passed}
    return ok, rows


def report(rows: dict, correct: bool, notes: dict | None = None) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error."""
    for name, r in rows.items():
        extra = {k: v for k, v in r.items()
                 if k not in ("value", "limit", "ok")}
        print(f"check {name}: value {r['value']:.6g} limit {r['limit']} "
              f"{'ok' if r['ok'] else 'FAILED'} {extra or ''}".rstrip(),
              file=sys.stderr)
    for k, v in (notes or {}).items():
        print(f"check note {k}: {v}", file=sys.stderr)
    print(f"check correct: {correct}", file=sys.stderr, flush=True)
