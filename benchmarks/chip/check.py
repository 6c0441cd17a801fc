"""The comparison that decides ``correct``.

Training cells compare three numbers between the program's first steps and
the reference's, each against a limit kept in the cell's workload file:

* ``loss_gap``: the largest relative gap of a step's loss, over the steps
  the reference follows.
* ``grad_gap``: by the worst leaf, the gap between the norms of the first
  step's gradient (the program's as its optimizer received it, worked out
  from the optimizer's first moment), over the larger of the reference's
  norm of that leaf and of the median leaf.
* ``update_gap``: the same for the parameters' change over the steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's move by round-off alone under Adam and are left out.
* ``grad_median_gap``: the median leaf's ``grad_gap``, steady where one
  small leaf's gradient is mostly cancellation (the last bias under
  softmax cross-entropy) and its gap mostly round-off.

A cell compares the numbers its workload file gives limits for.
"""

from __future__ import annotations

import sys

import jax
import numpy as np

# a leaf whose reference gradient norm is below this share of the median
# leaf's moves by round-off alone under Adam
STILL_LEAF = 1e-3


def leaf_norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree_util.tree_leaves(tree)])


def _leaf_gaps(prog: np.ndarray, ref: np.ndarray, keep) -> np.ndarray:
    """Per leaf kept, the gap of norms over the larger of the reference's
    norm of the leaf and of the median leaf."""
    floor = np.maximum(ref[keep], np.median(ref[keep]))
    return np.abs(prog[keep] - ref[keep]) / np.where(floor > 0, floor, 1.0)


def training_numbers(prog: dict, ref: dict, params0) -> dict:
    """``prog`` and ``ref`` hold ``losses``, ``grads`` (the first step's)
    and ``params`` (after the last step); ``params0`` the initial weights."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr))) if np.all(np.isfinite(lp)) else float("inf")
    g_ref = leaf_norms(ref["grads"])
    g_prog = leaf_norms(prog["grads"])
    moving = g_ref >= STILL_LEAF * np.median(g_ref)
    delta = lambda p: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64), p, params0)
    u_ref, u_prog = leaf_norms(delta(ref["params"])), leaf_norms(delta(prog["params"]))
    grad = _leaf_gaps(g_prog, g_ref, np.ones(len(g_ref), dtype=bool))
    nums = {
        "loss_gap": loss_gap,
        "grad_gap": float(np.max(grad)),
        "grad_median_gap": float(np.median(grad)),
        "update_gap": float(np.max(_leaf_gaps(u_prog, u_ref, moving))),
    }
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in nums.items()}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the ``{name: {"value", "limit"}}`` table of the
    numbers that have a limit; a limit without its number is not correct."""
    table = {k: {"value": numbers.get(k), "limit": limits[k]} for k in sorted(limits)}
    ok = all(v["value"] is not None and v["limit"] is not None and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table


def report(table: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error."""
    for k, v in table.items():
        sys.stderr.write(f"check {k}: {v['value']!r} limit {v['limit']!r}\n")
    sys.stderr.flush()
