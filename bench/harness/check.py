"""The comparison that decides ``correct``.

The program's set-up steps (``runner.setup``) against the reference's
(``reference.run_setup``) from the same seed, layer by layer:

* ``rollout`` — collector farm: every trajectory the farm landed,
  largest gap over each field's largest magnitude;
* ``ring`` — ring ingest: the ring against the program's own
  trajectories laid out by the reference's FIFO rule (exact), and full;
* ``model_loss`` — model learner: the held-out loss after each of its
  first three epochs, relative gap;
* ``model_grad`` — the first gradients as Adam holds them after the first
  epoch (its first moment), by the worst leaf;
* ``model_change`` — the parameters' change over three epochs, by the
  worst leaf;
* ``policy_return`` — policy learner: imagined return of each of its
  first three steps, largest gap over the largest magnitude of the
  three (a return near zero would blow a per-step relative gap up);
* ``policy_step`` — the first step as the update takes it, as the
  algorithm's file says (``bench/algos/<algo>.py::first_step``: the
  change of the first TRPO step, Adam's first moment after the first PPO
  step), by the worst leaf;
* ``policy_change`` — the policy's change over three steps, by the worst
  leaf;
* ``window_ring`` — ring ingest in the window: the ring as the window
  left it, against the last trajectories the window's drains moved (of
  every drain size it ran) laid out by the reference's FIFO rule
  (exact), and full;
* ``window_finite`` — non-finite leaves in the learners' parameters when
  the window closed.

"By the worst leaf" is the gap between the two sides' norms of a leaf,
over the larger of the reference's norm of that leaf and the median
leaf's. A change is compared only for leaves whose reference gradient
(Adam's first moment after the first step) is at least a thousandth of
the median leaf's; leaves below that move by round-off alone.

None of these depends on what the window happened to catch: every
number is taken from steps fixed by counts before the window opened, or
from the state it left (a window without a landing leaves set-up's ring
to compare), so every run reaches a decision.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from . import cells
from .reference import ring_layout

LEAF_FLOOR = 1e-3       # gradient share under which a leaf does not move


def leaves(tree):
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def _norms(tree):
    return np.array([np.linalg.norm(x) for x in leaves(tree)])


def delta(a, b):
    return [x - y for x, y in zip(leaves(a), leaves(b))]


def worst_leaf(got, ref, keep=None) -> float:
    """max over leaves of | |got| - |ref| | / max(|ref|, median |ref|)."""
    g = np.array([np.linalg.norm(x) for x in got])
    r = np.array([np.linalg.norm(x) for x in ref])
    if keep is not None:
        g, r = g[keep], r[keep]
    if not np.all(np.isfinite(g)):
        return math.inf
    scale = np.maximum(r, np.median(r))
    scale = np.where(scale > 0, scale, 1.0)
    return float(np.max(np.abs(g - r) / scale))


def rel_gap(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-30)))


def field_gap(got: dict, ref: dict) -> float:
    worst = 0.0
    for k, r in ref.items():
        g = np.asarray(got[k], np.float64)
        r = np.asarray(r, np.float64)
        if g.shape != r.shape or not np.all(np.isfinite(g)):
            return math.inf
        worst = max(worst, float(np.max(np.abs(g - r))
                                 / max(np.max(np.abs(r)), 1e-30)))
    return worst


def ring_gap(got: dict, layout, full: bool) -> float:
    """Largest gap between the program's rings and a reference layout;
    infinite where the rings are not full."""
    if not full:
        return math.inf
    train, val = layout
    return max(float(np.max(np.abs(got[part][k] - want[k])))
               for part, want in (("ring", train), ("ring_val", val))
               for k in want)


def moving(ref_grad) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding."""
    n = _norms(ref_grad)
    return n >= LEAF_FLOOR * np.median(n)


def _policy(state):
    return state["policy"]


def numbers(prog: dict, ref: dict, config: dict) -> dict:
    """Each compared number, from the two sides' set-up records."""
    out = {}
    out["rollout"] = field_gap(prog["trajs"], ref["trajs"])
    every = max(int(round(1 / config["holdout_frac"])), 2)
    out["ring"] = ring_gap(prog, ring_layout(prog["trajs"], config, every),
                           prog.get("ring_full", True))
    n = 3
    out["model_loss"] = rel_gap(prog["val_loss"][:n], ref["val_loss"][:n])
    out["model_grad"] = worst_leaf(leaves(prog["model_opt1"]),
                                   leaves(ref["model_opt1"]))
    keep = moving(ref["model_opt1"])
    out["model_change"] = worst_leaf(
        delta(prog["model3"], prog["model0"]),
        delta(ref["model3"], ref["model0"]), keep)
    out["policy_return"] = field_gap(
        {"return": prog["imagined_return"][:n]},
        {"return": ref["imagined_return"][:n]})
    first = cells.algorithm(config["algo"]).first_step
    out["policy_step"] = worst_leaf(first(prog["policy0"], prog["policy1"]),
                                    first(ref["policy0"], ref["policy1"]))
    out["policy_change"] = worst_leaf(
        delta(_policy(prog["policy3"]), _policy(prog["policy0"])),
        delta(_policy(ref["policy3"]), _policy(ref["policy0"])))
    if "window" in prog:
        w = prog["window"]
        out["window_ring"] = (
            ring_gap(w, ring_layout(w["trajs"], config, every, w["start"]),
                     w["full"]) if w["seen"] == w["ingested"] else math.inf)
    if "window_nonfinite" in prog:
        out["window_finite"] = float(prog["window_nonfinite"])
    return out


def judge(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}}; a number with no limit fails."""
    return {k: {"value": v, "limit": limits.get(k)} for k, v in values.items()}


def passed(checks: dict) -> bool:
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
