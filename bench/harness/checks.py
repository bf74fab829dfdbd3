"""The numbers the correctness check compares, from the program's and
the reference's readings. Limits live in ``limits/<workload>.json``;
``PERF.md`` gives the readings each was set from.
"""
from __future__ import annotations

import statistics
from typing import Dict, Sequence

import numpy as np

# A leaf whose reference gradient norm is under this share of the median
# leaf's moves under Adam by round-off alone: its change is not compared.
STILL_LEAF = 1e-3


def _worst_leaf(got: Dict[str, float], want: Dict[str, float],
                leaves: Sequence[str]):
    """Largest ``|‖got‖ − ‖want‖|`` over ``leaves``, each against the
    larger of the reference's norm of that leaf and of the median leaf;
    returns (gap, leaf)."""
    med = statistics.median(want.values())
    return max((abs(got[k] - want[k]) / max(want[k], med, 1e-30), k)
               for k in leaves)


def train_gaps(prog: Dict, ref: Dict):
    """The numbers compared for a train cell, and where each is worst.

    ``loss_gap``: the relative gap of the first step's loss;
    ``loss_gap_later``: the largest of the later steps' (whose weights
    have moved under Adam's first, sign-like steps, so round-off moves
    them more); ``grad_gap``: the worst leaf's gap of first-gradient
    norms; ``update_gap``: the worst leaf's gap of the parameters'
    change after the steps, over the leaves the reference moves."""
    losses = [abs(a - b) / abs(b)
              for a, b in zip(prog["losses"], ref["losses"])]
    med = statistics.median(ref["grad"].values())
    moving = [k for k, g in ref["grad"].items() if g >= STILL_LEAF * med]
    grad, grad_leaf = _worst_leaf(prog["grad"], ref["grad"], list(ref["grad"]))
    upd, upd_leaf = _worst_leaf(prog["delta"], ref["delta"], moving)
    numbers = {"loss_gap": losses[0], "loss_gap_later": max(losses[1:]),
               "grad_gap": grad, "update_gap": upd}
    notes = {"losses": prog["losses"], "ref_losses": ref["losses"],
             "step_loss_gaps": losses, "grad_leaf": grad_leaf,
             "update_leaf": upd_leaf,
             "still_leaves": sorted(set(ref["grad"]) - set(moving)),
             "ref_margins": ref.get("margins"),
             "grad_norms": {k: [prog["grad"][k], v]
                            for k, v in ref["grad"].items()},
             "delta_norms": {k: [prog["delta"][k], v]
                             for k, v in ref["delta"].items()}}
    return numbers, notes


def topk_gap(best: np.ndarray, got: np.ndarray, vals,
             scale: np.ndarray, ids_ok: bool = True) -> float:
    """Widest gap of a returned top-k, relative to each row's largest
    reference score magnitude ``scale``: by how much the reference's
    score ``got`` of the item in slot ``j`` lies off the reference's
    ``j``-th best ``best``, and (when ``vals`` is given) by how much the
    returned value lies off ``got``. An id outside the catalog's valid
    range (``ids_ok`` false) reads 1."""
    if not ids_ok:
        return 1.0
    gap = np.abs(best - got)
    if vals is not None:
        gap = np.maximum(gap, np.abs(np.asarray(vals) - got))
    return float(np.max(gap / scale[:, None]))


def rank_gap(at_rank: np.ndarray, target: np.ndarray,
             scale: np.ndarray) -> float:
    """Widest gap of a returned rank ``r``: by how much the reference's
    ``r``-th best score ``at_rank`` (0-based, the target placed last
    among equals) lies off the reference's target score, relative to
    the row's largest score magnitude."""
    return float(np.max(np.abs(at_rank - target) / scale))
