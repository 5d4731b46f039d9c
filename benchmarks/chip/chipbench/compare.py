"""The numbers that decide ``correct``. A cell compares those that its
``limits/<cell>.json`` gives a limit.

A training cell compares the program's readings with the reference's after
the same first steps from the same weights and batches:

* ``loss_gap``: the largest ``|loss - loss_ref| / |loss_ref|`` over the
  compared steps;
* ``grad_norm_gap``: the first step's gradient as the optimizer got it,
  by the worst leaf: ``|norm - norm_ref|`` over the larger of the leaf's
  reference norm and the median leaf's;
* ``update_norm_gap``: the same for the weights' change after the last
  compared step, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (the others move by round-off alone).
"""
from __future__ import annotations

import numpy as np

MOVING = 1e-3                      # share of the median gradient norm

TRAIN_CHECKS = ("loss_gap", "grad_norm_gap", "update_norm_gap")


def _worst_leaf(prog, ref, keep=None):
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    denom = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / denom))


def moving(ref: dict) -> np.ndarray:
    """Which leaves ``update_norm_gap`` compares: those whose reference
    gradient is at least ``MOVING`` of the median leaf's."""
    g = np.asarray(ref["grad_norms"], np.float64)
    return g >= MOVING * np.median(g)


def train_gaps(prog: dict, ref: dict) -> dict:
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_norm_gap": _worst_leaf(prog["grad_norms"], ref["grad_norms"]),
        "update_norm_gap": _worst_leaf(prog["delta_norms"],
                                       ref["delta_norms"], moving(ref)),
    }
