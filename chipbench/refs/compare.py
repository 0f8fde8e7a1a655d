"""The comparison that decides ``correct``.

A training cell is compared on three numbers, each against the plain
reference run over the same inputs:

* ``loss_gap``: the largest gap between a step's loss in the program and
  in the reference, over the first three steps, relative to the
  reference's loss, and in nats where that loss is under 1 nat: a
  cross-entropy that training drives towards 0 would otherwise make a
  rounding gap of the same size read ever larger.
* the first-step number (``grad1_gap`` for the pod step: the first
  gradient, read from the momentum after one step; ``change1_gap`` for a
  protocol round: the change of every client's model after one round) and
  ``change3_gap``, the same change after three steps: per leaf, the gap
  between the program's norm and the reference's, measured against the
  reference's norm of that leaf or the median moved leaf's, whichever is
  larger, and the worst leaf taken.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["loss_gap", "norm_gap", "numbers"]

NAT = 1.0           # the loss scale below which a loss gap is absolute


def loss_gap(prog, ref) -> float:
    p = np.asarray(prog, np.float64).ravel()
    r = np.asarray(ref, np.float64).ravel()
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return math.inf
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), NAT)))


def norm_gap(prog, ref) -> float:
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return math.inf
    moved = r[r > 0]
    if moved.size == 0:
        return 0.0 if not np.any(p) else math.inf
    denom = np.maximum(r, np.median(moved))
    return float(np.max(np.abs(p - r) / denom))


def numbers(prog: dict, ref: dict) -> dict:
    """Every compared number of one run, from the program's and the
    reference's readings (dicts with ``losses``, ``first`` and ``third``)."""
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            prog["first_name"]: norm_gap(prog["first"], ref["first"]),
            "change3_gap": norm_gap(prog["third"], ref["third"])}
