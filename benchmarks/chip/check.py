"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against a limit of its own kept in the cell's file
``cells/<workload>.json`` with the readings it was set from:

- ``loss_gap``: the largest relative gap between the program's loss and
  the reference's, over the steps the reference follows;
- ``grad_gap``: after step 1 each worker's momentum is the gradient as
  the optimizer got it (clipped, plus weight decay); the gap between the
  program's norm and the reference's of each weight (of each layer),
  over the reference's norm or the median one, whichever is larger, at
  the worst weight and worker;
- ``change_gap``: the same for each weight's change from its initial
  value after the steps the reference follows (their syncs included).
  Weights whose reference gradient is under a thousandth of the median
  weight's move by round-off alone and are left out.

A state left unchanged reads 1 on ``change_gap`` by construction.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
NAMES = ("loss_gap", "grad_gap", "change_gap")
QUIET = 1e-3        # a weight whose gradient is under QUIET x the median


def load_limits(workload: str, here: Path = HERE) -> dict:
    path = Path(here) / "cells" / f"{workload}.json"
    with open(path) as f:
        limits = json.load(f)["limits"]
    missing = [n for n in NAMES if n not in limits]
    if missing:
        raise KeyError(f"{path}: no limit for {missing}")
    return {n: float(limits[n]["limit"]) for n in NAMES}


def _norm_gap(prog: dict, ref: dict, keep=None) -> float:
    names = sorted(ref)
    if sorted(prog) != names:
        raise ValueError(f"weights differ: {sorted(prog)} vs {names}")
    r = np.concatenate([np.asarray(ref[k], np.float64).ravel() for k in names])
    p = np.concatenate([np.asarray(prog[k], np.float64).ravel()
                        for k in names])
    if p.shape != r.shape:
        raise ValueError(f"{p.shape} norms vs {r.shape}")
    if keep is not None:
        p, r = p[keep], r[keep]
    floor = max(float(np.median(r)), 1e-30)
    return float(np.max(np.abs(p - r) / np.maximum(r, floor)))


def readings(prog: dict, ref: dict) -> dict:
    """The three numbers; NaN in either side reads as infinity."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape:
        raise ValueError(f"{lp.size} program steps vs {lr.size} reference")
    names = sorted(ref["grad_norms"])
    g = np.concatenate([np.asarray(ref["grad_norms"][k], np.float64).ravel()
                        for k in names])
    moving = g >= QUIET * np.median(g)
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
           "grad_gap": _norm_gap(prog["grad_norms"], ref["grad_norms"]),
           "change_gap": _norm_gap(prog["change_norms"], ref["change_norms"],
                                   keep=moving)}
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number within its
    limit."""
    checks = {n: {"value": values[n], "limit": limits[n]} for n in NAMES}
    return all(values[n] <= limits[n] for n in NAMES), checks
