"""Plain reference for the Section 5 deployments with window-based
predictions.

A straightforward scalar restatement of the paper's Section 4 model
(arXiv:1207.6936: prediction windows, strategies Instant, NoCkptI and
WithCkptI) on the Section 5 platforms, written against NumPy alone: it
imports nothing of the program under test.  It reuses the exact-date
reference (:mod:`chipbench.references.s5`) for what the two models
share: the renewal streams, and the job's regular mode, faults,
checkpoints and the restart rule.

What windows add, one job of ``W`` seconds of work:

* a fault predicted (probability ``r``) gives a window
  ``[t_f - u I, t_f - u I + I]``, ``u`` uniform on ``[0, 1)``, its start
  clipped at 0; a false prediction's window starts at its date;
* trusted predictions are served in window-start order: a stable sort of
  the true positives in fault order followed by the false predictions,
  so ties go by fault order and a true before a false prediction;
* at a window start ``t0`` (action point ``t0 - C``) the job takes a
  proactive checkpoint ending at ``t0`` (or, with no time for it, works
  on to ``t0``, crediting its regular period only up to ``t0 - C``);
  then ``instant`` returns to regular mode, ``nockpt`` works to
  ``t0 + I`` without checkpoints, and ``withckpt`` checkpoints every
  ``T_P`` inside the window;
* the regular period is ``sqrt(2 mu C / (1 - r))``, uncapped, for all
  three strategies (Section 4's extremum at trust ``q = 1``), and
  ``T_P`` is Section 4.3's integer partition of ``I``.

``cascade=False`` is the control, as in :mod:`chipbench.references.s5`:
a fault during downtime or recovery is ignored.
"""

from __future__ import annotations

import math
import os
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

import numpy as np

from chipbench.references import s5
from chipbench.references.s5 import EPS, STATS, arrivals

#: worker processes of :func:`simulate` (0: one per spare core, up to 12)
WORKERS = 0


# --------------------------------------------------------------------------- #
# Traces with windows
# --------------------------------------------------------------------------- #
def trace(rng, law, shape, mu, recall, precision, horizon, I):
    """One trace: sorted fault dates, and predictions ``(t0, fault date or
    None)`` in window-start order."""
    faults = arrivals(rng, law, shape, mu, horizon)
    hit = rng.random(len(faults)) < recall
    off = rng.random(len(faults)) * I
    preds = [(max(0.0, f - o), f) for f, h, o in zip(faults, hit, off) if h]
    if recall > 0.0 and precision < 1.0:
        fp_mean = precision * mu / (recall * (1.0 - precision))
        preds += [(t, None) for t in arrivals(rng, law, shape, fp_mean, horizon)]
    preds.sort(key=lambda e: e[0])  # stable: fault order, then true first
    return faults, preds


# --------------------------------------------------------------------------- #
# One job
# --------------------------------------------------------------------------- #
class _Job(s5._Job):
    """The exact-date job with Section 4's window episodes."""

    def __init__(self, W, C, D, R, M, T, mode, faults, preds, cascade, I, T_P):
        super().__init__(W, C, D, R, M, T, "exact", faults, preds, cascade)
        if mode not in ("instant", "nockpt", "withckpt"):
            self.preds = []  # "none": no prediction is trusted
        self.mode, self.I, self.T_P = mode, I, T_P

    def episode(self, t0: float, fault_date) -> None:
        C = self.C
        if self.t <= t0 - C:
            # the pre-window checkpoint, as late as possible
            if self.t < t0 - C and (self.work_until(t0 - C) or self.done):
                return
            if self.checkpoint(proactive=True):
                return
        else:
            # no time for it: work on to the window start, crediting the
            # period only up to t0 - C
            if self.work_until(max(self.t, t0 - C)) or self.done:
                return
            if self.work_until(t0, credit=False) or self.done:
                return
        end = t0 + self.I
        if self.mode == "nockpt":
            self.work_until(end, credit=False)
        elif self.mode == "withckpt":
            while self.t < end - EPS and not self.done:
                seg = min(self.t + (self.T_P - C), end - C)
                if seg > self.t and (
                    self.work_until(seg, credit=False) or self.done
                ):
                    return
                if self.checkpoint(proactive=True):
                    return


# --------------------------------------------------------------------------- #
# Periods (Section 4)
# --------------------------------------------------------------------------- #
def period(strategy, C, mu, r, q=1.0, dtype=np.float64):
    """Regular period ``sqrt(2 mu C / (1 - r q))``, never below ``C``: the
    uncapped extremum of every window strategy's waste."""
    f = dtype
    return max(np.sqrt(f(2.0) * f(mu) * f(C) / (f(1.0) - f(r) * f(q))), f(C))


def proactive_period(C, p, I):
    """WithCkptI's ``T_P`` (Section 4.3): ``I / k`` for the whole ``k``
    near ``I / T_P^extr`` that minimises ``K C / T_P + T_P``, with
    ``T_P^extr = sqrt(K C)`` and ``K = ((1 - p) I + p E_f) / p``, the fault
    uniform in its window (``E_f = I / 2``); ``T_P >= C``.  None where the
    window cannot hold a checkpoint (``I < C``)."""
    if I < C or I <= 0.0:
        return None
    K = ((1.0 - p) * I + p * I / 2.0) / p
    k_lo = max(1, math.floor(I / math.sqrt(K * C)))
    best = None
    for k in (k_lo, k_lo + 1):
        tp = I / k
        if tp >= C and (best is None or K * C / tp + tp < best[0]):
            best = (K * C / tp + tp, tp)
    if best is None:
        return I / max(1, math.floor(I / C))
    return best[1]


# --------------------------------------------------------------------------- #
# Monte-Carlo over cells
# --------------------------------------------------------------------------- #
def _slot_runs(task):
    """Simulate runs ``lo..hi`` of one trace slot for each of its cells."""
    slot, lo, hi, seed, cascade = task
    out = np.zeros((len(slot["cells"]), hi - lo, len(STATS) + 1))
    for j, run in enumerate(range(lo, hi)):
        rng = np.random.default_rng([seed, slot["id"], run])
        faults, preds = trace(
            rng, slot["law"], slot["shape"], slot["mu"], slot["recall"],
            slot["precision"], slot["horizon"], slot["I"],
        )
        for i, c in enumerate(slot["cells"]):
            out[i, j] = _Job(
                slot["W"], c["C"], c["D"], c["R"], c["M"], c["T"], c["mode"],
                faults, preds, cascade, c["I"], c["T_P"],
            ).run(slot["horizon"])
    return slot["id"], lo, out


def simulate(cells, n_runs: int, seed: int, cascade: bool = True):
    """Per-run statistics of ``n_runs`` jobs of every cell.

    ``cells`` are dicts with ``label, law, shape, mu, recall, precision,
    horizon, W, C, D, R, M, T, mode, I, T_P``.  Cells with equal trace
    parameters share their traces (the paired design).  Returns
    ``{label: {stat: (n_runs,) array}}`` plus the ``exhausted`` count per
    label.  Runs are spread over :data:`WORKERS` spawned processes; the
    result does not depend on how many."""
    slots = {}
    for c in cells:
        key = (c["law"], c["shape"], c["mu"], c["recall"], c["precision"],
               c["horizon"], c["W"], c["I"])
        s = slots.setdefault(key, {
            "id": len(slots), "law": c["law"], "shape": c["shape"],
            "mu": c["mu"], "recall": c["recall"],
            "precision": c["precision"], "horizon": c["horizon"],
            "W": c["W"], "I": c["I"], "cells": [],
        })
        s["cells"].append(c)
    workers = WORKERS or max(1, min(12, (os.cpu_count() or 2) - 1))
    step = max(1, -(-n_runs // max(1, (2 * workers) // max(1, len(slots)))))
    tasks = [
        (s, lo, min(lo + step, n_runs), seed, cascade)
        for s in slots.values() for lo in range(0, n_runs, step)
    ]
    if workers == 1:
        parts = [_slot_runs(t) for t in tasks]
    else:
        with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as ex:
            parts = list(ex.map(_slot_runs, tasks))
    by_slot = {s["id"]: s for s in slots.values()}
    acc = {}
    for sid, lo, out in parts:
        for i, c in enumerate(by_slot[sid]["cells"]):
            acc.setdefault(c["label"], {})[lo] = out[i]
    res = {}
    for label, chunks in acc.items():
        a = np.concatenate([chunks[k] for k in sorted(chunks)])
        res[label] = {k: a[:, i] for i, k in enumerate(STATS)}
        res[label]["exhausted"] = int(a[:, len(STATS)].sum())
    return res
