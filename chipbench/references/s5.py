"""Plain reference for the Section 5 exact-date deployments.

A straightforward scalar re-statement of the paper's simulation model
(arXiv:1207.6936, Sections 2, 3 and 5) and of its closed-form wastes,
written against NumPy alone: it imports nothing of the program under
test and takes nothing the program made.  It holds only what the
benchmark's exact-date configurations run: the Young baseline, exact-date
proactive checkpointing and proactive migration, on renewal fault
streams (exponential or Weibull) with an independent false-prediction
stream.

The model, one job of ``W`` seconds of work:

* faults form a renewal process of mean ``mu``; each is predicted with
  probability ``r`` (recall) at its exact date; false predictions form a
  second renewal process of mean ``p mu / (r (1 - p))`` of the same law;
* the job checkpoints (cost ``C``) after every ``T - C`` seconds of work
  credited to the period; a fault loses the work since the last
  checkpoint and costs downtime ``D`` plus recovery ``R``; a fault that
  strikes during downtime or recovery is counted and restarts them;
* a trusted prediction at date ``t0`` triggers a proactive checkpoint
  that ends at ``t0`` (``exact``), or a migration of cost ``M`` that ends
  at ``t0`` and escapes the predicted fault (``migration``); when the
  action point has passed, the prediction is ignored.

``cascade=False`` breaks the restart rule (a fault during downtime or
recovery is ignored), which is the benchmark's control: a shortcut a
faster engine could take, and one the comparison has to catch.
"""

from __future__ import annotations

import math
import os
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

import numpy as np

#: absolute time tolerance of the end-of-span checks, seconds
EPS = 1e-6

#: worker processes of :func:`simulate` (0: one per spare core, up to 12)
WORKERS = 0

STATS = ("waste", "makespan", "faults", "regular", "proactive", "migrations")


# --------------------------------------------------------------------------- #
# Fault and prediction traces
# --------------------------------------------------------------------------- #
def gaps(rng, law: str, shape: float, mean: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps of the given law, scaled to ``mean``."""
    if law == "exponential":
        g = rng.exponential(mean, n)
    elif law == "weibull":
        g = mean / math.gamma(1.0 + 1.0 / shape) * rng.weibull(shape, n)
    else:
        raise ValueError(f"unknown fault law {law!r}")
    return np.maximum(g, 1e-9)


def arrivals(rng, law, shape, mean, horizon) -> list:
    """Renewal arrival dates in ``(0, horizon]``."""
    if not math.isfinite(mean):
        return []
    out = []
    t = 0.0
    block = max(16, int(horizon / mean * 1.5) + 8)
    while True:
        cum = t + np.cumsum(gaps(rng, law, shape, mean, block))
        keep = cum[cum <= horizon]
        out.extend(keep.tolist())
        if len(keep) < len(cum):
            return out
        t = float(cum[-1])


def trace(rng, law, shape, mu, recall, precision, horizon):
    """One exact-date trace: sorted fault dates, and sorted predictions as
    ``(t0, fault date or None)`` pairs."""
    faults = arrivals(rng, law, shape, mu, horizon)
    hit = rng.random(len(faults)) < recall
    preds = [(f, f) for f, h in zip(faults, hit) if h]
    if recall > 0.0 and precision < 1.0:
        fp_mean = precision * mu / (recall * (1.0 - precision))
        preds += [(t, None) for t in arrivals(rng, law, shape, fp_mean, horizon)]
    preds.sort(key=lambda e: e[0])
    return faults, preds


# --------------------------------------------------------------------------- #
# One job
# --------------------------------------------------------------------------- #
class _Job:
    def __init__(self, W, C, D, R, M, T, mode, faults, preds, cascade):
        self.W, self.C, self.DR, self.M, self.T = W, C, D + R, M, T
        self.mode = mode
        self.faults = list(faults)
        self.preds = preds if mode in ("exact", "migration") else []
        self.cascade = cascade
        self.t = self.saved = self.unsaved = self.pw = 0.0
        self.fi = self.pi = 0
        self.done = False
        self.n_faults = self.n_reg = self.n_pro = self.n_mig = 0

    def next_fault(self) -> float:
        F = self.faults
        while self.fi < len(F) and F[self.fi] < self.t:
            f = F[self.fi]
            if self.cascade and f >= self.t - self.DR:
                self.n_faults += 1
                self.t = f + self.DR
            self.fi += 1
        return F[self.fi] if self.fi < len(F) else math.inf

    def next_action(self) -> float:
        lead = self.M if self.mode == "migration" else self.C
        while self.pi < len(self.preds) and self.preds[self.pi][0] - lead < self.t:
            self.pi += 1
        if self.pi >= len(self.preds):
            return math.inf
        return self.preds[self.pi][0] - lead

    def fault(self, tf: float) -> None:
        self.fi += 1
        self.n_faults += 1
        self.unsaved = self.pw = 0.0
        self.t = tf + self.DR

    def work_until(self, target: float, credit: bool = True) -> bool:
        target = min(target, self.t + self.W - self.saved - self.unsaved)
        nf = self.next_fault()
        if nf <= target:
            self.fault(nf)
            return True
        dt = target - self.t
        self.unsaved += dt
        if credit:
            self.pw += dt
        self.t = target
        if self.saved + self.unsaved >= self.W - EPS:
            self.done = True
        return False

    def idle_until(self, target: float) -> bool:
        nf = self.next_fault()
        if nf <= target:
            self.fault(nf)
            return True
        self.t = target
        return False

    def checkpoint(self, proactive: bool) -> bool:
        end = self.t + self.C
        nf = self.next_fault()
        if nf < end:
            self.fault(nf)
            return True
        self.t = end
        self.saved += self.unsaved
        self.unsaved = 0.0
        if proactive:
            self.n_pro += 1
        else:
            self.n_reg += 1
            self.pw = 0.0
        return False

    def episode(self, t0: float, fault_date) -> None:
        if self.mode == "migration":
            # the predicted fault strikes the vacated node
            if fault_date is not None and fault_date >= self.t:
                try:
                    self.faults.pop(self.faults.index(fault_date, self.fi))
                except ValueError:
                    pass
            if not self.idle_until(t0):
                self.n_mig += 1
            return
        if self.t <= t0 - self.C:
            if self.t < t0 - self.C:
                if self.work_until(t0 - self.C) or self.done:
                    return
            self.checkpoint(proactive=True)
            return
        # no time for the proactive checkpoint: work on to the date,
        # crediting the period only up to t0 - C
        if self.work_until(max(self.t, t0 - self.C)):
            return
        if not self.done:
            self.work_until(t0, credit=False)

    def run(self, horizon: float):
        wpp = max(self.T - self.C, 1e-9)
        exhausted = False
        while not self.done:
            if self.t > horizon:
                exhausted = True
            na = self.next_action()
            rem = wpp - self.pw
            if rem <= EPS:
                if self.checkpoint(proactive=False):
                    continue
                if na <= self.t and self.pi < len(self.preds):
                    t0, fd = self.preds[self.pi]
                    self.pi += 1
                    if t0 >= self.t - 1e-9:
                        self.episode(t0, fd)
                continue
            seg_end = self.t + rem
            if na < seg_end:
                if self.work_until(na):
                    continue
                if self.done:
                    break
                t0, fd = self.preds[self.pi]
                self.pi += 1
                self.episode(t0, fd)
                continue
            self.work_until(seg_end)
        return (
            1.0 - self.W / self.t, self.t, self.n_faults, self.n_reg,
            self.n_pro, self.n_mig, exhausted,
        )


# --------------------------------------------------------------------------- #
# Periods and closed forms (Young; Equations (1) and (3))
# --------------------------------------------------------------------------- #
def waste(strategy, T, q, C, D, R, M, mu, r, p, dtype=np.float64):
    """Closed-form waste of ``strategy`` at period ``T`` and trust ``q``."""
    f = dtype
    T, q, C, D, R, M, mu, r, p = (f(x) for x in (T, q, C, D, R, M, mu, r, p))
    one, two = f(1.0), f(2.0)
    if strategy == "young":
        return C / T + (T / two + D + R) / mu
    if strategy == "exact":
        return C / T + ((one - r * q) * T / two + D + R + (q * r / p) * C) / mu
    if strategy == "migration":
        return C / T + ((one - r * q) * (T / two + D + R) + (q * r / p) * M) / mu
    raise ValueError(f"unknown strategy {strategy!r}")


def period(strategy, C, mu, r, q=1.0, dtype=np.float64):
    """Extremal period ``sqrt(2 mu C / (1 - r q))`` (Young's at ``q = 0``),
    never below ``C``: the paper's uncapped Section 5 policy."""
    f = dtype
    rq = f(0.0) if strategy == "young" else f(r) * f(q)
    return max(np.sqrt(f(2.0) * f(mu) * f(C) / (f(1.0) - rq)), f(C))


def optimum(strategy, C, D, R, M, mu, r, p, dtype=np.float64):
    """``(T, q, waste)`` minimising the closed form over ``q`` in {0, 1}."""
    best = None
    for q in ((0.0,) if strategy == "young" else (0.0, 1.0)):
        T = period("young" if q == 0.0 else strategy, C, mu, r, q, dtype)
        w = waste(strategy, T, q, C, D, R, M, mu, r, p, dtype)
        if best is None or w < best[2]:
            best = (float(T), q, float(w))
    return best


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
            slot["precision"], slot["horizon"],
        )
        for i, c in enumerate(slot["cells"]):
            out[i, j] = _Job(
                slot["W"], c["C"], c["D"], c["R"], c["M"], c["T"], c["mode"],
                faults, preds, cascade,
            ).run(slot["horizon"])
    return slot["id"], lo, out


def simulate(cells, n_runs: int, seed: int, cascade: bool = True):
    """Per-run statistics of ``n_runs`` jobs of every cell.

    ``cells`` are dicts with ``label, law, shape, mu, recall, precision,
    horizon, W, C, D, R, M, T, mode``.  Cells with equal trace parameters
    share their traces (the paired design).  Returns ``{label: {stat:
    (n_runs,) array}}`` plus the ``exhausted`` count per label.  Runs are
    spread over :data:`WORKERS` spawned processes; the result does not
    depend on how many."""
    slots = {}
    for c in cells:
        key = (c["law"], c["shape"], c["mu"], c["recall"], c["precision"],
               c["horizon"], c["W"])
        s = slots.setdefault(key, {
            "id": len(slots), "law": c["law"], "shape": c["shape"],
            "mu": c["mu"], "recall": c["recall"],
            "precision": c["precision"], "horizon": c["horizon"],
            "W": c["W"], "cells": [],
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
