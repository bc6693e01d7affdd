"""Traffic kind ``query``: back-to-back policy queries from one client.

A query takes one (law, predictor, platform size) point of the
configuration, asks ``repro.core.optimize(..., method="newton")`` for
every strategy's optimal period, then simulates each strategy at that
period with ``runs_per_strategy`` runs in one ``run_grid`` call, and
answers with the strategy of least simulated waste.  Points come in
blocks, each a seeded permutation of all points, so every seed sends the
same mix in another order.  The window keeps every answer: the periods
and wastes for the reference's optima, the simulated statistics for its
Monte-Carlo runs."""

from __future__ import annotations

import time
from dataclasses import replace

import numpy as np

from chipbench import cells as C
from chipbench import compare
from chipbench.bench import percentile
from chipbench.load import (STREAM_CONTROL, STREAM_PROGRAM, STREAM_REFERENCE,
                            annotate, engine, seed_stream, stream_seed)


class Load:
    """Back-to-back policy queries from one client."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg = cfg
        self.points = C.points(cfg)
        self.strategies = list(cfg["strategies"])
        self.names = [s["name"] for s in self.strategies]
        self.runs = int(traffic["runs_per_strategy"])
        self.engine = engine(traffic)
        self._order = seed_stream(seed, STREAM_PROGRAM)
        self._block = []
        self.reset()

    def reset(self) -> None:
        self.sums = compare.Sums()
        self.done = self.failed = 0
        self.latency_s, self.newton_s, self.pack_s = [], [], []
        self.answers = []  # (point index, [(T, q, waste) per strategy])
        self.hits = {}  # point index -> queries answered

    def warm(self) -> None:
        """One query at every point: every shape the window can use."""
        for i in range(len(self.points)):
            self.query(i)

    def step(self) -> None:
        if not self._block:
            self._block = list(self._order.permutation(len(self.points)))
        self.query(int(self._block.pop()))

    def query(self, i: int):
        from repro.core import jax_sim, optimize
        from repro.experiments import GridSpec, run_grid

        law, pred, n = self.points[i]
        t0 = time.monotonic()
        try:
            with annotate("chipbench.query"):
                plat, pm, _ = C.program_objects(self.cfg, law, pred, n)
                with annotate("chipbench.optimize"):
                    pol = optimize(self.names, plat, pm, method="newton")
                t1 = time.monotonic()
                cells = []
                for j, s in enumerate(self.strategies):
                    base = C.program_cell(self.cfg, law, pred, n, s).strategy
                    q = float(pol.q[j])
                    strat = replace(
                        base, T_R=float(pol.T_R[j]), q=q,
                        mode=base.mode if q > 0.0 else "none",
                    )
                    cells.append(C.program_cell(self.cfg, law, pred, n, s, strat))
                grid = GridSpec(
                    tuple(cells), n_runs=self.runs,
                    seed=int(self._order.integers(0, 2**62)),
                )
                with annotate("chipbench.run_grid"):
                    sweep = run_grid(grid, config=self.engine)
                best = min(sweep.cells, key=lambda c: c.mean_waste)
        except RuntimeError:
            self.failed += 1
            return None
        self.latency_s.append(time.monotonic() - t0)
        self.newton_s.append(t1 - t0)
        self.pack_s.append(jax_sim.LAST_TIMINGS["pack_s"])
        self.sums.add_sweep(sweep)
        self.answers.append((i, [
            (float(pol.T_R[j]), float(pol.q[j]), float(pol.waste[j]))
            for j in range(len(self.strategies))
        ]))
        self.hits[i] = self.hits.get(i, 0) + 1
        self.done += 1
        return best.cell.label, best.mean_waste

    def e2e(self, window_s: float) -> dict:
        return {
            "query_p50_s": percentile(self.latency_s, 50),
            "query_p95_s": percentile(self.latency_s, 95),
        }

    def layer(self) -> dict:
        return {"pack_s": self.pack_s, "newton_s": self.newton_s}

    def owed(self) -> dict:
        return {
            C.label(*self.points[i], s): self.runs * k
            for i, k in self.hits.items() for s in self.strategies
        }

    def check(self, ref, seed: int) -> dict:
        opt = optima(self.cfg, self.points, self.strategies, self.hits, ref)
        sims = ref.simulate(
            optimum_cells(self.cfg, self.points, self.strategies, opt),
            compare.REFERENCE_RUNS, stream_seed(seed, STREAM_REFERENCE),
        )
        nums, worst = compare.mc_checks(self.sums, sims, self.owed())
        nums["policy_rel_err"] = policy_error(self.cfg, self.points, [
            ((i, s["name"]), p) for i, pols in self.answers
            for s, p in zip(self.strategies, pols)
        ], opt, ref)
        return {"numbers": nums, "worst": worst}


def _costs(cfg: dict, point) -> tuple:
    """``(C, D, R, M, mu, recall, precision)`` of one point."""
    _, pred, n = point
    return (cfg["C_s"], cfg["D_s"], cfg["R_s"], cfg["M_s"],
            cfg["mu_ind_s"] / n, pred["recall"], pred["precision"])


def optima(cfg, points, strategies, hits, ref, dtype=np.float64) -> dict:
    """The reference's ``(T, q, waste)`` per (point index, strategy name)
    for every point in ``hits``."""
    return {
        (i, s["name"]): ref.optimum(s["name"], *_costs(cfg, points[i]), dtype)
        for i in hits for s in strategies
    }


def optimum_cells(cfg, points, strategies, opt: dict) -> list:
    """Reference cells at the periods and trust of ``opt``."""
    by_name = {s["name"]: s for s in strategies}
    return [
        C.reference_cell(cfg, *points[i], by_name[s], T, q)
        for (i, s), (T, q, _) in opt.items()
    ]


def policy_error(cfg, points, answers, opt: dict, ref) -> float:
    """``policy_rel_err`` of ``answers``, ``[((point, strategy), (T, q,
    waste))]``, against the reference's optima ``opt``."""
    keys = [k for k, _ in answers]
    return compare.policy_rel_err(
        [a for _, a in answers], [opt[k] for k in keys],
        lambda j, T, q: ref.waste(
            keys[j][1], T, q, *_costs(cfg, points[keys[j][0]])
        ),
    )


def control_numbers(cfg: dict, traffic: dict, seed: int, n_ctrl: int,
                    ref) -> dict:
    """The control's numbers on one seed: every point's optimum in float32
    where the configuration states double, simulated by the reference
    with faults during downtime or recovery ignored."""
    points = C.points(cfg)
    strategies = list(cfg["strategies"])
    hits = range(len(points))
    good = optima(cfg, points, strategies, hits, ref)
    low = optima(cfg, points, strategies, hits, ref, np.float32)
    cells = optimum_cells(cfg, points, strategies, low)
    runs = ref.simulate(cells, n_ctrl, stream_seed(seed, STREAM_CONTROL),
                        cascade=False)
    sims = ref.simulate(optimum_cells(cfg, points, strategies, good),
                        compare.REFERENCE_RUNS,
                        stream_seed(seed, STREAM_REFERENCE))
    nums, worst = compare.mc_checks(
        compare.sums_of(runs), sims, {c["label"]: n_ctrl for c in cells}
    )
    nums["policy_rel_err"] = policy_error(cfg, points, list(low.items()),
                                          good, ref)
    return {"numbers": nums, "worst": worst}
