"""Traffic kind ``sweep``: back-to-back ``run_grid`` sweeps of the
configuration's whole grid, ``lanes_per_sweep`` Monte-Carlo runs each,
split evenly over the cells; every sweep draws a fresh grid seed from
``--seed``.  The window keeps every sweep's per-cell statistics for the
comparison with the plain reference."""

from __future__ import annotations

from chipbench import cells as C
from chipbench import compare
from chipbench.bench import BenchError, rate
from chipbench.load import (STREAM_CONTROL, STREAM_PROGRAM, STREAM_REFERENCE,
                            annotate, engine, seed_stream, stream_seed)


class Load:
    """Back-to-back sweeps of the whole grid."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.experiments import GridSpec

        self.cfg = cfg
        self.cells = tuple(C.program_cells(cfg))
        self.runs = int(traffic["lanes_per_sweep"]) // len(self.cells)
        if self.runs < 2:
            raise BenchError("lanes_per_sweep leaves a cell under 2 runs")
        self.engine = engine(traffic)
        self._grid = lambda s: GridSpec(self.cells, n_runs=self.runs, seed=s)
        self._seeds = seed_stream(seed, STREAM_PROGRAM)
        self.reset()

    def reset(self) -> None:
        """Forget what was measured (the warm-up's answers are not kept)."""
        self.sums = compare.Sums()
        self.done = self.failed = 0
        self.runs_done = 0
        self.pack_s = []

    def warm(self) -> None:
        """One sweep: every chunk shape the window uses."""
        self.step()

    def step(self) -> None:
        from repro.core import jax_sim
        from repro.experiments import run_grid

        grid = self._grid(int(self._seeds.integers(0, 2**62)))
        try:
            with annotate("chipbench.sweep"):
                sweep = run_grid(grid, config=self.engine)
        except RuntimeError:  # lanes that never converge: no answer
            self.failed += 1
            return
        self.sums.add_sweep(sweep)
        self.done += 1
        self.runs_done += grid.n_lanes
        self.pack_s.append(jax_sim.LAST_TIMINGS["pack_s"])

    def e2e(self, window_s: float) -> dict:
        return {"runs_per_s": rate(self.runs_done, window_s)}

    def layer(self) -> dict:
        return {"pack_s": self.pack_s}

    def owed(self) -> dict:
        return {c.label: self.runs * self.done for c in self.cells}

    def check(self, ref, seed: int) -> dict:
        sims = ref.simulate(
            C.reference_cells(self.cfg, ref), compare.REFERENCE_RUNS,
            stream_seed(seed, STREAM_REFERENCE),
        )
        nums, worst = compare.mc_checks(self.sums, sims, self.owed())
        return {"numbers": nums, "worst": worst}


def control_numbers(cfg: dict, traffic: dict, seed: int, n_ctrl: int,
                    ref) -> dict:
    """The control's numbers on one seed: the reference with faults during
    downtime or recovery ignored, in the program's place."""
    cells = C.reference_cells(cfg, ref)
    runs = ref.simulate(cells, n_ctrl, stream_seed(seed, STREAM_CONTROL),
                        cascade=False)
    sims = ref.simulate(cells, compare.REFERENCE_RUNS,
                        stream_seed(seed, STREAM_REFERENCE))
    nums, worst = compare.mc_checks(
        compare.sums_of(runs), sims, {c["label"]: n_ctrl for c in cells}
    )
    return {"numbers": nums, "worst": worst}
