"""Traffic kind ``window_sweep``: the ``sweep`` kind's back-to-back sweeps
of the whole grid, for a deployment with prediction windows.  The
program is driven exactly as ``sweep`` drives it; the comparison differs:
its reference cells carry the window ``I`` and the periods the plain
reference computes for windows itself (the regular period and
WithCkptI's ``T_P``), which :func:`chipbench.cells.reference_cell` does
not.

A program that keeps one pending true positive per lane serves windows
in fault order, not in window order: the kind refuses it before any run
(:class:`~chipbench.bench.BenchError`), as it refuses a missing chip."""

from __future__ import annotations

from chipbench import cells as C
from chipbench import compare
from chipbench.bench import BenchError
from chipbench.load import STREAM_CONTROL, STREAM_REFERENCE, stream_seed
from chipbench.traffic import sweep


def reference_cells(cfg: dict, ref) -> list:
    """Every cell of the grid with its window, at the reference's own
    periods (WithCkptI falls back to NoCkptI where no ``T_P`` fits)."""
    I = cfg["window_s"]
    out = []
    for law, pred, n in C.points(cfg):
        mu = cfg["mu_ind_s"] / n
        T_P = ref.proactive_period(cfg["C_s"], pred["precision"], I)
        for s in cfg["strategies"]:
            T = float(ref.period(s["name"], cfg["C_s"], mu, pred["recall"]))
            cell = C.reference_cell(cfg, law, pred, n, s, T)
            if cell["mode"] == "withckpt" and T_P is None:
                cell["mode"] = "nockpt"
            cell.update(I=I, T_P=T_P)
            out.append(cell)
    return out


class Load(sweep.Load):
    """Back-to-back sweeps of a grid with prediction windows."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core import jax_sim

        if getattr(jax_sim, "WINDOW_TP_SLOTS", 1) < 2:
            raise BenchError(
                "the program keeps one pending true positive per lane and "
                "serves them in fault order: it has no window-ordered buffer"
            )
        super().__init__(cfg, traffic, seed)

    def check(self, ref, seed: int) -> dict:
        sims = ref.simulate(
            reference_cells(self.cfg, ref), compare.REFERENCE_RUNS,
            stream_seed(seed, STREAM_REFERENCE),
        )
        nums, worst = compare.mc_checks(self.sums, sims, self.owed())
        return {"numbers": nums, "worst": worst}


def control_numbers(cfg: dict, traffic: dict, seed: int, n_ctrl: int,
                    ref) -> dict:
    """The control's numbers on one seed: the reference with faults during
    downtime or recovery ignored, in the program's place."""
    cells = reference_cells(cfg, ref)
    runs = ref.simulate(cells, n_ctrl, stream_seed(seed, STREAM_CONTROL),
                        cascade=False)
    sims = ref.simulate(cells, compare.REFERENCE_RUNS,
                        stream_seed(seed, STREAM_REFERENCE))
    nums, worst = compare.mc_checks(
        compare.sums_of(runs), sims, {c["label"]: n_ctrl for c in cells}
    )
    return {"numbers": nums, "worst": worst}
