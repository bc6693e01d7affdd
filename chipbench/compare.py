"""The comparison that decides ``correct``.

Monte-Carlo answers cannot be replayed draw for draw by the reference
(the chip's Threefry-x32 streams have no host replay), so each cell's
answer is compared in distribution: the program's mean of every
per-run statistic over all its runs in the window, against the plain
reference's mean over its own runs of the same cell,

    z = (mean_program - mean_reference) / (sd_ref * sqrt(1/n_program + 1/n_ref))

with ``sd_ref`` the reference's sample deviation (both sides draw from
the same law when the program is sound).  Statistics that the reference
finds constant in a cell (Young's regular checkpoint count, a count that
is 0) carry no spread to scale by and are left to the other statistics
of the cell.  The numbers compared:

* ``lanes_missing``: runs the program owes and did not report, summed
  over cells (exact: limit 0);
* ``lanes_unfinished``: runs that outlived their fault streams or did
  not converge (exact: limit 0);
* ``max_abs_z``: the largest ``|z|`` over cells and statistics;
* ``policy_rel_err`` (queries): the largest relative gap between the
  program's optimal waste, or the reference's waste at the program's
  period, and the reference's optimum.
"""

from __future__ import annotations

import math

import numpy as np

#: per-run statistics compared, in the reference's names
STATS = ("waste", "makespan", "faults", "regular", "proactive", "migrations")

#: runs of each cell the plain reference simulates for the comparison
REFERENCE_RUNS = 500

#: limits, each set between the readings of sound runs (lower) and of the
#: control (upper); see PERF.md for the readings they come from
LIMITS = {
    "lanes_missing": 0,
    "lanes_unfinished": 0,
    "max_abs_z": 10.0,
    "policy_rel_err": 1e-9,
}


class Sums:
    """Per-cell sums of a program's answers, pooled over the window."""

    def __init__(self):
        self.n = {}
        self.sums = {}
        self.unfinished = 0

    def add(self, label: str, n: float, means: dict, exhausted: int = 0):
        self.n[label] = self.n.get(label, 0.0) + n
        s = self.sums.setdefault(label, dict.fromkeys(STATS, 0.0))
        for k in STATS:
            s[k] += n * float(means[k])
        self.unfinished += int(exhausted)

    def add_sweep(self, sweep) -> None:
        """Pool one ``run_grid`` result (stats-backed cell rows)."""
        for c in sweep.cells:
            self.add(c.cell.label, c.n_runs, {
                "waste": c.mean_waste,
                "makespan": c.mean_makespan,
                "faults": c.mean_faults,
                "regular": c.mean_regular_ckpts,
                "proactive": c.mean_proactive_ckpts,
                "migrations": c.mean_migrations,
            }, c.n_exhausted)

    def mean(self, label: str, k: str) -> float:
        return self.sums[label][k] / self.n[label]


def sums_of(runs: dict) -> Sums:
    """Pool the reference's per-run statistics as a program's answers."""
    out = Sums()
    for label, r in runs.items():
        out.add(label, len(r["waste"]), {k: np.mean(r[k]) for k in STATS},
                r["exhausted"])
    return out


def z_scores(prog: Sums, ref: dict):
    """``{(label, stat): z}`` for every statistic with reference spread."""
    out = {}
    for label, r in ref.items():
        if label not in prog.n or prog.n[label] <= 0:
            continue
        n_p = prog.n[label]
        for k in STATS:
            x = np.asarray(r[k], np.float64)
            sd = float(x.std(ddof=1)) if x.size > 1 else 0.0
            if sd <= 0.0:
                continue
            se = sd * math.sqrt(1.0 / n_p + 1.0 / x.size)
            out[(label, k)] = (prog.mean(label, k) - float(x.mean())) / se
    return out


def mc_checks(prog: Sums, ref: dict, owed: dict) -> tuple:
    """The Monte-Carlo numbers and the worst ``(label, stat, z)``.

    ``owed`` maps each label to the runs the program should have made."""
    missing = sum(abs(owed[k] - prog.n.get(k, 0.0)) for k in owed)
    z = z_scores(prog, ref)
    worst = max(z.items(), key=lambda kv: abs(kv[1]), default=(("-", "-"), 0.0))
    nums = {
        "lanes_missing": float(missing),
        "lanes_unfinished": float(prog.unfinished),
        "max_abs_z": float(abs(worst[1])) if z else math.inf,
    }
    return nums, (worst[0][0], worst[0][1], float(worst[1]))


def policy_rel_err(program: list, reference: list, waste_at) -> float:
    """Largest relative gap of the program's optima from the reference's.

    ``program`` and ``reference`` hold one ``(T, q, waste)`` per answer;
    ``waste_at(i, T, q)`` is the reference's waste of answer ``i`` at the
    program's period and trust."""
    worst = 0.0
    for i, ((T, q, w), (_, _, w_ref)) in enumerate(zip(program, reference)):
        for v in (w, waste_at(i, T, q)):
            worst = max(worst, abs(float(v) - w_ref) / abs(w_ref))
    return worst


def checks(numbers: dict) -> dict:
    """Each number beside its limit; ``correct`` is every one within."""
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}


def passed(checked: dict) -> bool:
    return all(
        c["value"] <= c["limit"] and not math.isnan(c["value"])
        for c in checked.values()
    )
