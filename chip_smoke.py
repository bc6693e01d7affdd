"""Smoke run of the main path on TPU chips, through the entry points a
user calls, at the paper's full Section 5 deployment sizes.

One chip (no arguments), in one process and in this order:

1. device check: the platform must be ``tpu`` (there is no CPU fallback);
2. paper grid: ``paper_grid_cells("full")`` (108 cells, N = 2^14..2^19,
   8-day jobs) through ``run_grid`` in device trace mode, fused dispatch,
   device-reduced stats, 1,000 runs per cell, at the engine's own
   precision with compiled Pallas kernels; no lane exhausted and no Holm
   reject at alpha 0.01 against the closed forms;
3. scenario grid: the two-level and silent-error validation cells (24),
   under the same contract;
4. optimiser: ``optimize(..., method="newton")`` over the full grid
   against the closed-form (``method="analytic"``) optima;
5. campaign: a ``CampaignRunner`` over the bench grid, which must finish
   with no recovery event, no engine degradation, and the stats of a
   ``run_grid`` at the same chunking.

``--chips 4`` runs only the lane-sharded path: the full paper grid on
four devices against one, per-lane results bit-identical, per-cell sums
equal to float summation rounding, and the Holm contract on four.

Times printed are wall times of this smoke run (compile included, and
the compile share alone), not benchmark metrics.  The last line printed
is ``{"ok": true, "device": {...}}``; any failed phase exits non-zero
before it.

    python3 chip_smoke.py [--chips 4] [--out DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
#: the compile stages JAX reports through its monitoring events
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
ALPHA = 0.01
#: Monte-Carlo runs per cell, and the grids' seed
RUNS = 1000
SEED = 0


class SmokeFailure(Exception):
    """A phase's result broke its contract."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Phases:
    """Runs named phases, printing each one's wall and compile seconds."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            self.compile_s += secs

    def run(self, name, fn):
        c0, t0 = self.compile_s, time.monotonic()
        detail = fn()
        wall = time.monotonic() - t0
        print(
            f"phase {name}: ok  smoke wall {wall:.1f} s, of which compile "
            f"{self.compile_s - c0:.1f} s  {detail}",
            flush=True,
        )


def fused_config(**kw):
    from repro.core.engine import EngineConfig

    return EngineConfig(
        engine="jax", trace_mode="device", dispatch="fused", **kw
    )


def grid_phase(cells, n_runs, out, name):
    """One fused stats sweep of ``cells`` held to the validation contract."""
    from repro.core import jax_sim
    from repro.experiments import GridSpec, run_grid
    from repro.experiments.validation import validate_sweep, write_z_table

    grid = GridSpec(tuple(cells), n_runs=n_runs, seed=SEED)
    sweep = run_grid(grid, config=fused_config(collect="stats"))
    resolved = dict(jax_sim.LAST_TIMINGS)
    check(resolved["precision"] == "x32",
          f"{name}: engine resolved precision {resolved['precision']!r}")
    check(resolved["pallas"] == "compiled",
          f"{name}: Pallas kernels ran {resolved['pallas']!r}")
    check(all(c.n_runs == n_runs for c in sweep.cells),
          f"{name}: a cell lost runs")
    n_exh = sum(c.n_exhausted for c in sweep.cells)
    check(n_exh == 0, f"{name}: {n_exh} lane(s) exhausted their traces")
    rows, fails = validate_sweep(sweep, alpha=ALPHA)
    write_z_table(rows, os.path.join(out, f"{name}_ztable.csv"))
    check(not fails, f"{name}: Holm rejects {[r.label for r in fails]}")
    return (
        f"cells {len(rows)}, lanes {grid.n_lanes}, precision x32, "
        f"kernels compiled, exhausted 0, Holm rejects 0 at alpha {ALPHA}"
    )


def optimiser_phase():
    """Batched Newton against the closed-form optima of the full grid."""
    from dataclasses import replace

    from repro.core import optimize
    from repro.experiments.paper_grid import paper_grid_cells
    from repro.experiments.validation import analytic_waste

    cells = paper_grid_cells("full")
    # a window predictor has no exact dates: the grid's Instant cells run
    # the exact-date mode with a window, which optimize() calls "instant"
    family = {"none": "young", "exact": "exact", "migration": "migration",
              "nockpt": "nockpt", "withckpt": "withckpt"}
    names = [
        "instant" if c.strategy.mode == "exact" and c.predictor.window > 0
        else family[c.strategy.mode]
        for c in cells
    ]
    plats = [c.platform for c in cells]
    preds = [c.predictor for c in cells]
    newton = optimize(names, plats, preds, method="newton")
    closed = optimize(names, plats, preds, method="analytic")
    excess = float(np.max(newton.waste - closed.waste))
    check(excess <= 1e-12, f"optimiser: Newton waste exceeds the "
          f"closed-form optimum by {excess:.3e}")
    check(np.array_equal(newton.q, closed.q), "optimiser: q differs")
    rel = np.abs(newton.T_R - closed.T_R) / closed.T_R
    n_flat = 0
    for i in np.flatnonzero(rel > 1e-6):
        # where the closed-form waste is flat in T_R every period is
        # optimal: the period must then score the same waste
        c = cells[i]
        at = [
            analytic_waste(replace(c, strategy=replace(c.strategy, T_R=t)))
            for t in (newton.T_R[i], closed.T_R[i])
        ]
        check(abs(at[0] - at[1]) <= 1e-12,
              f"optimiser: {c.label} period {newton.T_R[i]} != "
              f"{closed.T_R[i]}")
        n_flat += 1
    return (
        f"cells {len(cells)}, max waste excess {excess:.3e}, max period "
        f"rel diff {float(np.max(np.where(rel > 1e-6, 0.0, rel))):.3e}, "
        f"cells with a flat optimum {n_flat}"
    )


def campaign_phase(n_runs, out):
    """A resumable campaign over the bench grid equals ``run_grid``."""
    from repro.core.jax_sim import default_chunk_lanes
    from repro.experiments import GridSpec, run_grid
    from repro.experiments.paper_grid import paper_grid_cells
    from repro.ft import CampaignConfig, CampaignRunner

    grid = GridSpec(tuple(paper_grid_cells("bench")), n_runs=n_runs,
                    seed=SEED)
    chunk = default_chunk_lanes()
    ckpt_dir = os.path.join(out, "campaign")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    cfg = fused_config(collect="stats", chunk_lanes=chunk)
    res = CampaignRunner(grid, CampaignConfig(ckpt_dir=ckpt_dir), cfg).run(
        resume=False
    )
    camp = res.meta["campaign"]
    check(not camp["events"], f"campaign: events {camp['events']}")
    check(not camp["engine_degraded"], "campaign: degraded to the host")
    ref = run_grid(grid, config=cfg)
    n_chunks = -(-grid.n_lanes // chunk)
    # the campaign adds each chunk's float32 sums on the host in float64,
    # run_grid in its float32 device accumulator: the chunk sums are the
    # same bits, the n_chunks - 1 adds round differently
    rtol = 2.0 * n_chunks * 2.0**-24
    for a, b in zip(ref.cells, res.cells):
        check(a.n_runs == b.n_runs and a.n_exhausted == b.n_exhausted == 0,
              f"campaign: {a.cell.label} run counts differ")
        for k in ("mean_waste", "mean_makespan", "mean_faults",
                  "mean_regular_ckpts", "mean_proactive_ckpts"):
            x, y = getattr(a, k), getattr(b, k)
            check(abs(x - y) <= rtol * abs(x),
                  f"campaign: {a.cell.label} {k} {y} != run_grid {x}")
    return (
        f"cells {len(grid.cells)}, lanes {grid.n_lanes}, chunks {n_chunks}, "
        f"snapshots {camp['n_snapshots']}, events 0, degraded False, "
        f"stats equal to run_grid within {rtol:.1e}"
    )


def sharded_phase(n_chips, n_runs, out):
    """The paper grid on ``n_chips`` devices against one."""
    from repro.experiments import GridSpec, run_grid
    from repro.experiments.paper_grid import paper_grid_cells
    from repro.experiments.validation import validate_sweep, write_z_table

    grid = GridSpec(tuple(paper_grid_cells("full")), n_runs=n_runs,
                    seed=SEED)
    lanes = {d: run_grid(grid, config=fused_config(collect="lanes", devices=d))
             for d in (1, n_chips)}
    for a, b in zip(lanes[1].cells, lanes[n_chips].cells):
        for k in ("waste", "makespan", "n_faults", "n_proactive_ckpts",
                  "n_regular_ckpts", "n_migrations"):
            check(np.array_equal(getattr(a, k), getattr(b, k)),
                  f"sharded: {a.cell.label} {k} differs on {n_chips} chips")
    stats = {d: run_grid(grid, config=fused_config(collect="stats", devices=d))
             for d in (1, n_chips)}
    # float32 sums of n positive terms in two orders differ by at most
    # about 2 n ulps of the sum; the counters are exact integers
    rtol = 2.0 * n_runs * 2.0**-24
    worst = 0.0
    for a, b in zip(stats[1].cells, stats[n_chips].cells):
        for k in ("mean_waste", "mean_makespan"):
            x, y = getattr(a, k), getattr(b, k)
            worst = max(worst, abs(x - y) / abs(x))
        for k in ("mean_faults", "mean_proactive_ckpts",
                  "mean_regular_ckpts", "mean_migrations"):
            check(getattr(a, k) == getattr(b, k),
                  f"sharded: {a.cell.label} {k} differs on {n_chips} chips")
    check(worst <= rtol, f"sharded: stats rel diff {worst:.3e} > {rtol:.1e}")
    rows, fails = validate_sweep(stats[n_chips], alpha=ALPHA)
    write_z_table(rows, os.path.join(out, f"paper_{n_chips}chips_ztable.csv"))
    check(not fails, f"sharded: Holm rejects {[r.label for r in fails]}")
    return (
        f"cells {len(rows)}, lanes {grid.n_lanes}, per-lane results "
        f"bit-identical on 1 and {n_chips} chips, stats max rel diff "
        f"{worst:.3e} (bound {rtol:.1e}), Holm rejects 0 on {n_chips} chips"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", default=os.path.join(ROOT, "smoke-out"),
                    help="z-tables and campaign snapshots")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is not here: {e}",
              file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"devices: {dev['platform']} {dev['kind']} x{dev['count']}",
          flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; this smoke run needs one",
              file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1
    from repro.experiments.paper_grid import (
        paper_grid_cells,
        silent_grid_cells,
        two_level_grid_cells,
    )

    os.makedirs(args.out, exist_ok=True)
    phases = Phases()
    try:
        if args.chips > 1:
            phases.run(f"sharded{args.chips}", lambda: sharded_phase(
                args.chips, RUNS, args.out))
        else:
            phases.run("paper_grid", lambda: grid_phase(
                paper_grid_cells("full"), RUNS, args.out, "paper"))
            phases.run("scenario_grid", lambda: grid_phase(
                two_level_grid_cells("validation")
                + silent_grid_cells("validation"),
                RUNS, args.out, "scenario"))
            phases.run("optimiser", optimiser_phase)
            phases.run("campaign", lambda: campaign_phase(RUNS, args.out))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
