"""Lanes-throughput curve: JAX device engine vs the NumPy batch engine,
the host-vs-device *trace-mode* comparison, the multi-device scaling
curve of the sharded dispatch, and the fused-vs-per-cell paper-grid
sweep comparison.

One representative paper cell (Instant strategy, exponential faults,
accurate predictor) swept over lane counts; both engines consume the same
generated ``BatchTraces``, so the per-lane results must agree while the
wall-clock diverges.  The JAX engine is warmed up first (its jit compile
is a one-off, amortized across every later call at the same chunk shape)
and timed in steady state — the number a long Monte-Carlo campaign sees.

``jax_engine/device_trace_lanes{n}`` times the same cell end-to-end in
device trace mode (``TraceSpec``: counter-based RNG streams sampled
inside the engine, O(1) cursor state per lane) against the host path
(host NumPy generation + event-array engine), with the
generation/packing/dispatch(=compute)/fetch split of both.  The 40960-lane record
carries the acceptance number ``speedup_end_to_end`` (device-mode
campaign throughput vs the host-trace JAX path) plus the waste-mean
z-score against the NumPy engine.

The devices curve (``jax_engine/devices{d}_lanes{n}``) times the sharded
engine on 1/2/4/8 devices at a >= 10k lane count, in this process, over
the devices it has (a host with one chip records only ``devices1``;
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives a CPU run
eight host devices).  One process holds the chips: a child started
after this process touched JAX could not reach them.

``jax_engine/fused_grid_cells{n}`` is the experiment-sweep acceptance
record: the paper grid (``repro.experiments.paper_grid``, every platform
size x both predictors x all six strategies, device trace mode) run as
one fused cell-multiplexed dispatch (``run_grid(dispatch="fused")`` —
per-cell parameter tables broadcast on device by the lane -> cell index,
one compiled executable for the whole exponential family) vs one engine
call per cell (``dispatch="percell"``) at equal lanes per cell.  The
record carries ``speedup_fused_vs_percell`` (acceptance: >= 3x),
``fused_cells_per_s`` (the regression-gate floor), the device-reduced
``collect="stats"`` timing, and the fused-vs-percell per-cell equality
check (must be 0.0 — both paths consume identical counter streams).

``jax_engine/mixed_law_grid_cells{n}`` is the mixed-law one-dispatch
acceptance record: the paper grid replicated under three failure-law
families (exponential, Weibull k=0.7, lognormal sigma=0.5) and run as
literally ONE law-multiplexed device dispatch (per-cell ``law_index`` +
unified parameter tables, branchless law-indexed sampler) vs the
per-family baseline (one dispatch per law through the *same* indexed
sampler).  The record carries ``mixed_law_cells_per_s`` (the
regression-gate floor), ``speedup_vs_perfamily``, the engine-executable
build counts of both paths, and ``fused_vs_perfamily_max_diff`` (must
be 0.0 — identical sampler, identical counter streams).  On a
compute-bound CPU the two paths are near parity (total lane-steps are
equal, and the fused hot loop runs to the slowest family's iteration
count); the one-dispatch win is the 3x dispatch/fetch amortization and
the single executable, which pays off in dispatch-bound regimes — real
accelerators, many-family grids, and distributed meshes.

``jax_engine/analytic_opt_cells{n}`` is the analytic-layer acceptance
record: every bench-grid cell's optimal regular period solved in one
jitted batched safeguarded-Newton dispatch (``jax.grad`` of the
branchless waste twins over the shared per-cell tables) vs a host
scalar scan of the same analytic objective over the
``best_period_search`` period grid.  The record carries
``analytic_opt_cells_per_s`` (the regression-gate floor),
``speedup_vs_host_scan``, ``newton_excess_waste_max`` (gate: the
continuous optimum must dominate the 10-point scan on every cell, to
float rounding) and ``newton_vs_extremizer_max_rel`` (smooth-family
periods must land on the closed-form extremizer).

``jax_engine/two_level_silent_cells{n}`` is the scenario-family
acceptance record: the two-level (memory + disk tiers, rho-stride
nesting, Bernoulli(f) tier recovery) and silent-error (verified
checkpoints every k_V-th period, detection-latency rollback) grids
through the fused device engine with ``collect="stats"``.  It carries
``two_level_silent_cells_per_s`` (the regression-gate perf floor),
``fused_vs_percell_max_diff`` (0.0 — identical counter streams, tier
coins and strike cursors included) and ``newton_excess_waste_max`` (the
analytic-dominance gate over the corrected two-level/silent waste
models).

Acceptance trajectory: jax lanes/s >= numpy lanes/s at 10k lanes on CPU,
device trace mode >= 2x the host-trace path end-to-end at 40960 lanes,
and sharded lanes/s non-decreasing with device count (expected >> on an
accelerator, where the Pallas hot step compiles to a real Mosaic kernel
instead of interpret mode and every device is a physical chip).

    PYTHONPATH=src python -m benchmarks.jax_engine [--full] [--devices all]
    PYTHONPATH=src python -m benchmarks.run --only jax_engine
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import (
    EngineConfig,
    Platform,
    PredictorModel,
    make_event_traces_batch,
    simulate_batch,
)
from repro.core import jax_sim
from repro.core import simulator as S
from repro.core.events import lognormal, make_trace_spec, weibull
from repro.core.jax_sim import simulate_batch_jax

from .common import emit

MN = 60.0
WORK = 10 * 86400.0
LANES_QUICK = [1024, 4096, 10240]
LANES_FULL = [1024, 4096, 10240, 32768, 102400]

#: sharded-dispatch scaling curve: forced host device counts x lane count
DEVICES_CURVE = (1, 2, 4, 8)
DEVICES_LANES = 40960

#: lane count of the trace-mode acceptance comparison
TRACE_MODE_LANES = 40960

#: lanes per cell of the fused-grid sweep comparison (equal for both
#: dispatch granularities — the acceptance condition)
FUSED_GRID_RUNS = 16

#: lanes per chunk of the campaign-overhead record: small enough that
#: the bench grid spans several chunks (= several snapshots at period 0,
#: the worst-case durability cost); the plain sweep is chunked the same
CAMPAIGN_CHUNK = 256

#: failure laws of the mixed-law one-dispatch sweep — one family each of
#: the memoryless / aging / heavy-tail classes (None = the preset's
#: exponential default)
MIXED_LAWS = (
    ("exp", None),
    ("weibull", weibull(0.7)),
    ("lognormal", lognormal(0.5)),
)


#: engine configurations of the grid-sweep records (one fused device
#: dispatch is the headline path; the rest are its baselines)
_CFG_FUSED = EngineConfig(engine="jax", trace_mode="device")
_CFG_STATS = _CFG_FUSED.replace(collect="stats")
_CFG_PERCELL = _CFG_FUSED.replace(dispatch="percell")
_CFG_PERFAMILY = _CFG_STATS.replace(dispatch="perfamily")


def _cell():
    plat = Platform(mu=1000 * MN, C=10 * MN, D=1 * MN, R=10 * MN)
    pred = PredictorModel(0.85, 0.82, window=300.0, lead=3600.0)
    return plat, pred, S.instant(plat, pred)


def _traces(n: int, plat: Platform, pred: PredictorModel, seed: int = 7):
    rng = np.random.default_rng(seed)
    return make_event_traces_batch(
        rng, n, horizon=12 * WORK, mtbf=plat.mu,
        recall=pred.recall, precision=pred.precision,
        window=pred.window, lead=pred.lead,
    )


def _spec(n: int, plat: Platform, pred: PredictorModel, seed: int = 7):
    return make_trace_spec(
        n, horizon=12 * WORK, mtbf=plat.mu,
        recall=pred.recall, precision=pred.precision,
        window=pred.window, lead=pred.lead, seed=seed,
    )


def _split():
    # dispatch_s is the device-compute leg on CPU (execution blocks the
    # dispatch); on accelerators compute hides under dispatch + fetch
    t = jax_sim.LAST_TIMINGS
    return {
        "pack_s": round(t.get("pack_s", 0.0), 3),
        "dispatch_s": round(t.get("dispatch_s", 0.0), 3),
        "fetch_s": round(t.get("fetch_s", 0.0), 3),
    }


def run(quick: bool = True, devices=None) -> None:
    plat, pred, strat = _cell()
    reps = 3 if quick else 5
    for n in (LANES_QUICK if quick else LANES_FULL) + [TRACE_MODE_LANES]:
        t0 = time.monotonic()
        traces = _traces(n, plat, pred)
        gen_s = time.monotonic() - t0
        spec = _spec(n, plat, pred)

        res_np = simulate_batch(WORK, plat, strat, traces)
        res_jx = simulate_batch_jax(  # jit warmup
            WORK, plat, strat, traces, devices=devices
        )
        res_dev = simulate_batch_jax(  # device-generation warmup
            WORK, plat, strat, spec, devices=devices
        )

        # interleaved best-of-N: all engines see the same machine noise;
        # the pack/fetch split is captured from the winning rep so it
        # decomposes the reported time
        np_s = jx_s = dv_s = float("inf")
        jx_split = dv_split = {}
        for _ in range(reps):
            np_s = min(
                np_s,
                _timed(lambda traces=traces: simulate_batch(
                    WORK, plat, strat, traces
                )),
            )
            t = _timed(lambda traces=traces: simulate_batch_jax(
                WORK, plat, strat, traces, devices=devices
            ))
            if t < jx_s:
                jx_s, jx_split = t, _split()
            t = _timed(lambda spec=spec: simulate_batch_jax(
                WORK, plat, strat, spec, devices=devices
            ))
            if t < dv_s:
                dv_s, dv_split = t, _split()

        agree = float(np.abs(res_jx.waste - res_np.waste).max())
        emit(
            f"jax_engine/lanes{n}",
            jx_s * 1e6 / n,
            {
                "numpy_s": round(np_s, 3),
                "jax_s": round(jx_s, 3),
                "gen_s": round(gen_s, 3),
                **jx_split,
                "numpy_lanes_per_s": round(n / np_s, 1),
                "jax_lanes_per_s": round(n / jx_s, 1),
                "speedup_vs_numpy": round(np_s / jx_s, 2),
                "max_abs_waste_diff": agree,
            },
        )
        # device trace mode: generation happens inside the engine, so the
        # end-to-end comparison charges the host path its generation time
        mw_np = float(res_np.waste.mean())
        mw_dev = float(res_dev.waste.mean())
        se = float(res_np.waste.std(ddof=1)) / np.sqrt(n)
        emit(
            f"jax_engine/device_trace_lanes{n}",
            dv_s * 1e6 / n,
            {
                "jax_dev_s": round(dv_s, 3),
                **dv_split,
                "jax_dev_lanes_per_s": round(n / dv_s, 1),
                "host_end_to_end_s": round(gen_s + jx_s, 3),
                "speedup_end_to_end": round((gen_s + jx_s) / dv_s, 2),
                "mean_waste_numpy": round(mw_np, 6),
                "mean_waste_device": round(mw_dev, 6),
                # independent samples of the same law: |z| <~ 2-3
                "waste_z_vs_numpy": round(
                    (mw_dev - mw_np) / (se * np.sqrt(2.0)), 2
                ),
            },
        )
    _run_fused_grid(reps=reps)
    _run_campaign_grid(reps=reps)
    _run_mixed_law_grid(reps=reps)
    _run_analytic_opt(reps=reps)
    _run_two_level_silent(reps=reps)
    _run_devices_curve(reps=reps)


def _run_fused_grid(reps: int = 3) -> None:
    """Time the paper grid end-to-end: fused cell-multiplexed dispatch
    (lanes + device-reduced stats collection) vs per-cell dispatch."""
    from repro.experiments import GridSpec, paper_grid_cells, run_grid

    cells = paper_grid_cells("bench")
    grid = GridSpec(tuple(cells), n_runs=FUSED_GRID_RUNS, seed=3)
    n_cells = len(cells)

    # warm the fused executable at the *full* cell-table shape (the
    # table length is a static of the compiled program) and the percell
    # executables on a 4-cell subgrid that covers both the plain and the
    # migration-specialized variants — per-cell chunk shapes are
    # cell-count independent, so the subgrid warms them all
    sweep_f = run_grid(grid, _CFG_FUSED)
    sub = GridSpec(tuple(cells[:4]), n_runs=FUSED_GRID_RUNS, seed=3)
    assert any(c.strategy.mode == "migration" for c in sub.cells)
    run_grid(sub, _CFG_PERCELL)

    fused_s = stats_s = percell_s = float("inf")
    fused_split = {}
    for _ in range(reps):
        t = _timed(lambda: run_grid(grid, _CFG_FUSED))
        if t < fused_s:
            fused_s, fused_split = t, _split()
        stats_s = min(stats_s, _timed(lambda: run_grid(grid, _CFG_STATS)))
    for _ in range(max(1, reps - 1)):  # the slow leg: fewer reps
        t0 = time.monotonic()
        sweep_p = run_grid(grid, _CFG_PERCELL)
        percell_s = min(percell_s, time.monotonic() - t0)

    # both dispatches consume identical counter streams: exact equality
    diff = max(
        abs(a.mean_waste - b.mean_waste)
        for a, b in zip(sweep_f.cells, sweep_p.cells)
    )
    emit(
        f"jax_engine/fused_grid_cells{n_cells}",
        fused_s * 1e6 / n_cells,
        {
            "n_cells": n_cells,
            "lanes_per_cell": FUSED_GRID_RUNS,
            "n_lanes": grid.n_lanes,
            "fused_s": round(fused_s, 3),
            "fused_stats_s": round(stats_s, 3),
            "percell_s": round(percell_s, 3),
            "speedup_fused_vs_percell": round(percell_s / fused_s, 2),
            "speedup_stats_vs_percell": round(percell_s / stats_s, 2),
            "fused_cells_per_s": round(n_cells / fused_s, 1),
            "fused_lanes_per_s": round(grid.n_lanes / fused_s, 1),
            "fused_vs_percell_max_diff": diff,
            **fused_split,
        },
    )


def _run_campaign_grid(reps: int = 3) -> None:
    """Time the resumable campaign runner (``repro.ft.run_campaign``)
    against the plain fused sweep at the *same* chunking: the price of
    durability — chunk-boundary CellSums snapshots through
    CheckpointStore at the production default period (chosen online by
    ``repro.core.optimize`` from the measured snapshot cost and the
    configured MTBF) — expressed as ``campaign_overhead_frac``.
    check_regression gates it at <= 5%: resilience must stay
    effectively free."""
    import shutil
    import tempfile

    from repro.experiments import GridSpec, paper_grid_cells, run_grid
    from repro.ft import CampaignConfig, run_campaign

    cells = paper_grid_cells("bench")
    grid = GridSpec(tuple(cells), n_runs=FUSED_GRID_RUNS, seed=3)
    n_cells = len(cells)
    cfg = _CFG_STATS.replace(chunk_lanes=CAMPAIGN_CHUNK)

    run_grid(grid, cfg)  # warm the chunk-shape executable
    plain_s = camp_s = float("inf")
    n_snapshots = 0
    for _ in range(reps):
        plain_s = min(plain_s, _timed(lambda: run_grid(grid, cfg)))
        root = tempfile.mkdtemp(prefix="bench_campaign_")
        try:
            t0 = time.monotonic()
            res = run_campaign(
                grid, CampaignConfig(ckpt_dir=root), cfg
            )
            t = time.monotonic() - t0
        finally:
            shutil.rmtree(root, ignore_errors=True)
        if t < camp_s:
            camp_s = t
            n_snapshots = res.meta["campaign"]["n_snapshots"]
    overhead = camp_s / plain_s - 1.0
    emit(
        f"jax_engine/campaign_grid_cells{n_cells}",
        camp_s * 1e6 / n_cells,
        {
            "n_cells": n_cells,
            "n_lanes": grid.n_lanes,
            "chunk_lanes": CAMPAIGN_CHUNK,
            "n_snapshots": n_snapshots,
            "plain_s": round(plain_s, 3),
            "campaign_s": round(camp_s, 3),
            "campaign_overhead_frac": round(max(overhead, 0.0), 4),
        },
    )


def _run_mixed_law_grid(reps: int = 3) -> None:
    """Time the mixed-law paper grid: one law-multiplexed device
    dispatch over the concatenated per-law grids vs the per-family
    baseline (one dispatch per failure-law family, same law-indexed
    sampler — the equality reference).  On CPU expect ~parity end to
    end (compute-bound; see the module docstring) with bit-exact
    per-cell stats and a single engine-executable build."""
    from dataclasses import replace

    from repro.experiments import GridSpec, paper_grid_cells, run_grid

    cells = [
        replace(c, label=f"{law}/{c.label}", fault_dist=dist)
        for law, dist in MIXED_LAWS
        for c in paper_grid_cells("bench")
    ]
    grid = GridSpec(tuple(cells), n_runs=FUSED_GRID_RUNS, seed=5)
    n_cells = len(cells)

    # warm both executables and capture the engine-executable build
    # counts: the fused path compiles ONE program for the whole 3-law
    # grid; the per-family baseline compiles one per *shape*, reused
    # across its (equal-sized) family dispatches
    n0 = len(jax_sim._RUN_CACHE)
    sweep_f = run_grid(grid, _CFG_STATS)
    fused_builds = len(jax_sim._RUN_CACHE) - n0
    assert jax_sim.LAST_TIMINGS["n_chunks"] == 1, (
        "mixed-law grid must run as one fused dispatch"
    )
    n0 = len(jax_sim._RUN_CACHE)
    sweep_p = run_grid(grid, _CFG_PERFAMILY)
    perfamily_builds = len(jax_sim._RUN_CACHE) - n0

    fused_s = perfam_s = float("inf")
    fused_split = {}
    for _ in range(reps):
        t = _timed(lambda: run_grid(grid, _CFG_STATS))
        if t < fused_s:
            fused_s, fused_split = t, _split()
        perfam_s = min(perfam_s, _timed(lambda: run_grid(grid, _CFG_PERFAMILY)))

    # both granularities run the same law-indexed sampler on the same
    # counter streams: per-cell device-reduced stats are bit-identical
    diff = max(
        abs(a.mean_waste - b.mean_waste)
        for a, b in zip(sweep_f.cells, sweep_p.cells)
    )
    emit(
        f"jax_engine/mixed_law_grid_cells{n_cells}",
        fused_s * 1e6 / n_cells,
        {
            "n_cells": n_cells,
            "n_laws": len(MIXED_LAWS),
            "lanes_per_cell": FUSED_GRID_RUNS,
            "n_lanes": grid.n_lanes,
            "fused_s": round(fused_s, 3),
            "perfamily_s": round(perfam_s, 3),
            "speedup_vs_perfamily": round(perfam_s / fused_s, 2),
            "mixed_law_cells_per_s": round(n_cells / fused_s, 1),
            "fused_engine_builds": fused_builds,
            "perfamily_engine_builds": perfamily_builds,
            "perfamily_dispatches": len(MIXED_LAWS),
            "fused_vs_perfamily_max_diff": diff,
            **fused_split,
        },
    )


def _run_analytic_opt(reps: int = 3) -> None:
    """Time the batched-Newton period optimizer: every bench-grid cell's
    optimal regular period solved in ONE jitted device dispatch
    (``repro.core.analytic.newton_optimize_tables`` — per-cell
    safeguarded Newton through ``jax.grad`` of the branchless waste
    twins) against the host baseline (a scalar Python scan of the
    analytic objective over ``best_period_search``'s period grid,
    argmin per cell — the pre-redesign way to pick a period without a
    Monte-Carlo campaign).

    Acceptance is dominance, not agreement: the Newton period's waste
    must be <= the scan's best on EVERY cell up to float rounding
    (``newton_excess_waste_max`` — the continuous optimum can only
    undercut a 10-point grid), and on the smooth strategy families the
    period itself must land on the closed-form extremizer
    (``newton_vs_extremizer_max_rel``)."""
    from dataclasses import replace

    from repro.core import analytic as A
    from repro.core.simulator import PERIOD_GRID
    from repro.experiments import paper_grid_cells
    from repro.experiments.validation import analytic_waste

    cells = paper_grid_cells("bench")
    n_cells = len(cells)
    tabs = A.tables_from_cells(cells)
    res = A.newton_optimize_tables(tabs)  # jit warmup

    newton_s = float("inf")
    for _ in range(reps):
        newton_s = min(
            newton_s, _timed(lambda: A.newton_optimize_tables(tabs))
        )

    # host scan baseline: the analytic objective at best_period_search's
    # period candidates, one scalar evaluation at a time
    t0 = time.monotonic()
    scan_w = np.empty(n_cells)
    for i, c in enumerate(cells):
        periods = [
            max(c.platform.C * 1.01, c.strategy.T_R * m) for m in PERIOD_GRID
        ]
        scan_w[i] = min(
            analytic_waste(replace(c, strategy=replace(c.strategy, T_R=t)))
            for t in periods
        )
    scan_s = time.monotonic() - t0
    scan_w = np.minimum(scan_w, 1.0)

    # dominance: the one-dispatch Newton periods must be at least as
    # good as the host grid scan on every cell
    excess = float((res["waste"] - scan_w).max())

    # period agreement on the smooth families (everything except the
    # Instant kink cells), against the closed-form extremizer the host
    # optimizers use
    t_ext = A.analytic_period_cells(cells)
    smooth = np.array(
        [
            not (c.strategy.mode == "exact" and c.predictor.window > 0.0)
            for c in cells
        ]
    ) & (res["q"] > 0.0)
    rel = np.abs(res["T_R"] - t_ext) / t_ext
    agree = float(rel[smooth].max()) if smooth.any() else 0.0

    emit(
        f"jax_engine/analytic_opt_cells{n_cells}",
        newton_s * 1e6 / n_cells,
        {
            "n_cells": n_cells,
            "newton_s": round(newton_s, 4),
            "host_scan_s": round(scan_s, 4),
            "analytic_opt_cells_per_s": round(n_cells / newton_s, 1),
            "speedup_vs_host_scan": round(scan_s / newton_s, 2),
            "newton_excess_waste_max": excess,
            "newton_vs_extremizer_max_rel": agree,
        },
    )


def _run_two_level_silent(reps: int = 3) -> None:
    """Scenario-grid acceptance record: the two-level + silent phase
    families through the SAME one-dispatch fused device engine with
    device-reduced statistics.

    Carries ``two_level_silent_cells_per_s`` (the regression-gate perf
    floor for the scenario families), ``fused_vs_percell_max_diff``
    (must be 0.0 — fused and per-cell dispatch consume identical counter
    streams, including the per-fault tier coins and silent strike
    cursors), and ``newton_excess_waste_max`` (the analytic-dominance
    gate: the batched-Newton optimum of the corrected two-level / silent
    waste models must dominate a host scan of the same objective on
    every cell — the gate that would have caught the old
    (1-rq)-scaled-disk-term extremizers, which a scan undercuts)."""
    from dataclasses import replace

    from repro.core import analytic as A
    from repro.core.simulator import PERIOD_GRID
    from repro.experiments import GridSpec, run_grid
    from repro.experiments.paper_grid import (
        silent_grid_cells,
        two_level_grid_cells,
    )
    from repro.experiments.validation import analytic_waste

    cells = tuple(two_level_grid_cells("bench")) + tuple(
        silent_grid_cells("bench")
    )
    n_cells = len(cells)
    grid = GridSpec(cells, n_runs=FUSED_GRID_RUNS, seed=9)
    sweep_f = run_grid(grid, _CFG_STATS)  # jit warmup
    sweep_p = run_grid(grid, _CFG_PERCELL)

    stats_s = float("inf")
    stats_split = {}
    for _ in range(reps):
        t = _timed(lambda: run_grid(grid, _CFG_STATS))
        if t < stats_s:
            stats_s, stats_split = t, _split()

    diff = max(
        abs(a.mean_waste - b.mean_waste)
        for a, b in zip(sweep_f.cells, sweep_p.cells)
    )

    # analytic dominance: one batched-Newton dispatch over the scenario
    # cell tables vs a host scan of the same corrected waste objective
    tabs = A.tables_from_cells(cells)
    res = A.newton_optimize_tables(tabs)
    scan_w = np.empty(n_cells)
    for i, c in enumerate(cells):
        periods = [
            max(c.platform.C * 1.01, c.strategy.T_R * m) for m in PERIOD_GRID
        ]
        scan_w[i] = min(
            analytic_waste(replace(c, strategy=replace(c.strategy, T_R=t)))
            for t in periods
        )
    scan_w = np.minimum(scan_w, 1.0)
    excess = float((res["waste"] - scan_w).max())

    emit(
        f"jax_engine/two_level_silent_cells{n_cells}",
        stats_s * 1e6 / n_cells,
        {
            "n_cells": n_cells,
            "lanes_per_cell": FUSED_GRID_RUNS,
            "n_lanes": grid.n_lanes,
            "fused_stats_s": round(stats_s, 3),
            "two_level_silent_cells_per_s": round(n_cells / stats_s, 1),
            "fused_vs_percell_max_diff": diff,
            "newton_excess_waste_max": excess,
            **stats_split,
        },
    )


def _run_devices_curve(reps: int = 3) -> None:
    """Sharded-dispatch scaling over the devices this process has."""
    import statistics

    import jax

    plat, pred, strat = _cell()
    n = DEVICES_LANES
    traces = _traces(n, plat, pred)
    counts = [d for d in DEVICES_CURVE if d <= len(jax.devices())]
    base = None
    times = {d: [] for d in counts}
    for d in counts:  # compile every specialization up front
        simulate_batch_jax(WORK, plat, strat, traces, devices=d)
    # interleaved, median-of-N: the scaling ratios survive noisy shared
    # runners far better than best-of (all device counts see every phase
    # of the machine noise)
    for _ in range(max(reps, 5)):
        for d in counts:
            times[d].append(_timed(lambda d=d: simulate_batch_jax(
                WORK, plat, strat, traces, devices=d
            )))
    for d in counts:
        s = statistics.median(times[d])
        base = base or s
        emit(
            f"jax_engine/devices{d}_lanes{n}",
            round(s * 1e6 / n, 1),
            {
                "jax_s": round(s, 3),
                "jax_lanes_per_s": round(n / s, 1),
                "speedup_vs_1dev": round(base / s, 2),
                "n_devices": d,
            },
        )


def _timed(fn) -> float:
    t0 = time.monotonic()
    fn()
    return time.monotonic() - t0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument(
        "--devices", default=None,
        help="shard the timed engine calls ('all', an int, default: one)",
    )
    args = ap.parse_args()
    devices = args.devices
    if devices and devices != "all":
        devices = int(devices)
    run(quick=not args.full, devices=devices)
