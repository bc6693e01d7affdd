"""Batched execution of experiment grids.

The runner flattens a :class:`~repro.experiments.grid.GridSpec` into engine
lanes — one lane per (cell, run) pair — and advances the *entire grid* in a
handful of vectorized engine calls:

1. cells are grouped by trace-generation compatibility (failure-law family,
   superposition settings), and within a group cells with identical trace
   parameters (MTBF, predictor, window, horizon) *share* their traces — the
   paper's paired design, where every strategy faces the same failures;
2. each group's unique traces are generated in one batched pass
   (:func:`repro.core.events.make_event_traces_batch`);
3. the groups are concatenated and every lane advances simultaneously in
   one :func:`repro.core.batch_sim.simulate_batch` call.

``engine="jax"`` advances the very same lanes with the device-resident
engine (:mod:`repro.core.jax_sim`): jit + ``lax.while_loop`` over a stacked
lane-state pytree, Pallas hot step, host-side chunked lane scheduling
(``chunk_lanes``) so 100k-lane grids never exceed device memory, and
optional lane sharding across a device set (``devices=`` / ``mesh=``) with
device-count-invariant results.
``engine="scalar"`` feeds each lane's :class:`EventTrace` view to the scalar
reference engine instead: identical traces, Python event loop — the oracle
for equivalence checks.  ``engine="legacy"`` reproduces the pre-batching
pipeline exactly (per-run Python-object trace generation via
:func:`make_event_trace` + scalar engine, per-run seeds ``seed + 1000 i +
17``) — the wall-clock baseline the vectorized path is measured against.

Fused vs per-cell dispatch
==========================

``dispatch="fused"`` (the default for the batched engines) makes the
experiment cell a *lane-level axis* of the engine: strategy, period,
checkpoint costs, predictor parameters and trust ship as per-cell tables
broadcast on device through an int32 per-lane cell index
(``simulate_batch_jax(cell_index=...)``), so one device dispatch runs the
entire grid with lanes from many cells interleaved across chunks and
shards.  In device trace mode the failure law is part of those tables
too: a grid mixing exponential / Weibull / lognormal families
concatenates its per-family specs (:meth:`TraceSpec.concat_cells`) and
runs as literally ONE dispatch through the law-indexed sampler — one
compiled executable per grid *shape*, not per family.  (A single-family
grid keeps the law-specialized sampler: same results, slightly cheaper
draws.)

``dispatch="perfamily"`` (jax engine, device trace mode) is the
pre-fusion baseline the mixed-law benchmark is measured against: one
engine call per trace-compatibility group, paying k executables, k host
round-trips and k pipeline drains on a k-family grid.  Its specs are
tuple-ized (:meth:`TraceSpec.indexed`) so both dispatch granularities
run the *same* law-indexed sampler — per-lane results (and device-
reduced stats) are bit-identical to the one-dispatch path by
construction, which is what the benchmark equality gate asserts.

``dispatch="percell"`` launches one engine call per cell instead (the
original pre-fusion baseline, and a differential-validation path: paired
per-lane RNG streams make both dispatches bit-identical in device trace
mode for single-family grids and for the deterministic trust settings
``q in {0, 1}`` in host mode; fractional-``q`` host-mode trust coins are
drawn per engine call and agree only in distribution; on *mixed*-law
grids percell runs law-specialized samplers whose lognormal draws can
differ from the fused path's law-indexed transform by XLA
fusion-context rounding, ~1e-12 relative).

``collect="stats"`` (jax engine) segment-reduces each cell's waste /
makespan / event-counter moments *on device* and fetches O(cells) sums
instead of O(lanes) per-run arrays; the resulting
:class:`~repro.experiments.grid.CellResult` rows carry identical summary
statistics (to float rounding) without the raw samples.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.batch_sim import simulate_batch
from ..core.engine import UNSET, resolve_engine_config
from ..core.events import (
    BatchTraces,
    TraceSpec,
    make_event_trace,
    make_event_traces_batch,
    make_trace_spec,
)
from ..core.simulator import simulate
from ..core.spans import span
from .grid import CellResult, ExperimentCell, GridSpec, SweepResult

__all__ = ["run_grid", "run_cells", "FusedLayout", "build_fused_layout"]


def _group_cells(grid: GridSpec) -> List[Tuple[Tuple, List[int]]]:
    groups: Dict[Tuple, List[int]] = {}
    for ci, cell in enumerate(grid.cells):
        groups.setdefault(cell.group_key(), []).append(ci)
    return list(groups.items())


def _trace_key(cell: ExperimentCell) -> Tuple:
    """Cells with equal keys face identical traces (paired comparison).

    Keyed on the predictor's true parameters — not the strategy — so a
    mode-"none" baseline (Young/Daly) shares its fault stream with the
    prediction-following strategies it is compared against; the engine's
    trust filter hides the predictions from it."""
    return (
        cell.work,
        cell.horizon_factor,
        cell.platform.mu,
        cell.predictor.recall,
        cell.predictor.precision,
        cell.predictor.window,
        cell.predictor.lead,
    )


def _trace_slots(grid: GridSpec, cell_idx: List[int]):
    """Shared-trace layout of one group: cells mapping to the same
    :func:`_trace_key` share one *slot* of unique traces.  A slot is as
    wide as its widest cell (per-cell ``n_runs`` heterogeneity): every
    cell consumes the slot's first ``n_runs`` lanes, so pairing holds on
    the common prefix.  Returns ``(uniq_cells, cell_slot, slot_runs,
    slot_off, rows)`` where ``rows[lane]`` indexes the unique-lane pool.
    """
    cells = [grid.cells[ci] for ci in cell_idx]
    runs = [grid.cell_runs(ci) for ci in cell_idx]
    uniq: Dict[Tuple, int] = {}
    cell_slot = [uniq.setdefault(_trace_key(c), len(uniq)) for c in cells]
    uniq_cells: List[Optional[ExperimentCell]] = [None] * len(uniq)
    slot_runs = np.zeros(len(uniq), dtype=np.int64)
    for c, slot, r in zip(cells, cell_slot, runs):
        if uniq_cells[slot] is None:
            uniq_cells[slot] = c
        slot_runs[slot] = max(slot_runs[slot], r)
    slot_off = np.concatenate([[0], np.cumsum(slot_runs)])
    rows = (
        np.concatenate(
            [
                slot_off[slot] + np.arange(r)
                for slot, r in zip(cell_slot, runs)
            ]
        )
        if cells
        else np.zeros(0, dtype=np.int64)
    )
    return uniq_cells, cell_slot, slot_runs, slot_off, rows


def _group_traces(grid: GridSpec, cell_idx: List[int], group_no: int) -> BatchTraces:
    """Generate one group's traces: one batched pass over the group's
    *unique* trace parameters, then row-expansion to per-cell lanes."""
    uniq_cells, _, slot_runs, slot_off, rows = _trace_slots(grid, cell_idx)
    rep = lambda vals: np.repeat(np.asarray(vals, dtype=np.float64), slot_runs)
    rng = np.random.default_rng([grid.seed, group_no])
    proto = grid.cells[cell_idx[0]]
    traces = make_event_traces_batch(
        rng,
        int(slot_off[-1]),
        horizon=rep([c.horizon_factor * c.work for c in uniq_cells]),
        mtbf=rep([c.platform.mu for c in uniq_cells]),
        recall=rep([c.predictor.recall for c in uniq_cells]),
        precision=rep([c.predictor.precision for c in uniq_cells]),
        window=rep([c.predictor.window for c in uniq_cells]),
        lead=rep([c.predictor.lead for c in uniq_cells]),
        fault_dist=proto.dist,
        false_pred_dist=proto.false_pred_dist,
        n_components=proto.n_components,
        stationary=proto.stationary,
        # recovery-tier uniforms for two-level cells; drawn after every
        # other draw, so enabling them never perturbs the group's traces
        tier=any(
            grid.cells[ci].strategy.mode == "two_level" for ci in cell_idx
        ),
    )
    return traces.take(rows)


def _group_trace_spec(
    grid: GridSpec, cell_idx: List[int], stream_base: int
) -> Tuple[TraceSpec, int]:
    """Device-generation counterpart of :func:`_group_traces`: build the
    group's *cell-indexed* :class:`TraceSpec` — one parameter row per
    cell, O(lanes) stream ids — with *globally unique* stream ids per
    unique (trace-parameters, run) pair: cells sharing trace parameters
    share stream ids (paired design), and stream ids are stable across
    engines, dispatch granularities, chunk sizes and device counts.
    Returns the spec and the next free stream id."""
    cells = [grid.cells[ci] for ci in cell_idx]
    runs = [grid.cell_runs(ci) for ci in cell_idx]
    proto = cells[0]
    if proto.n_components:
        raise ValueError(
            "trace_mode='device' does not support superposed component "
            "traces (n_components); use trace_mode='host'"
        )
    _, cell_slot, _, slot_off, _ = _trace_slots(grid, cell_idx)
    stream = np.concatenate(
        [
            stream_base + slot_off[slot] + np.arange(r, dtype=np.int64)
            for slot, r in zip(cell_slot, runs)
        ]
    )
    cidx = np.repeat(np.arange(len(cells), dtype=np.int32), runs)
    spec = make_trace_spec(
        stream.shape[0],
        horizon=[c.horizon_factor * c.work for c in cells],
        mtbf=[c.platform.mu for c in cells],
        recall=[c.predictor.recall for c in cells],
        precision=[c.predictor.precision for c in cells],
        window=[c.predictor.window for c in cells],
        lead=[c.predictor.lead for c in cells],
        fault_dist=proto.dist,
        false_pred_dist=proto.false_pred_dist,
        seed=grid.seed,
        stream=stream,
        cell_index=cidx,
    )
    return spec, stream_base + int(slot_off[-1])


def _run_legacy(grid: GridSpec) -> List[List]:
    """The seed repository's exact pipeline: per-run object-based trace
    generation + scalar engine, one trace per (cell, run)."""
    out = []
    for ci, cell in enumerate(grid.cells):
        runs = []
        for i in range(grid.cell_runs(ci)):
            rng = np.random.default_rng(grid.seed + 1000 * i + 17)
            trace = make_event_trace(
                rng,
                horizon=cell.horizon_factor * cell.work,
                mtbf=cell.platform.mu,
                recall=cell.gen_recall,
                precision=cell.predictor.precision,
                window=cell.predictor.window,
                lead=cell.predictor.lead,
                fault_dist=cell.dist,
                false_pred_dist=cell.false_pred_dist,
                n_components=cell.n_components,
                stationary=cell.stationary,
            )
            runs.append(simulate(cell.work, cell.platform, cell.strategy, trace, rng))
        out.append(runs)
    return out


#: per-lane result fields assembled into CellResult arrays
_LANE_FIELDS = (
    "waste", "makespan", "n_faults", "n_proactive_ckpts",
    "n_regular_ckpts", "n_migrations", "trace_exhausted",
)


def _lane_arrays(res) -> Dict[str, np.ndarray]:
    return {k: getattr(res, k) for k in _LANE_FIELDS}


def _scalar_lane_arrays(outs) -> Dict[str, np.ndarray]:
    return {
        "waste": np.array([r.waste for r in outs]),
        "makespan": np.array([r.makespan for r in outs]),
        "n_faults": np.array([r.n_faults for r in outs]),
        "n_proactive_ckpts": np.array([r.n_proactive_ckpts for r in outs]),
        "n_regular_ckpts": np.array([r.n_regular_ckpts for r in outs]),
        "n_migrations": np.array([r.n_migrations for r in outs]),
        "trace_exhausted": np.array([r.trace_exhausted for r in outs]),
    }


def _cat_lane_arrays(parts: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {k: np.concatenate([p[k] for p in parts]) for k in _LANE_FIELDS}


@dataclass
class FusedLayout:
    """The fused dispatch's lane layout, grid-deterministic.

    Everything the cell-multiplexed engine call needs, assembled once
    from a :class:`GridSpec`: cells regrouped in trace-compatibility
    order (``cell_order``), per-cell lane counts and offsets, the
    per-cell engine tables (``work_c`` / ``plats_c`` / ``strats_c``),
    the lane -> cell index, and the trace source — per-group
    :class:`TraceSpec` streams (device trace mode) or one concatenated
    :class:`BatchTraces` (host mode).  Both :func:`run_grid` and the
    resumable :class:`~repro.ft.campaign.CampaignRunner` build the
    *same* layout from the same grid, which is what makes a campaign's
    lane partition (and therefore its results) reconstructible from
    ``(grid, cursor)`` alone — no trace replay, no stored traces."""

    grid: GridSpec
    groups: List[Tuple[Tuple, List[int]]]
    cell_order: List[int]
    runs_o: np.ndarray  # (n_cells,) lanes per cell, cell_order order
    offs: np.ndarray  # (n_cells + 1,) lane offsets per cell
    specs: List[TraceSpec]  # device trace mode: one spec per group
    traces: Optional[BatchTraces]  # host trace mode: all lanes
    work_c: np.ndarray
    plats_c: List
    strats_c: List
    cidx: np.ndarray  # (n_lanes,) lane -> cell_order position

    @property
    def n_groups(self) -> int:
        return len(self.groups)

    @property
    def n_lanes(self) -> int:
        return int(self.offs[-1])

    def concat_spec(self) -> TraceSpec:
        """The one-dispatch device-mode spec: multi-group grids
        concatenate per-group specs into a single cell-indexed spec
        (law-indexed sampler); single-group grids keep the
        law-specialized spec — same results, cheaper draws."""
        if not self.specs:
            raise ValueError("concat_spec requires trace_mode='device'")
        if len(self.specs) == 1:
            return self.specs[0]
        return TraceSpec.concat_cells(self.specs)

    def host_traces(self) -> BatchTraces:
        """Host-materialized event arrays for all lanes (host engines,
        and the campaign's batch-engine degradation path in device
        trace mode)."""
        if self.traces is not None:
            return self.traces
        return BatchTraces.concat([s.materialize() for s in self.specs])


def build_fused_layout(grid: GridSpec, trace_mode: str) -> FusedLayout:
    """Assemble the fused dispatch's :class:`FusedLayout` for ``grid``.

    Deterministic in ``(grid, trace_mode)``: host traces are generated
    from ``grid.seed`` per group, device specs carry globally-unique
    counter-RNG stream ids — so two processes building the layout from
    the same grid get bit-identical lanes in the same order."""
    groups = _group_cells(grid)
    cell_order: List[int] = [ci for _, idx in groups for ci in idx]
    runs_o = np.array([grid.cell_runs(ci) for ci in cell_order], np.int64)
    offs = np.concatenate([[0], np.cumsum(runs_o)])
    specs: List[TraceSpec] = []
    traces: Optional[BatchTraces] = None
    if trace_mode == "device":
        base = 0
        for _, idx in groups:
            spec, base = _group_trace_spec(grid, idx, base)
            specs.append(spec)
    else:
        # per-group batched generation, then one engine call over all
        # groups: with zero-copy sentinel adoption the width padding of
        # concat costs less than the extra iterations of per-group calls
        traces = BatchTraces.concat(
            [
                _group_traces(grid, idx, gno)
                for gno, (_, idx) in enumerate(groups)
            ]
        )
    # per-cell tables in cell_order (the fused dispatch's cell axis)
    work_c = np.asarray(
        [grid.cells[ci].work for ci in cell_order], dtype=np.float64
    )
    plats_c = [grid.cells[ci].platform for ci in cell_order]
    strats_c = [grid.cells[ci].strategy for ci in cell_order]
    cidx = np.repeat(np.arange(len(cell_order), dtype=np.int32), runs_o)
    return FusedLayout(
        grid=grid, groups=groups, cell_order=cell_order, runs_o=runs_o,
        offs=offs, specs=specs, traces=traces, work_c=work_c,
        plats_c=plats_c, strats_c=strats_c, cidx=cidx,
    )


def _stats_cell_result(cell: ExperimentCell, sums, i: int) -> CellResult:
    """One stats-backed CellResult row from device-reduced CellSums."""
    return CellResult.from_stats(
        cell,
        int(sums.n_exhausted[i]),
        sums.n[i],
        sums.mean_waste[i], sums.ci95_waste[i],
        sums.mean_makespan[i], sums.ci95_makespan[i],
        sums.n_faults[i] / sums.n[i],
        sums.n_proactive_ckpts[i] / sums.n[i],
        sums.n_regular_ckpts[i] / sums.n[i],
        sums.n_migrations[i] / sums.n[i],
    )


def run_grid(
    grid: GridSpec, config=None, *, engine=UNSET, chunk_lanes=UNSET,
    devices=UNSET, mesh=UNSET, trace_mode=UNSET,
    dispatch=UNSET, collect=UNSET,
) -> SweepResult:
    """Execute every cell of ``grid`` and aggregate per-cell statistics.

    ``config`` is an :class:`~repro.core.engine.EngineConfig` (or a bare
    engine-name string, honoring the historical positional form); the
    individual engine keywords below are deprecated shims for it.

    ``chunk_lanes`` (jax engine only) caps the lanes resident on the
    device per engine call — "auto" picks a backend-appropriate chunk,
    an int forces one, None runs the whole grid in a single call.
    ``devices`` / ``mesh`` (jax engine only) shard each chunk's lanes
    across a device set (:func:`repro.core.jax_sim.simulate_batch_jax`);
    per-lane results are identical for any device count.

    ``trace_mode="device"`` replaces host trace generation with per-lane
    counter-based RNG streams (:class:`~repro.core.events.TraceSpec`):
    the JAX engine samples events lazily on the device — mixed-law grids
    fuse into ONE dispatch through the law-indexed sampler — while the
    batch/scalar engines replay the identical streams host-side.  The paired design is
    preserved (cells sharing trace parameters share stream ids), and
    results are chunk-size and device-count invariant.  Not supported
    for the legacy engine or superposed (``n_components``) traces.

    ``dispatch`` selects "fused" (default for batched engines: the whole
    grid — all failure-law families included in device trace mode —
    rides ONE cell-multiplexed engine call), "perfamily" (jax + device
    trace mode: one call per trace-compatibility group through the same
    law-indexed sampler — the bit-exact pre-fusion baseline), or
    "percell" (one engine call per cell; see the module docstring).  The
    legacy engine is inherently per-cell.  ``collect="stats"`` (jax
    only) fetches device-reduced per-cell statistics instead of per-run
    arrays."""
    cfg = resolve_engine_config(
        config, "run_grid", engine=engine, chunk_lanes=chunk_lanes,
        devices=devices, mesh=mesh, trace_mode=trace_mode,
        dispatch=dispatch, collect=collect,
    )
    engine, chunk_lanes = cfg.engine, cfg.chunk_lanes
    devices, mesh = cfg.devices, cfg.mesh
    trace_mode, dispatch, collect = cfg.trace_mode, cfg.dispatch, cfg.collect
    if engine not in ("batch", "scalar", "legacy", "jax"):
        raise ValueError(
            f"unknown engine {engine!r} "
            "(expected 'batch', 'jax', 'scalar' or 'legacy')"
        )
    if engine != "jax" and (devices is not None or mesh is not None):
        raise ValueError("devices=/mesh= require engine='jax'")
    if trace_mode not in ("host", "device"):
        raise ValueError(
            f"unknown trace_mode {trace_mode!r} (expected 'host' or 'device')"
        )
    if trace_mode == "device" and engine == "legacy":
        raise ValueError("trace_mode='device' requires a batched engine")
    if dispatch is None:
        dispatch = "percell" if engine == "legacy" else "fused"
    if dispatch not in ("fused", "percell", "perfamily"):
        raise ValueError(
            f"unknown dispatch {dispatch!r} "
            "(expected 'fused', 'perfamily' or 'percell')"
        )
    if engine == "legacy" and dispatch == "fused":
        raise ValueError("engine='legacy' is inherently per-cell")
    if dispatch == "perfamily" and not (
        engine == "jax" and trace_mode == "device"
    ):
        raise ValueError(
            "dispatch='perfamily' requires engine='jax' and "
            "trace_mode='device'"
        )
    if collect not in ("lanes", "stats"):
        raise ValueError(
            f"unknown collect {collect!r} (expected 'lanes' or 'stats')"
        )
    if collect == "stats" and engine != "jax":
        raise ValueError("collect='stats' requires engine='jax'")
    if collect == "stats" and dispatch == "percell":
        raise ValueError(
            "collect='stats' requires dispatch='fused' or 'perfamily'"
        )
    with span(
        "repro.run_grid", cells=len(grid.cells), lanes=grid.n_lanes,
        trace_mode=trace_mode,
    ):
        return _run_grid(
            grid, engine, chunk_lanes, devices, mesh, trace_mode, dispatch,
            collect,
        )


def _run_grid(
    grid: GridSpec, engine, chunk_lanes, devices, mesh, trace_mode,
    dispatch, collect,
) -> SweepResult:
    """:func:`run_grid` past its checks."""
    t0 = time.monotonic()
    if engine == "legacy":
        cells = []
        for cell, runs in zip(grid.cells, _run_legacy(grid)):
            cells.append(
                CellResult(
                    cell=cell,
                    waste=np.array([r.waste for r in runs]),
                    makespan=np.array([r.makespan for r in runs]),
                    n_faults=np.array([r.n_faults for r in runs]),
                    n_proactive_ckpts=np.array([r.n_proactive_ckpts for r in runs]),
                    n_regular_ckpts=np.array([r.n_regular_ckpts for r in runs]),
                    n_migrations=np.array([r.n_migrations for r in runs]),
                    n_exhausted=sum(r.trace_exhausted for r in runs),
                )
            )
        return SweepResult(
            grid=grid, cells=cells, engine=engine,
            wall_time_s=time.monotonic() - t0, dispatch=dispatch,
        )
    with span("repro.run_grid.layout"):
        layout = build_fused_layout(grid, trace_mode)
    groups, cell_order = layout.groups, layout.cell_order
    runs_o, offs, specs = layout.runs_o, layout.offs, layout.specs
    work_c, plats_c = layout.work_c, layout.plats_c
    strats_c, cidx = layout.strats_c, layout.cidx
    traces = layout.traces
    if trace_mode == "device" and engine != "jax":
        # host engines replay the device streams via materialize()
        traces = layout.host_traces()

    lane_parts: List[Dict[str, np.ndarray]] = []
    # (CellSums, position of its first cell in cell_order)
    stats_parts: List[Tuple[object, int]] = []

    if dispatch == "percell":
        # one engine call per cell: same traces/streams as the fused
        # path, so per-cell results match it (bit-identically for the
        # deterministic trust settings; see module docstring)
        if engine == "jax":
            from ..core.jax_sim import simulate_batch_jax

        # cell position -> (owning group, group's first lane offset)
        group_of: List[int] = []
        group_lane0: List[int] = []
        p = 0
        for g, (_, idx) in enumerate(groups):
            group_of.extend([g] * len(idx))
            group_lane0.extend([int(offs[p])] * len(idx))
            p += len(idx)
        expanded: List[Optional[TraceSpec]] = [None] * len(specs)
        for k in range(len(cell_order)):
            sl = slice(int(offs[k]), int(offs[k + 1]))
            n_k = int(runs_o[k])
            wk = np.full(n_k, work_c[k])
            pk, sk = [plats_c[k]] * n_k, [strats_c[k]] * n_k
            if trace_mode == "device" and engine == "jax":
                g = group_of[k]
                if expanded[g] is None:
                    expanded[g] = specs[g].expand()
                glo = group_lane0[k]
                sub = expanded[g].take(
                    np.arange(sl.start - glo, sl.stop - glo)
                )
            else:
                sub = traces.take(np.arange(sl.start, sl.stop))
            if engine == "jax":
                res = simulate_batch_jax(
                    wk, pk, sk, sub,
                    rng=np.random.default_rng([grid.seed, len(groups), k]),
                    chunk=chunk_lanes, devices=devices, mesh=mesh,
                )
                lane_parts.append(_lane_arrays(res))
            elif engine == "batch":
                res = simulate_batch(
                    wk, pk, sk, sub,
                    rng=np.random.default_rng([grid.seed, len(groups), k]),
                )
                lane_parts.append(_lane_arrays(res))
            else:  # scalar: per-lane rng seeds match the fused path
                outs = [
                    simulate(
                        float(work_c[k]), plats_c[k], strats_c[k],
                        sub.lane(j),
                        np.random.default_rng(
                            [grid.seed, len(groups), sl.start + j]
                        ),
                    )
                    for j in range(n_k)
                ]
                lane_parts.append(_scalar_lane_arrays(outs))
    elif engine == "jax" and trace_mode == "device":
        from ..core.jax_sim import simulate_batch_jax

        if dispatch == "fused" and len(groups) > 1:
            # ONE mixed-law dispatch: the per-group specs concatenate
            # into a single cell-indexed spec whose failure laws ride
            # the cell tables through the law-indexed sampler — one
            # compiled executable per grid *shape*, not per family
            with span("repro.run_grid.layout"):
                spec = TraceSpec.concat_cells(specs)
            res = simulate_batch_jax(
                work_c, plats_c, strats_c, spec,
                chunk=chunk_lanes, devices=devices, mesh=mesh,
                collect=collect,
            )
            if collect == "stats":
                stats_parts.append((res, 0))
            else:
                lane_parts.append(_lane_arrays(res))
        else:
            # one dispatch per trace-compatibility group: the
            # single-family fast path of "fused" (law-specialized
            # sampler, no indexed overhead) and the explicit
            # "perfamily" baseline, whose specs are tuple-ized so the
            # law-indexed sampler — hence every per-lane result — is
            # bit-identical to the one-dispatch path
            pos = 0
            for (_, idx), spec in zip(groups, specs):
                a, b = pos, pos + len(idx)
                if dispatch == "perfamily":
                    spec = spec.indexed()
                res = simulate_batch_jax(
                    work_c[a:b], plats_c[a:b], strats_c[a:b], spec,
                    chunk=chunk_lanes, devices=devices, mesh=mesh,
                    collect=collect,
                )
                if collect == "stats":
                    stats_parts.append((res, a))
                else:
                    lane_parts.append(_lane_arrays(res))
                pos = b
    elif engine == "jax":
        # fused host-trace dispatch: per-cell engine tables + the lane ->
        # cell index (event arrays stay per-lane)
        from ..core.jax_sim import simulate_batch_jax

        res = simulate_batch_jax(
            work_c, plats_c, strats_c, traces,
            rng=np.random.default_rng([grid.seed, len(groups)]),
            chunk=chunk_lanes, devices=devices, mesh=mesh,
            cell_index=cidx, collect=collect,
        )
        if collect == "stats":
            stats_parts.append((res, 0))
        else:
            lane_parts.append(_lane_arrays(res))
    elif engine == "batch":
        res = simulate_batch(
            np.repeat(work_c, runs_o),
            [plats_c[k] for k in range(len(cell_order)) for _ in range(runs_o[k])],
            [strats_c[k] for k in range(len(cell_order)) for _ in range(runs_o[k])],
            traces,
            rng=np.random.default_rng([grid.seed, len(groups)]),
        )
        lane_parts.append(_lane_arrays(res))
    else:  # scalar
        work_l = np.repeat(work_c, runs_o)
        plats_l = [
            plats_c[k] for k in range(len(cell_order)) for _ in range(runs_o[k])
        ]
        strats_l = [
            strats_c[k] for k in range(len(cell_order)) for _ in range(runs_o[k])
        ]
        outs = [
            simulate(
                float(work_l[i]), plats_l[i], strats_l[i], traces.lane(i),
                np.random.default_rng([grid.seed, len(groups), i]),
            )
            for i in range(traces.n_lanes)
        ]
        lane_parts.append(_scalar_lane_arrays(outs))

    with span("repro.run_grid.results"):
        cells = _cell_results(
            grid, cell_order, offs, collect, lane_parts, stats_parts
        )
    return SweepResult(
        grid=grid, cells=cells, engine=engine,
        wall_time_s=time.monotonic() - t0, dispatch=dispatch, collect=collect,
    )


def _cell_results(grid, cell_order, offs, collect, lane_parts, stats_parts):
    """The sweep's :class:`CellResult` rows, in ``grid.cells`` order."""
    cells: List[Optional[CellResult]] = [None] * len(grid.cells)
    if collect == "stats":
        for sums, first in stats_parts:
            for i in range(sums.n_cells):
                ci = cell_order[first + i]
                cells[ci] = _stats_cell_result(grid.cells[ci], sums, i)
        return cells
    lanes = _cat_lane_arrays(lane_parts)
    for k, ci in enumerate(cell_order):
        sl = slice(int(offs[k]), int(offs[k + 1]))
        cells[ci] = CellResult(
            cell=grid.cells[ci],
            waste=lanes["waste"][sl],
            makespan=lanes["makespan"][sl],
            n_faults=lanes["n_faults"][sl],
            n_proactive_ckpts=lanes["n_proactive_ckpts"][sl],
            n_regular_ckpts=lanes["n_regular_ckpts"][sl],
            n_migrations=lanes["n_migrations"][sl],
            n_exhausted=int(np.count_nonzero(lanes["trace_exhausted"][sl])),
        )
    return cells


def run_cells(
    cells: Sequence[ExperimentCell],
    n_runs: int = 100,
    seed: int = 0,
    config=None,
    *,
    engine=UNSET,
    chunk_lanes=UNSET,
    devices=UNSET,
    mesh=UNSET,
    trace_mode=UNSET,
    dispatch=UNSET,
    collect=UNSET,
) -> SweepResult:
    """Convenience wrapper: build a :class:`GridSpec` and run it."""
    cfg = resolve_engine_config(
        config, "run_cells", engine=engine, chunk_lanes=chunk_lanes,
        devices=devices, mesh=mesh, trace_mode=trace_mode,
        dispatch=dispatch, collect=collect,
    )
    return run_grid(GridSpec(tuple(cells), n_runs=n_runs, seed=seed), cfg)
