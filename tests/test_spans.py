"""The program's own spans, kernel scope and loop counters
(``repro.core.spans``, ``jax_sim.LAST_TIMINGS["loop_iters"]``,
``["walk_passes"]`` and the pending-TP buffer's counts), on the CPU: they
leave results bit-identical, keep ``LAST_TIMINGS``'s fields, count each
chunk's outer loop, prediction walk and buffer, reach a recorded trace
nested and tagged as documented, and add no host transfer."""

import glob
import os
import subprocess
import sys
from dataclasses import replace

import numpy as np
import pytest

from repro.core import Platform, PredictorModel, jax_sim, spans
from repro.core import events as E
from repro.core import simulator as S
from repro.core.jax_sim import simulate_batch_jax

MN = 60.0
PLAT = Platform(mu=1000 * MN, C=10 * MN, D=1 * MN, R=10 * MN, M=5 * MN)
PLAT2 = Platform(mu=500 * MN, C=5 * MN, D=1 * MN, R=5 * MN, M=3 * MN)
WORK = 20 * 86400.0
PREDW = PredictorModel(recall=0.85, precision=0.82, window=3000.0)
PRED = PredictorModel(recall=0.85, precision=0.82)

#: lanes per chunk of the loop-count checks, and chunks per call
CHUNK = 1024
N_CHUNKS = 3

#: the host spans of one run_grid (device trace, stats) and one
#: optimize(method="newton") call: name -> (parent, attributes)
SPANS = {
    "repro.run_grid": (None, {"call", "cells", "lanes", "trace_mode"}),
    "repro.run_grid.layout": ("repro.run_grid", {"call"}),
    "repro.engine.prepare": ("repro.run_grid", {"call", "lanes", "cells"}),
    "repro.engine.pack": ("repro.run_grid", {"call"}),
    "repro.engine.dispatch": (
        "repro.run_grid", {"call", "chunk", "arrays", "bytes"}
    ),
    "repro.engine.wait": ("repro.run_grid", {"call"}),
    "repro.engine.fetch": ("repro.run_grid", {"call"}),
    "repro.run_grid.results": ("repro.run_grid", {"call"}),
    "repro.optimize": (None, {"call", "method", "cells"}),
    "repro.optimize.tables": ("repro.optimize", {"call"}),
    "repro.optimize.solve": ("repro.optimize", {"call"}),
}

TIMING_KEYS = {"trace_mode", "pack_s", "dispatch_s", "fetch_s", "n_chunks",
               "loop_iters", "walk_passes", "tp_overtakes", "tp_pending_peak",
               "tp_overflow", "precision", "pallas"}
#: the counts of a chunk, one per device, each as ``LAST_TIMINGS`` keeps it
COUNTS = ("loop_iters", "walk_passes", "tp_overtakes", "tp_pending_peak",
          "tp_overflow")


def _cells(n_runs):
    """Three cells (one migration cell) as a cell-indexed spec."""
    plats = [PLAT, PLAT2, PLAT2]
    preds = [PREDW, PRED, PRED]
    strats = [S.instant(PLAT, PREDW), S.young(PLAT2), S.migration(PLAT2, PRED)]
    cidx = np.repeat(np.arange(3, dtype=np.int32), n_runs)
    spec = E.make_trace_spec(
        3 * n_runs, horizon=[12 * WORK] * 3, mtbf=[p.mu for p in plats],
        recall=[p.recall for p in preds],
        precision=[p.precision for p in preds],
        window=[p.window for p in preds], lead=[p.lead for p in preds],
        seed=17, cell_index=cidx,
    )
    return plats, strats, cidx, spec


def _grid():
    from repro.experiments import ExperimentCell, GridSpec

    cells = [
        ExperimentCell(label=s.name, work=6 * 86400.0, platform=PLAT,
                       predictor=PRED, strategy=s)
        for s in (S.young(PLAT), S.exact_prediction(PLAT, PRED),
                  S.migration(PLAT, PRED))
    ]
    return GridSpec(tuple(cells), n_runs=40, seed=3)


def _config(collect):
    from repro.core import EngineConfig

    return EngineConfig(engine="jax", trace_mode="device", collect=collect,
                        chunk_lanes=64)


def _profiled(tmp_path, fn):
    """``fn()`` inside a profiler session; returns its value and the
    session's host events ``[(line, name, start_ns, end_ns, stats)]``."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    events = [
        (line.name, e.name, e.start_ns, e.start_ns + e.duration_ns,
         dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if not plane.name.startswith("/device:")
        for line in plane.lines for e in line.events
        if e.name.startswith("repro.")
    ]
    return out, events


def _sweep_arrays(sweep):
    return [
        np.asarray(v, np.float64)
        for c in sweep.cells
        for v in (c.mean_waste, c.ci95_waste, c.mean_makespan,
                  c.ci95_makespan, c.mean_faults, c.waste, c.makespan)
        if v is not None
    ]


@pytest.mark.parametrize("collect", ["stats", "lanes"])
def test_results_are_bit_identical_with_spans_recording(tmp_path, collect):
    """A seeded run_grid gives the same stats and lanes whether the
    spans only time (no profiler) or also annotate and record."""
    from repro.experiments import run_grid

    grid = _grid()
    plain = run_grid(grid, _config(collect))
    keys = set(jax_sim.LAST_TIMINGS)
    traced, _ = _profiled(tmp_path, lambda: run_grid(grid, _config(collect)))
    for a, b in zip(_sweep_arrays(plain), _sweep_arrays(traced)):
        np.testing.assert_array_equal(a, b)
    assert keys == set(jax_sim.LAST_TIMINGS) == TIMING_KEYS


def test_timings_keep_their_fields_and_meaning():
    from repro.experiments import run_grid

    run_grid(_grid(), _config("stats"))
    t = jax_sim.LAST_TIMINGS
    assert set(t) == TIMING_KEYS
    assert t["n_chunks"] == 2  # 120 lanes in chunks of 64
    for k in COUNTS:
        assert t[k].shape == (2, 1)
        assert t[k].dtype == np.int64
        # no window in this grid: nothing for the buffer to count
        assert (t[k] > 0).all() if k in ("loop_iters", "walk_passes") else (
            t[k] == 0).all()
    for k in ("pack_s", "dispatch_s", "fetch_s"):
        assert t[k] > 0.0
    assert (t["trace_mode"], t["precision"]) == ("device", "x64")


@pytest.mark.parametrize("collect", ["stats", "lanes"])
def test_loop_iters_are_each_chunks_count(collect):
    """``loop_iters[k]`` is chunk ``k``'s outer-loop count, and
    ``walk_passes[k]`` its prediction walk's passes, and the buffer's
    counts its own (the window cell's chunk overtakes): lanes evolve
    independently, so the same lanes run alone count as much of each —
    but for the buffer's peak, which only a call with a window counts
    (lanes with exact dates hold at most one TP)."""
    plats, strats, cidx, spec = _cells(CHUNK)
    simulate_batch_jax([WORK] * 3, plats, strats, spec, chunk=CHUNK,
                       collect=collect, use_pallas=False)
    whole = {k: jax_sim.LAST_TIMINGS[k] for k in COUNTS}
    assert all(v.shape == (N_CHUNKS, 1) for v in whole.values())
    assert whole["tp_overtakes"][0, 0] > 0
    assert (whole["tp_overtakes"][1:] == 0).all()
    lane = spec.expand()
    for k in range(N_CHUNKS):
        rows = np.arange(k * CHUNK, (k + 1) * CHUNK)
        simulate_batch_jax(
            WORK, [plats[c] for c in cidx[rows]],
            [strats[c] for c in cidx[rows]], lane.take(rows), chunk=CHUNK,
            use_pallas=False,
        )
        for name, v in whole.items():
            got = jax_sim.LAST_TIMINGS[name].tolist()
            if name == "tp_pending_peak" and k:
                assert got == [[0]] and v[k, 0] <= 1
            else:
                assert got == [[v[k, 0]]], name


def _walk_passes(strat, pred, trace_mode="device"):
    from repro.experiments import ExperimentCell, GridSpec, run_grid

    cells = tuple(
        ExperimentCell(label=f"{strat.name}{i}", work=6 * 86400.0,
                       platform=PLAT, predictor=pred, strategy=strat)
        for i in range(2)
    )
    cfg = replace(_config("lanes" if trace_mode == "host" else "stats"),
                  trace_mode=trace_mode)
    run_grid(GridSpec(cells, n_runs=40, seed=5), cfg)
    return jax_sim.LAST_TIMINGS["walk_passes"]


@pytest.mark.parametrize("strat,pred", [
    (S.young(PLAT), PRED),
    (S.Strategy("Distrust", S.young(PLAT).T_R, q=0.0, mode="exact"), PRED),
    (S.exact_prediction(PLAT, PredictorModel(0.0, 1.0)),
     PredictorModel(0.0, 1.0)),
], ids=["young", "distrust", "no-predictions"])
def test_walk_passes_are_zero_where_no_lane_predicts(strat, pred):
    """Lanes that trust no prediction, or get none, never walk."""
    got = _walk_passes(strat, pred)
    assert got.shape == (2, 1) and got.dtype == np.int64
    assert (got == 0).all()


def test_walk_passes_count_exact_prediction():
    """ExactPrediction lanes walk at least one pass per prediction they
    consume, so every chunk counts some; host traces have no walk."""
    got = _walk_passes(S.exact_prediction(PLAT, PRED), PRED)
    assert got.shape == (2, 1) and got.dtype == np.int64
    assert (got > 0).all()
    host = _walk_passes(S.exact_prediction(PLAT, PRED), PRED, "host")
    assert (host == 0).all()


SHARDED = """
import numpy as np
from repro.core import jax_sim
from repro.core.jax_sim import simulate_batch_jax
import test_spans as T

plats, strats, cidx, spec = T._cells(T.CHUNK)
lane = spec.expand()
half = T.CHUNK // 2
for collect in ("stats", "lanes"):
    simulate_batch_jax([T.WORK] * 3, plats, strats, spec, chunk=T.CHUNK,
                       devices=2, collect=collect, use_pallas=False)
    two = {k: jax_sim.LAST_TIMINGS[k] for k in T.COUNTS}
    assert all(v.shape == (T.N_CHUNKS, 2) for v in two.values()), two
    for k in range(T.N_CHUNKS):
        for d in range(2):
            rows = np.arange(k * T.CHUNK + d * half, k * T.CHUNK + (d + 1) * half)
            simulate_batch_jax(
                T.WORK, [plats[c] for c in cidx[rows]],
                [strats[c] for c in cidx[rows]], lane.take(rows),
                chunk=T.CHUNK, devices=1, use_pallas=False,
            )
            for name, v in two.items():
                one = jax_sim.LAST_TIMINGS[name].tolist()
                if name == "tp_pending_peak" and k:  # no window: no buffer
                    assert one == [[0]] and v[k, d] <= 1, (collect, k, d, v)
                else:
                    assert one == [[v[k, d]]], (collect, name, k, d, one, v)
print("LOOP_ITERS_OK")
"""


def test_loop_iters_count_per_device_when_sharded():
    """With 2 forced host devices each chunk reports one count (of loop
    iterations, of walk passes and of the buffer's) per device: that of
    its shard's lanes run alone on one device."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count=2").strip(),
        PYTHONPATH=os.pathsep.join(
            [here, os.path.join(here, "..", "src"),
             os.environ.get("PYTHONPATH", "")]),
    )
    proc = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "LOOP_ITERS_OK" in proc.stdout


def test_a_recorded_trace_holds_every_span_nested_and_tagged(tmp_path):
    """One run_grid and one optimize: every span of :data:`SPANS` is in
    the trace with its attributes, inside its parent on the same thread,
    with the parent's ``call`` id; the two calls have ids of their own;
    the process kept the same spans (``spans.RECORDED``)."""
    from repro.core import optimize
    from repro.experiments import run_grid

    spans.RECORDED.clear()

    def calls():
        run_grid(_grid(), _config("stats"))
        optimize(["young", "exact", "migration"], PLAT, PRED, method="newton")

    _, events = _profiled(tmp_path, calls)
    assert {e[1] for e in events} == set(SPANS)
    tops = {e[1]: e for e in events if SPANS[e[1]][0] is None}
    assert tops["repro.run_grid"][4]["call"] != tops["repro.optimize"][4]["call"]
    for line, name, s, t, stats in events:
        parent, attrs = SPANS[name]
        assert attrs <= set(stats), (name, stats)
        if parent is None:
            continue
        p = tops[parent]
        assert p[0] == line and p[2] <= s and t <= p[3], name
        assert stats["call"] == p[4]["call"], name
    chunked = [e for e in events if "chunk" in e[4]]
    assert {e[4]["chunk"] for e in chunked} == {0, 1}
    # the preparation comes in two parts, the lane table before the
    # pre-loop packing and the cell tables and accumulator after it
    prep = sorted(e[2:4] for e in events if e[1] == "repro.engine.prepare")
    pre = [e for e in events if e[1] == "repro.engine.pack" and "chunk" not in e[4]]
    assert len(prep) == 2 and len(pre) == 1
    assert prep[0][1] <= pre[0][2] and pre[0][3] <= prep[1][0]
    assert sorted(r[0] for r in spans.RECORDED) == sorted(e[1] for e in events)
    assert tops["repro.run_grid"][4]["trace_mode"] == "device"
    assert tops["repro.optimize"][4]["method"] == "newton"


def test_spans_outside_a_session_record_nothing():
    from repro.experiments import run_grid

    spans.RECORDED.clear()
    run_grid(_grid(), _config("stats"))
    assert not spans.RECORDED


def test_nested_spans_share_the_call_and_time_into_the_record():
    rec = {}
    with spans.span("repro.a", rec, "x") as a:
        with spans.span("repro.b", rec, "x") as b:
            pass
    with spans.span("repro.c") as c:
        pass
    assert a.attrs["call"] == b.attrs["call"] != c.attrs["call"]
    assert rec["x"] > 0.0


def test_stats_call_adds_no_transfer_while_traced(tmp_path):
    """Under ``jax.transfer_guard("disallow")`` the stats call passes
    with the profiler on: the spans, the wait and the loop counts move
    nothing to or from the device implicitly."""
    import jax

    plats, strats, cidx, spec = _cells(4)
    args = ([WORK] * 3, plats, strats, spec)
    ref = simulate_batch_jax(*args, collect="stats")  # compile outside

    def guarded():
        with jax.transfer_guard("disallow"):
            return simulate_batch_jax(*args, collect="stats")

    got, _ = _profiled(tmp_path, guarded)
    np.testing.assert_array_equal(got.waste_sum, ref.waste_sum)
    np.testing.assert_array_equal(got.n, ref.n)
