"""Reading what the program puts on the record (``chipbench/program_trace``)
and the per-layer metrics built on it, on events made by hand: idle gaps
named by the innermost span of either kind, the share of idle time a
program span holds, per-call sums of the kept spans, the step program's
shares (the kernel's scope, the nested loops), the loop counter, and a
program that records none of it."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import bench, program_trace, trace_reduce  # noqa: E402

DEV = "/device:TPU:0"


def _trace(ops, spans, modules=None):
    planes = [(DEV, {"XLA Ops": ops, "XLA Modules": modules or []}),
              ("/host:CPU", {"python": spans})]
    return trace_reduce.reduce_planes(planes)


#: device busy at [100, 180] and [300, 400]; gaps [50, 100], [180, 300]
#: and [400, 450] in the window [50, 450]
OPS = [("a", 100.0, 50.0), ("b", 120.0, 60.0), ("c", 300.0, 100.0)]
BENCH_SPANS = [
    ("chipbench.trace_window", 50.0, 400.0),
    ("chipbench.sweep", 0.0, 1000.0),
    ("chipbench.run_grid", 180.0, 100.0),
]


@pytest.mark.parametrize("program,want", [
    # no program span: every gap keeps the name the reduction gives it
    ([], ["chipbench.run_grid", "chipbench.sweep", "chipbench.sweep"]),
    # a dispatch span inside the benchmark's run_grid holds the long gap
    ([("repro.run_grid", 182.0, 95.0, {"call": 1}),
      ("repro.engine.dispatch", 200.0, 60.0, {"call": 1})],
     ["repro.engine.dispatch", "chipbench.sweep", "chipbench.sweep"]),
    # a program span that does not hold a gap's midpoint leaves it alone
    ([("repro.engine.pack", 181.0, 5.0, {"call": 1})],
     ["chipbench.run_grid", "chipbench.sweep", "chipbench.sweep"]),
])
def test_gaps_are_named_by_the_innermost_span_of_either_kind(program, want):
    tr = _trace(OPS, BENCH_SPANS)
    gaps = program_trace.gap_names(tr, program)
    assert [g[0] for g in gaps] == want
    assert [g[1] for g in gaps] == pytest.approx([120e-9, 50e-9, 50e-9])
    if not program:
        assert gaps == tr.idle_gaps()


@pytest.mark.parametrize("program,want", [
    ([], 0.0),
    ([("repro.engine.dispatch", 200.0, 60.0, {})], 60.0),
    ([("repro.run_grid", 150.0, 200.0, {}),
      ("repro.engine.dispatch", 200.0, 60.0, {})], 100.0),
])
def test_held_share_of_the_idle_time_inside_a_benchmark_span(program, want):
    tr = _trace(OPS, BENCH_SPANS)
    # idle inside chipbench.run_grid [180, 280]: 100 ns
    assert program_trace.held_share(tr, program, "chipbench.run_grid") \
        == pytest.approx(want)
    assert program_trace.held_share(tr, program, "chipbench.query") is None


#: two run_grid calls and one optimize call, as the process keeps them
RECORDED = [
    ("repro.run_grid.layout", 0, 1_000_000, {"call": 1}),
    ("repro.engine.dispatch", 0, 3_000_000, {"call": 1, "chunk": 0}),
    ("repro.engine.wait", 0, 10_000_000, {"call": 1}),
    ("repro.run_grid", 0, 20_000_000, {"call": 1}),
    ("repro.optimize.solve", 0, 8_000_000, {"call": 2}),
    ("repro.optimize", 0, 11_000_000, {"call": 2}),
    ("repro.engine.dispatch", 0, 2_000_000, {"call": 3, "chunk": 0}),
    ("repro.engine.dispatch", 0, 1_000_000, {"call": 3, "chunk": 1}),
    ("repro.engine.wait", 0, 4_000_000, {"call": 3}),
    ("repro.run_grid", 0, 16_000_000, {"call": 3}),
    # a call whose top span opened before the session: not counted
    ("repro.engine.wait", 0, 99_000_000, {"call": 0}),
]


@pytest.fixture
def kept(monkeypatch):
    from repro.core import spans

    monkeypatch.setattr(spans, "RECORDED", list(RECORDED))


@pytest.mark.parametrize("metric,want", [
    ("run_grid_host_ms.query", ((20 - 10) + (16 - 4)) / 2),
    ("dispatch_ms.query", (3 + 2 + 1) / 2),
    ("engine_wait_ms.query", (10 + 4) / 2),
    ("optimize_host_ms.query", 11 - 8),
])
def test_host_span_metrics_read_the_kept_spans(kept, metric, want):
    read = bench.load_module("metrics", metric, ROOT).read
    assert read({"layer": {}}) == pytest.approx(want)


def test_run_grid_host_and_wait_add_up_to_the_call(kept):
    host = bench.load_module("metrics", "run_grid_host_ms.query", ROOT).read
    wait = bench.load_module("metrics", "engine_wait_ms.query", ROOT).read
    calls = program_trace.calls(RECORDED, "repro.run_grid")
    assert len(calls) == 2
    whole = 1e-6 * sum(c["repro.run_grid"] for c in calls) / len(calls)
    assert host({}) + wait({}) == pytest.approx(whole)


def test_loop_iters_reads_the_last_call(monkeypatch):
    from repro.core import jax_sim

    read = bench.load_module("metrics", "loop_iters.sweep", ROOT).read
    monkeypatch.setattr(jax_sim, "LAST_TIMINGS",
                        {"loop_iters": np.array([[100], [300]], np.int64)})
    assert read({}) == pytest.approx(200.0)
    monkeypatch.setattr(jax_sim, "LAST_TIMINGS", {})
    assert read({}) is None


def _hlo(name, s, d):
    """A TPU op event: its HLO text as the name, no stats."""
    return (f"%{name} = f32[16384]{{0:T(1024)}} op(...)", s, d, "")


#: one step-program run [100, 180] as a TPU trace shows it: the outer
#: loop, the kernel its scope names, a nested loop holding another, a
#: loop inside a conditional, and a loop of a program that is not the
#: step's (outside the run)
TPU_OPS = [
    _hlo("while.237", 100.0, 80.0),
    _hlo("step_kernel.11", 110.0, 10.0),
    _hlo("while.238", 125.0, 20.0),
    _hlo("while.251", 130.0, 10.0),
    _hlo("cond.199", 150.0, 20.0),
    _hlo("while.2", 155.0, 10.0),
    _hlo("step_kernel.11", 500.0, 50.0),
    _hlo("while.9", 520.0, 20.0),
]
TPU_MODULES = [("jit_run_stats(2015)", 100.0, 80.0),
               ("jit_newton_policy(77)", 480.0, 100.0)]


@pytest.mark.parametrize("metric,ops,want", [
    ("step_kernel_pct.sweep", TPU_OPS, 100.0 * 10.0 / 80.0),
    ("inner_loops_pct.sweep", TPU_OPS, 100.0 * 30.0 / 80.0),
    # a trace that keeps the op's JAX name as a stat
    ("step_kernel_pct.sweep",
     [("fusion.3", 100.0, 80.0, "jit(run_stats)/while"),
      ("custom-call.1", 120.0, 20.0,
       "jit(run_stats)/while/body/step_kernel/pallas_call")],
     100.0 * 20.0 / 80.0),
])
def test_step_program_shares_read_from_the_trace(metric, ops, want):
    tr = _trace(ops, [("chipbench.trace_window", 0.0, 1000.0)],
                modules=TPU_MODULES)
    read = bench.load_module("metrics", metric, ROOT).read
    assert read({"trace": tr}) == pytest.approx(want)


def test_a_step_run_cut_by_the_window_keeps_its_outer_loop():
    """The outer loop of a run that began before the window is still
    the run's outer loop, not one of its nested loops."""
    tr = _trace(TPU_OPS, [("chipbench.trace_window", 135.0, 1000.0)],
                modules=TPU_MODULES)
    # busy in the window [135, 180]; nested loops [135, 145] and [155, 165]
    assert program_trace.nested_loop_share(tr) == pytest.approx(
        100.0 * 20.0 / 45.0)


def test_scope_names_are_not_the_roofline_readers_kernel():
    """The roofline reader finds the step kernel by its patterns; the
    scope the program adds may not match them."""
    k = bench.load_module("metrics", "step_kernel_roofline.sweep", ROOT)
    assert not k.KERNEL.search("jit(run_stats)/step_kernel/add")
    assert not k.KERNEL.search("%step_kernel.3 = f32[16384] add(...)")
    assert not k.KERNEL_OP.match("step_kernel.12")


def test_read_spans_keeps_every_threads_spans(tmp_path):
    """A span on a side thread, as the harness's loop thread makes them,
    is read beside the main thread's: two threads may share a line name
    in the trace."""
    import threading
    import time

    import jax

    def side():
        with jax.profiler.TraceAnnotation("chipbench.side"):
            time.sleep(0.01)

    jax.profiler.start_trace(str(tmp_path))
    try:
        th = threading.Thread(target=side)
        th.start()
        with jax.profiler.TraceAnnotation("chipbench.main"):
            time.sleep(0.02)
        th.join()
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    got = program_trace.read_spans(path, "chipbench.")
    assert sorted(s[0] for s in got) == ["chipbench.main", "chipbench.side"]
    assert not program_trace.read_spans(path)  # no program span


@pytest.mark.parametrize("metric", [
    "run_grid_host_ms.query", "dispatch_ms.query", "engine_wait_ms.query",
    "optimize_host_ms.query", "loop_iters.sweep", "step_kernel_pct.sweep",
    "inner_loops_pct.sweep",
])
def test_a_program_without_spans_gives_nothing(monkeypatch, metric):
    """An older program (no ``repro.core.spans``, no scopes, no counter:
    its kernel named after the loop body) on a trace with no nested loop
    leaves each new metric out of the line, without an error."""
    import repro.core
    from repro.core import jax_sim

    monkeypatch.delattr(repro.core, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    monkeypatch.setattr(jax_sim, "LAST_TIMINGS", {"pack_s": 0.1})
    tr = _trace([_hlo("fusion.1", 100.0, 80.0), _hlo("body.11", 110.0, 5.0)],
                [], modules=[("jit_run_stats", 100.0, 80.0)])
    read = bench.load_module("metrics", metric, ROOT).read
    assert read({"trace": tr, "layer": {"pack_s": [0.1]}}) is None
