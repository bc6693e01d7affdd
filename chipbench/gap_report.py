"""Where a cell's device idle time goes, named by the program's own spans.

    python3 chipbench/gap_report.py --workload <name> --seed <n> \\
        [--seconds 10] [--trace-seconds 4] [--out <file.json>]

Builds and warms the cell as ``run.py`` does (its chips, inputs and
compile cache), then runs it twice back to back: an untraced stretch of
``--seconds``, and the harness's traced window (``run.traced_window``:
the steps on a thread of their own, ``--trace-seconds`` of them profiled
from the main thread, ``run.TRACE_DELAY_S`` in).  Prints one JSON object
(and writes it to ``--out``):

* ``step_s``: median, 95th percentile and count of one step (a query or
  a sweep): untraced, from the host clock; traced, the benchmark's step
  spans in the trace; so the tracing's cost per step;
* ``idle_gaps``: the traced stretch's ten longest device idle gaps, each
  named by the innermost span holding it, ``chipbench.*`` or
  ``repro.*``;
* ``held_pct``: per benchmark span name, the share of the device idle
  time inside those spans that a program span holds;
* ``per_call_ms``: per top program span (``repro.run_grid``,
  ``repro.optimize``), the mean milliseconds of each span name in one
  call;
* ``program_ms``: per device program (``jit_run_stats``,
  ``jit_newton_policy``, ...), its runs in the traced stretch and their
  mean device milliseconds;
* ``slowest``: for the steps at or above the 95th percentile of the
  traced stretch and for the median step, the milliseconds of each
  program span inside it.

A tool for reading a cell, not one of its runs: it prints no metric the
benchmark compares.
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import time
from dataclasses import replace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def timed_steps(driver, seconds: float) -> list:
    out = []
    t_end = time.monotonic() + seconds
    while time.monotonic() < t_end:
        t0 = time.monotonic()
        driver.step()
        out.append(time.monotonic() - t0)
    return out


def summary(steps: list) -> dict:
    from chipbench.bench import percentile

    return {"median": statistics.median(steps),
            "p95": percentile(steps, 95), "count": len(steps)}


def inside(spans, s0: float, e0: float) -> dict:
    """Milliseconds per program span name within ``[s0, e0]``."""
    out = {}
    for name, s, d, _ in spans:
        if s >= s0 and s + d <= e0:
            out[name] = out.get(name, 0.0) + d * 1e-6
    return out


def report(workload: str, seed: int, seconds: float, trace_s: float) -> dict:
    from chipbench import bench, load, program_trace, run, trace_reduce

    b = bench.load_benchmark(ROOT)
    wl = bench.find_workload(b, workload)
    cfg = bench.load_config(wl["config"], ROOT)
    traffic = bench.load_traffic(wl["traffic"], ROOT)
    # the set-up of run.run_cell, which keeps it inline
    cache = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run.check_devices(wl["chips"])

    driver = load.make(cfg, traffic, seed, ROOT)
    driver.warm()
    driver.reset()
    plain = timed_steps(driver, seconds)
    trace_dir = os.path.join(ROOT, ".chipbench", f"gaps-{workload}-{seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    run.traced_window(
        lambda: timed_steps(driver, run.TRACE_DELAY_S + trace_s + 1.0),
        trace_dir, run.TRACE_DELAY_S, trace_s,
    )
    path = trace_reduce.find_xplane(trace_dir)
    # the reduction keeps one host line per line name, and the loop
    # thread's line has the main thread's name: read every thread's
    # benchmark spans here
    tr = replace(trace_reduce.load(path), spans=[
        s[:3] for s in program_trace.read_spans(path, trace_reduce.SPAN_PREFIX)
        if s[0] != trace_reduce.WINDOW_SPAN
    ])
    spans = program_trace.read_spans(path)
    shutil.rmtree(trace_dir, ignore_errors=True)

    outer = sorted({name for name, _, _ in tr.spans})
    tops = ("repro.run_grid", "repro.optimize")
    per_call = {}
    for top in tops:
        cs = program_trace.calls(spans, top)
        names = sorted({k for c in cs for k in c})
        per_call[top] = {
            k: 1e-6 * sum(c[k] for c in cs) / len(cs) for k in names
        }
    # the benchmark's outermost span per step: chipbench.query or .sweep
    steps = sorted(
        (d, s) for name, s, d in tr.spans
        if name in ("chipbench.query", "chipbench.sweep")
    )
    slowest = []
    if steps:
        p95 = bench.percentile([d for d, _ in steps], 95)
        mid = steps[len(steps) // 2]
        for d, s in [mid] + [x for x in steps if x[0] >= p95]:
            slowest.append({"step_ms": d * 1e-6,
                            "spans_ms": inside(spans, s, s + d)})
    programs = {}
    for evs in tr.modules.values():
        for name, _, d in evs:
            programs.setdefault(name.split("(")[0], []).append(d * 1e-6)
    traced = [d * 1e-9 for d, _ in steps]
    return {
        "workload": workload, "seed": seed,
        "step_s": {"untraced": summary(plain),
                   "traced": summary(traced) if traced else None},
        "idle_s": tr.window_s - tr.busy_s(), "window_s": tr.window_s,
        "idle_gaps": [[n, v] for n, v in
                      program_trace.gap_names(tr, spans)[:10]],
        "held_pct": {o: program_trace.held_share(tr, spans, o)
                     for o in outer},
        "per_call_ms": per_call,
        "program_ms": {k: {"runs": len(v), "mean": sum(v) / len(v)}
                       for k, v in programs.items()},
        "slowest": slowest,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace-seconds", type=float, default=4.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    out = report(args.workload, args.seed, args.seconds, args.trace_seconds)
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
