"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: check the devices (TPU, as many chips as the cell asks for;
there is no CPU fallback), build the cell's inputs from its configuration
file and ``--seed``, warm up the cell's own shapes, measure for
``--seconds`` in a closed loop with one client (the window ends at a
sweep or query boundary), then compare what the window produced with the
plain reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1``: ``busy_s``, ``window_s`` and a ``breakdown``), and last
``checks``, each number compared beside its limit.  The same numbers end
standard error.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of a
short stretch of the window and from the host clock.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the compile stages JAX reports through its monitoring events; a backend
#: compile event also covers an executable loaded from the persistent cache
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"

#: seconds into the window at which a traced run starts its trace
TRACE_DELAY_S = 1.0


class CompileCounter:
    """Counts executables built or loaded, with the seconds of tracing,
    lowering and backend compilation, and persistent-cache hits and
    misses."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        self.compile_s = self.trace_s = self.lower_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += secs
        elif name == TRACE:
            self.trace_s += secs
        elif name == LOWER:
            self.lower_s += secs

    def _event(self, name, **_):
        if name == CACHE_HITS:
            self.hits += 1
        elif name == CACHE_MISSES:
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "trace_s": self.trace_s, "lower_s": self.lower_s,
                "cache_hits": self.hits, "cache_misses": self.misses}


def traced_window(loop, directory: str, delay: float, span: float):
    """Run ``loop()`` in a thread of its own and profile ``span`` seconds of
    it, starting ``delay`` seconds in: the window's calls are too long to
    trace whole.  The profiler is driven from this (the main) thread, where
    it records the loop thread's spans; returns what ``loop`` returned."""
    import jax

    from chipbench import trace_reduce

    out = {}

    def body():
        try:
            out["value"] = loop()
        except BaseException as e:  # re-raised in the calling thread
            out["error"] = e

    th = threading.Thread(target=body, daemon=True)
    th.start()
    time.sleep(delay)
    jax.profiler.start_trace(directory)
    # the span marks the traced window on the trace's own clock
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        time.sleep(span)
    jax.profiler.stop_trace()
    th.join()
    if "error" in out:
        raise out["error"]
    return out["value"]


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def check_devices(chips: int):
    """The cell's chips: a TPU, with at least ``chips`` devices."""
    import jax

    from chipbench import bench

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise bench.BenchError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise bench.BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT) -> dict:
    """Run one cell of the benchmark in checkout ``root``; returns the
    result object."""
    from chipbench import bench, compare, load

    b = bench.load_benchmark(root)
    wl = bench.find_workload(b, workload)
    cfg = bench.load_config(wl["config"], root)
    traffic = bench.load_traffic(wl["traffic"], root)
    ref = bench.load_reference(cfg["reference"])
    e2e_names = [m["name"] for m in bench.cell_metrics(b, workload, "end_to_end")]
    layer_names = [m["name"] for m in bench.cell_metrics(b, workload, "per_layer")]

    # the persistent compile cache lives at one fixed path in the checkout
    cache = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    devs = check_devices(wl["chips"])
    peaks = bench.peaks(devs[0].device_kind, root)
    if traffic["engine"].get("devices") != "all":
        devs = devs[:1]

    counter = CompileCounter()
    driver = load.make(cfg, traffic, seed, root)
    driver.warm()
    if driver.failed:
        raise bench.BenchError("the warm-up failed")
    driver.reset()
    setup = counter.snapshot()
    setup_s = time.monotonic() - T0
    say(f"setup: {setup_s:.3f} s; executables built or loaded "
        f"{setup['compiles']} (backend {setup['compile_s']:.3f} s, tracing "
        f"{setup['trace_s']:.3f} s, lowering {setup['lower_s']:.3f} s); "
        f"persistent cache hits {setup['cache_hits']}, misses "
        f"{setup['cache_misses']}")

    def window():
        t0 = time.monotonic()
        while True:
            driver.step()
            if time.monotonic() - t0 >= seconds:
                return time.monotonic() - t0

    trace_dir = os.path.join(root, ".chipbench", f"trace-{workload}-{seed}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        window_s = traced_window(
            window, trace_dir, TRACE_DELAY_S, float(traffic["trace_span_s"])
        )
    else:
        window_s = window()
    after = counter.snapshot()
    in_window = after["compiles"] - setup["compiles"]
    say(f"window: {window_s:.3f} s, {driver.done} done, {driver.failed} "
        f"failed, executables built or loaded in the window {in_window}")
    device = device_info(devs)

    metrics, breakdown = {}, None
    if not trace:
        vals = dict(driver.e2e(window_s), setup_s=setup_s)
        units = {m["name"]: m["unit"] for m in b["end_to_end"]}
        for name in e2e_names:
            metrics[name] = {"value": vals[name], "unit": units[name]}
    else:
        from chipbench import trace_reduce

        tr = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        ctx = {"layer": driver.layer(), "trace": tr, "peaks": peaks,
               "cfg": cfg, "traffic": traffic, "devices": devs}
        units = {m["name"]: m["unit"] for m in b["per_layer"]}
        for name in layer_names:
            v = bench.load_module("metrics", name, root).read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}
        ops = sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])
        breakdown = {
            "device_ops": [[k, v] for k, v in ops[:10]],
            "idle_gaps": [[k, v] for k, v in tr.idle_gaps()[:10]],
        }

    # the reference runs once the program's state is freed
    attempted = driver.done + driver.failed
    failed = driver.failed
    gc.collect()
    jax.clear_caches()
    t_ref = time.monotonic()
    res = driver.check(ref, seed)
    checks = compare.checks(res["numbers"])
    correct = compare.passed(checks) and failed == 0 and driver.done > 0
    say(f"reference: {time.monotonic() - t_ref:.3f} s; worst z at "
        f"{res['worst'][0]} {res['worst'][1]}: {res['worst'][2]:+.3f}")
    return {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device, "breakdown": breakdown,
        "checks": checks, "window_compiles": in_window,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        say("chipbench: the program (src/repro) is not in this checkout")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from chipbench import bench

    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except bench.BenchError as e:
        say(f"chipbench: {e}")
        return 1
    for line in bench.check_lines(out["checks"]):
        say(line)
    print(bench.result_line(
        out["correct"], out["attempted"], out["failed"], out["metrics"],
        out["device"], out["checks"], out["breakdown"],
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
