"""The chip benchmark's own machinery, on the CPU: loading by name, the
arithmetic of its metrics, the trace reduction, the kernel byte count,
the result line, the plain reference, and the refusal to run without a
TPU.  Nothing here touches a TPU."""

import json
import math
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import bench, cells, compare, load, trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def benchmark():
    return bench.load_benchmark(ROOT)


# --------------------------------------------------------------------------- #
# BENCHMARK.json and the files it names
# --------------------------------------------------------------------------- #
def test_every_named_file_is_found_by_name(benchmark):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import simulator as S

    for w in benchmark["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        cfg = bench.load_config(w["config"], ROOT)
        assert cfg["name"] == w["config"]
        bench.load_reference(cfg["reference"])
        for s in cfg["strategies"]:
            assert callable(getattr(S, s["factory"]))
        traffic = bench.load_traffic(w["traffic"], ROOT)
        mod = load.kind(traffic, ROOT)
        assert callable(mod.Load) and callable(mod.control_numbers)
    for c in benchmark["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) <= set(bench.load_config(c["name"], ROOT))
    for m in benchmark["per_layer"]:
        assert callable(bench.load_module("metrics", m["name"], ROOT).read)


def test_each_cell_reports_what_its_layer_metrics_move(benchmark):
    e2e = {m["name"]: m for m in benchmark["end_to_end"]}
    cells_ = [w["name"] for w in benchmark["workloads"]]
    for w in cells_:
        names = [m["name"] for m in bench.cell_metrics(benchmark, w, "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        assert bench.cell_metrics(benchmark, w, "per_layer")
    for m in benchmark["per_layer"]:
        target = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in target.get("workloads", cells_)


NEW_KIND = """
from chipbench.load import engine


class Load:
    def __init__(self, cfg, traffic, seed):
        self.engine = engine(traffic)
        self.sweeps = traffic["sweeps"]


def control_numbers(cfg, traffic, seed, n_ctrl, ref):
    return {"numbers": {}, "worst": ("-", "-", 0.0)}
"""


def test_a_new_metric_is_found_by_name_without_editing_a_file(tmp_path):
    """A later PR adds a configuration, a traffic mix of a known kind, a
    traffic kind and a metric: new files and BENCHMARK.json entries."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cb = root / "chipbench"
    cfg = bench.load_config("s5-exact-exp", ROOT)
    cfg.update(name="s5-exact-small", N=[16384])
    (cb / "configs" / "s5-exact-small.json").write_text(json.dumps(cfg))
    mix = dict(bench.load_traffic("sweep", ROOT), lanes_per_sweep=72)
    mix["engine"] = dict(mix["engine"], trace_mode="host")
    (cb / "traffic" / "sweep-hosttrace.json").write_text(json.dumps(mix))
    (cb / "traffic" / "campaign.py").write_text(NEW_KIND)
    (cb / "traffic" / "campaign.json").write_text(json.dumps(
        {"kind": "campaign", "engine": {"engine": "jax"}, "sweeps": 3}))
    (cb / "metrics" / "sweeps_done.sweep.py").write_text(
        "def read(ctx):\n    return float(len(ctx['layer']['pack_s']))\n"
    )
    b = bench.load_benchmark(ROOT)
    b["configs"].append({"name": "s5-exact-small", "source": "x",
                         "file": "chipbench/configs/s5-exact-small.json",
                         "reduced": ["N"], "why": "test"})
    b["workloads"] += [
        {"name": "small-hosttrace", "config": "s5-exact-small",
         "traffic": "sweep-hosttrace", "chips": 1, "why": "test"},
        {"name": "small-campaign", "config": "s5-exact-small",
         "traffic": "campaign", "chips": 1, "why": "test"},
    ]
    b["per_layer"].append({
        "name": "sweeps_done.sweep", "unit": "sweeps", "better": "higher",
        "source": "host_clock", "layer": "chunk pipeline",
        "moves": "runs_per_s", "workloads": ["small-hosttrace"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    b = bench.load_benchmark(str(root))
    names = [m["name"] for m in bench.cell_metrics(b, "small-hosttrace", "per_layer")]
    assert "sweeps_done.sweep" in names
    reader = bench.load_module("metrics", "sweeps_done.sweep", str(root))
    assert reader.read({"layer": {"pack_s": [0.1, 0.2]}}) == 2.0
    wl = bench.find_workload(b, "small-hosttrace")
    assert bench.load_config(wl["config"], str(root))["N"] == [16384]
    traffic = bench.load_traffic(wl["traffic"], str(root))
    assert load.engine(traffic).trace_mode == "host"
    assert load.kind(traffic, str(root)).__name__.endswith("traffic_sweep")
    new = load.make({}, bench.load_traffic("campaign", str(root)), 1, str(root))
    assert new.sweeps == 3 and new.engine.engine == "jax"


def test_unknown_names_are_errors(benchmark):
    with pytest.raises(bench.BenchError):
        bench.find_workload(benchmark, "no-such-cell")
    with pytest.raises(bench.BenchError):
        bench.load_config("no-such-config", ROOT)
    with pytest.raises(bench.BenchError):
        bench.load_module("metrics", "no_such_metric", ROOT)


def test_peaks_are_keyed_by_device_kind():
    p = bench.peaks("TPU v5 lite", ROOT)
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(bench.BenchError):
        bench.peaks("TPU v4", ROOT)


# --------------------------------------------------------------------------- #
# Arithmetic of the end-to-end metrics and the result line
# --------------------------------------------------------------------------- #
def test_rate_is_all_work_over_all_time():
    # three sweeps of unequal length: the rate is not a mean of rates
    runs, secs = [1000, 1000, 1000], [1.0, 2.0, 7.0]
    assert bench.rate(sum(runs), sum(secs)) == 300.0
    with pytest.raises(bench.BenchError):
        bench.rate(10, 0.0)


@pytest.mark.parametrize("q,expected", [(50, 100), (95, 190), (100, 200), (1, 2)])
def test_percentile_is_nearest_rank_over_all_samples(q, expected):
    samples = list(range(200, 0, -1))
    assert bench.percentile(samples, q) == expected


def test_result_line_schema():
    checks = compare.checks({"lanes_missing": 0.0, "max_abs_z": 2.5})
    line = bench.result_line(
        True, 12, 0, {"runs_per_s": {"value": 1.5, "unit": "runs/s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 1}, checks,
        {"device_ops": [["fusion", 0.1]], "idle_gaps": []},
    )
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"]
    assert out["checks"]["max_abs_z"] == {"value": 2.5, "limit": compare.LIMITS["max_abs_z"]}
    assert bench.check_lines(checks)[0] == "check lanes_missing: 0.0 limit 0"


def test_checks_pass_only_within_every_limit():
    ok = compare.checks({"lanes_missing": 0.0, "max_abs_z": 1.0})
    assert compare.passed(ok)
    assert not compare.passed(compare.checks({"lanes_missing": 1.0}))
    assert not compare.passed(compare.checks({"max_abs_z": math.nan}))


# --------------------------------------------------------------------------- #
# The comparison
# --------------------------------------------------------------------------- #
def _ref_runs(rng, n, mean, sd):
    x = rng.normal(mean, sd, n)
    return {k: x for k in compare.STATS} | {"exhausted": 0}


def test_z_score_uses_both_sample_sizes():
    rng = np.random.default_rng(0)
    ref = {"a": _ref_runs(rng, 400, 10.0, 2.0)}
    sd = float(ref["a"]["waste"].std(ddof=1))
    prog = compare.Sums()
    prog.add("a", 1600, dict.fromkeys(compare.STATS, 10.5))
    z = compare.z_scores(prog, ref)[("a", "waste")]
    want = (10.5 - ref["a"]["waste"].mean()) / (sd * math.sqrt(1 / 1600 + 1 / 400))
    assert z == pytest.approx(want)
    nums, worst = compare.mc_checks(prog, ref, {"a": 1600})
    assert nums["lanes_missing"] == 0.0 and worst[0] == "a"


def test_missing_runs_and_constant_statistics():
    ref = {"a": {k: np.full(50, 3.0) for k in compare.STATS} | {"exhausted": 0}}
    prog = compare.Sums()
    prog.add("a", 100, dict.fromkeys(compare.STATS, 3.0), exhausted=2)
    nums, _ = compare.mc_checks(prog, ref, {"a": 120})
    assert nums["lanes_missing"] == 20.0
    assert nums["lanes_unfinished"] == 2.0
    # a reference with no spread leaves nothing to scale a z by
    assert nums["max_abs_z"] == math.inf


def test_policy_error_takes_both_the_answer_and_its_period():
    err = compare.policy_rel_err(
        [(100.0, 1.0, 0.2)], [(100.0, 1.0, 0.2)], lambda i, T, q: 0.2 * (1 + 1e-9)
    )
    assert err == pytest.approx(1e-9)


# --------------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------------- #
def test_reference_matches_the_scalar_engine_run_by_run():
    """Same traces, same answers: the reference restates the program's
    scalar model (it imports none of it; this test imports both)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import simulator as S
    from repro.core.events import EventTrace, FaultEvent, PredictionEvent
    from repro.core.waste import Platform, PredictorModel

    ref = bench.load_reference("s5")
    cfg = bench.load_config("s5-exact-exp", ROOT)
    rng = np.random.default_rng(7)
    for n in (2**14, 2**19):
        mu = cfg["mu_ind_s"] / n
        plat = Platform(mu=mu, C=600.0, D=60.0, R=600.0, M=300.0)
        pm = PredictorModel(0.85, 0.82, lead=3600.0)
        for strat in (S.young(plat), S.exact_prediction(plat, pm),
                      S.migration(plat, pm)):
            for _ in range(8):
                faults, preds = ref.trace(rng, "weibull", 0.7, mu, 0.85, 0.82,
                                          12 * cfg["work_s"])
                tr = EventTrace(
                    horizon=12 * cfg["work_s"],
                    faults=[FaultEvent(f, predicted=any(p[1] == f for p in preds))
                            for f in faults],
                    predictions=[PredictionEvent(t0=t, fault_time=f, lead=3600.0)
                                 for t, f in preds],
                )
                want = S.simulate(cfg["work_s"], plat, strat, tr)
                got = ref._Job(cfg["work_s"], 600.0, 60.0, 600.0, 300.0,
                               strat.T_R, strat.mode, faults, preds,
                               True).run(12 * cfg["work_s"])
                assert got[1] == pytest.approx(want.makespan, rel=1e-12)
                assert got[2:6] == (want.n_faults, want.n_regular_ckpts,
                                    want.n_proactive_ckpts, want.n_migrations)


def test_reference_periods_and_optima():
    ref = bench.load_reference("s5")
    mu, C = 7519.0, 600.0
    assert ref.period("young", C, mu, 0.85) == pytest.approx(math.sqrt(2 * mu * C))
    T, q, w = ref.optimum("exact", C, 60.0, 600.0, 300.0, mu, 0.85, 0.82)
    assert q == 1.0 and T == pytest.approx(math.sqrt(2 * mu * C / 0.15))
    lo = ref.optimum("exact", C, 60.0, 600.0, 300.0, mu, 0.85, 0.82, np.float32)
    assert 0.0 < abs(lo[2] - w) / w < 1e-5


def test_reference_does_not_depend_on_its_worker_count():
    ref = bench.load_reference("s5")
    cfg = dict(bench.load_config("s5-exact-exp", ROOT), N=[2**19],
               predictors=[{"name": "p82r85", "precision": 0.82, "recall": 0.85}])
    cs = cells.reference_cells(cfg, ref)
    try:
        ref.WORKERS = 2
        a = ref.simulate(cs, 6, seed=3)
        ref.WORKERS = 1
        b = ref.simulate(cs, 6, seed=3)
    finally:
        ref.WORKERS = 0
    for lab in a:
        np.testing.assert_array_equal(a[lab]["makespan"], b[lab]["makespan"])


# --------------------------------------------------------------------------- #
# Trace reduction and the kernel's bytes
# --------------------------------------------------------------------------- #
def test_busy_union_window_and_gap_names():
    planes = [
        ("/device:TPU:0", {
            "XLA Ops": [("a", 100.0, 50.0), ("b", 120.0, 60.0), ("c", 300.0, 100.0)],
            "XLA Modules": [("jit_run_stats", 100.0, 300.0)],
        }),
        ("/host:CPU", {"python": [
            ("chipbench.trace_window", 50.0, 400.0),
            ("chipbench.sweep", 0.0, 1000.0),
            ("chipbench.run_grid", 180.0, 100.0),
            ("other", 0.0, 5.0),
        ]}),
    ]
    tr = trace_reduce.reduce_planes(planes)
    assert (tr.start_ns, tr.end_ns) == (50.0, 450.0)
    assert tr.busy_intervals("/device:TPU:0") == [[100.0, 180.0], [300.0, 400.0]]
    assert tr.busy_s() == pytest.approx(180e-9)
    gaps = tr.idle_gaps()
    assert gaps[0] == ("chipbench.run_grid", pytest.approx(120e-9))
    assert [g[0] for g in gaps[1:]] == ["chipbench.sweep", "chipbench.sweep"]
    assert tr.op_seconds()["c"] == pytest.approx(100e-9)


def test_idle_share_averages_over_devices():
    ops = {f"/device:TPU:{i}": {"XLA Ops": [("k", 0.0, 100.0 * (i + 1))]}
           for i in range(2)}
    host = ("/host:CPU", {"t": [("chipbench.trace_window", 0.0, 400.0)]})
    tr = trace_reduce.reduce_planes(list(ops.items()) + [host])
    read = bench.load_module("metrics", "device_idle_pct.sweep", ROOT).read
    assert read({"trace": tr}) == pytest.approx(100.0 * (1 - 150.0 / 400.0))


def test_step_kernel_bytes_from_shapes():
    k = bench.load_module("metrics", "step_kernel_roofline.sweep", ROOT)
    # x32, fused draw: 92 bytes a lane (16 operands, 7 results, 4 bytes each)
    assert k.step_kernel_bytes(16384, law_indexed=False) == 16384 * 92
    assert k.step_kernel_bytes(16384, law_indexed=True) == 16384 * 104
    assert k.step_kernel_bytes(1024, False, x64=True) == 1024 * (8 + 72 + 8 + 4 + 16 + 36 + 12)
    assert k.step_kernel_bytes(1024, False, fused_draw=False) == 1024 * (44 + 20)


@pytest.mark.parametrize("event,want", [
    (("body.12", 0.0, 1.0, ""), True),
    (("body.12", 0.0, 1.0, "custom-call | jit(run_stats)/while/body/pallas_call"), True),
    (("custom-call.3", 0.0, 1.0, "tpu_custom_call"), True),
    (("fusion.7", 0.0, 1.0, "loop fusion | jit(run_stats)/while/body/add"), False),
    (("while.2", 0.0, 1.0), False),
])
def test_step_kernel_is_found_by_what_the_trace_says(event, want):
    k = bench.load_module("metrics", "step_kernel_roofline.sweep", ROOT)
    assert k.is_kernel(event) is want


def test_roofline_share_of_a_known_call():
    k = bench.load_module("metrics", "step_kernel_roofline.sweep", ROOT)
    nbytes = k.step_kernel_bytes(16384, False)
    t_ns = nbytes / 819e9 * 1e9 * 4  # four times the least time
    tr = trace_reduce.reduce_planes([("/device:TPU:0", {"XLA Ops": [
        ("body.11", 0.0, t_ns, "custom-call | jit(run_stats)/while/body/pallas_call"),
        ("fusion.1", t_ns, 10.0, "loop fusion")]})])
    assert k.share(tr, nbytes, 819e9) == pytest.approx(25.0)
    assert k.share(trace_reduce.reduce_planes([]), nbytes, 819e9) is None


# --------------------------------------------------------------------------- #
# Without the chip, and without the program
# --------------------------------------------------------------------------- #
def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "exact-sweep",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_no_tpu_means_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path))
    assert r.returncode != 0
    assert not r.stdout.strip()
