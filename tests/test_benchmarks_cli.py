"""Benchmark harness CLI + regression-gate unit coverage.

The ``--only`` validation must fail fast (before any benchmark module —
and hence jax — is imported), and the regression gate's comparison logic
is pure, so both are cheap to test."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


def _run_cli(*args, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )


def test_run_only_unknown_name_exits_nonzero():
    proc = _run_cli("--only", "definitely_not_a_benchmark")
    assert proc.returncode != 0
    err = proc.stderr + proc.stdout
    assert "definitely_not_a_benchmark" in err
    assert "waste_curves" in err  # the message lists the valid names


def test_run_only_unknown_name_writes_nothing(tmp_path):
    out = tmp_path / "should_not_exist.json"
    proc = _run_cli("--only", "nope", "--json", str(out))
    assert proc.returncode != 0
    assert not out.exists()


# ---------------------------------------------------------------------- #
# regression-gate comparison logic
# ---------------------------------------------------------------------- #
def _rec(name, **derived):
    return {"name": name, "us_per_call": 1.0, "derived": derived}


def test_compare_passes_on_identical_records():
    from benchmarks.check_regression import compare

    recs = [
        _rec("fig4/a", waste_pred_sim=0.05, waste_pred_capped=0.06),
        _rec("jax_engine/lanes1024", jax_lanes_per_s=20000.0,
             numpy_lanes_per_s=15000.0, max_abs_waste_diff=1e-15),
    ]
    assert compare(recs, recs) == []


def test_compare_flags_analytic_gap_and_drift():
    from benchmarks.check_regression import compare

    base = [_rec("fig4/a", waste_pred_sim=0.05, waste_pred_capped=0.06)]
    gap = [_rec("fig4/a", waste_pred_sim=0.30, waste_pred_capped=0.06)]
    fails = compare(base, gap)
    assert any("analytic-vs-sim" in f for f in fails)
    assert any("drifted" in f for f in fails)
    # small jitter within both tolerances passes
    ok = [_rec("fig4/a", waste_pred_sim=0.055, waste_pred_capped=0.06)]
    assert compare(base, ok) == []


def test_compare_flags_throughput_regression():
    from benchmarks.check_regression import compare

    base = [_rec("jax_engine/lanes1024", jax_lanes_per_s=20000.0)]
    slow = [_rec("jax_engine/lanes1024", jax_lanes_per_s=10000.0)]
    fails = compare(base, slow)
    assert len(fails) == 1 and "regressed" in fails[0]
    assert compare(base, slow, perf_tol=0.0) == []  # gate disabled
    within = [_rec("jax_engine/lanes1024", jax_lanes_per_s=15000.0)]
    assert compare(base, within) == []  # -25% is inside the 30% budget


def test_compare_flags_engine_disagreement():
    from benchmarks.check_regression import compare

    base = [_rec("jax_engine/lanes1024", max_abs_waste_diff=1e-15)]
    bad = [_rec("jax_engine/lanes1024", max_abs_waste_diff=1e-3)]
    fails = compare(base, bad)
    assert len(fails) == 1 and "jax-vs-numpy" in fails[0]


def test_compare_ignores_new_and_removed_names():
    from benchmarks.check_regression import compare

    base = [_rec("old/gone", jax_lanes_per_s=1.0)]
    fresh = [_rec("new/added", jax_lanes_per_s=1.0)]
    assert compare(base, fresh) == []


def test_check_regression_cli_missing_baseline(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.check_regression",
         "--baseline-dir", str(tmp_path), "--out-dir",
         str(tmp_path / "fresh")],
        capture_output=True, text=True, timeout=60, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "missing baseline" in proc.stdout


@pytest.mark.slow
def test_check_regression_cli_passes_on_committed_baselines(tmp_path):
    """End-to-end gate run against the repo's committed BENCH_*.json:
    must pass (and write fresh artifact records) on a healthy tree.
    Restricted to the seeded waste_curves module so the test stays fast;
    the CI bench-regression job runs the full gate."""
    out = tmp_path / "fresh"
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(REPO, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.check_regression",
         "--baseline-dir", REPO, "--out-dir", str(out),
         "--modules", "waste_curves",
         "--perf-tol", "0"],  # perf floors need comparable hardware
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    fresh = json.loads((out / "BENCH_sim.waste_curves.json").read_text())
    assert fresh["benchmarks"], "no fresh waste_curves records written"


def test_run_profile_help_and_unknown_name_precedence():
    """--only validation fails fast before any module import, and the
    removed --profile option (a trace nothing read) is refused."""
    proc = _run_cli("--only", "nope")
    assert proc.returncode != 0
    assert "nope" in proc.stderr + proc.stdout
    proc = _run_cli("--only", "sim_tables", "--profile")
    assert proc.returncode == 2
    assert "--profile" in proc.stderr


def test_compare_flags_device_trace_floor():
    from benchmarks.check_regression import compare

    base = [_rec("jax_engine/device_trace_lanes40960",
                 jax_dev_lanes_per_s=20000.0)]
    fresh = [_rec("jax_engine/device_trace_lanes40960",
                  jax_dev_lanes_per_s=10000.0)]
    fails = compare(base, fresh, perf_tol=0.30)
    assert len(fails) == 1 and "jax_dev_lanes_per_s" in fails[0]
    assert compare(base, fresh, perf_tol=0.0) == []


def test_compare_flags_fused_grid_floor_and_equality():
    """The fused-sweep record is gated on both axes: cells/sec within
    the perf tolerance of the baseline (and the tolerance flags apply),
    and exact fused-vs-percell per-cell agreement."""
    from benchmarks.check_regression import compare

    base = [_rec("jax_engine/fused_grid_cells72",
                 fused_cells_per_s=50.0, fused_vs_percell_max_diff=0.0)]
    slow = [_rec("jax_engine/fused_grid_cells72",
                 fused_cells_per_s=20.0, fused_vs_percell_max_diff=0.0)]
    fails = compare(base, slow, perf_tol=0.30)
    assert len(fails) == 1 and "fused_cells_per_s" in fails[0]
    assert compare(base, slow, perf_tol=0.0) == []  # tolerance flag applies
    assert compare(base, slow, perf_tol=0.70) == []
    split = [_rec("jax_engine/fused_grid_cells72",
                  fused_cells_per_s=50.0, fused_vs_percell_max_diff=1e-4)]
    fails = compare(base, split, perf_tol=0.30)
    assert len(fails) == 1 and "fused-vs-percell" in fails[0]
    assert compare(base, split, agree_tol=1e-3) == []
