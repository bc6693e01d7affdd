"""The window deployment's cell (``window-sweep``): the plain reference
for windows (``chipbench/references/s5window.py``) against the program's
scalar model, the kind ``window_sweep`` through ``run_cell`` on the CPU
in a checkout cut to one platform size and one predictor (a sound run
correct, a planted fault and the control not correct, a program without
the window-ordered buffer refused), the buffer's two readers, and the
four-chip sweep's mix."""

import json
import math
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import bench  # noqa: E402

CONFIG = "s5-window-exp"
CELL = "window-sweep"
#: one platform size (the one with the most overlapping windows) and one
#: predictor: 3 cells
SMALL_CONFIG = {"N": [524288], "predictors": [
    {"name": "p82r85", "precision": 0.82, "recall": 0.85}]}
REFERENCE_RUNS = 300
SEED = 2**31 + 4242


def _ref():
    return bench.load_reference("s5window")


# --------------------------------------------------------------------------- #
# The plain reference
# --------------------------------------------------------------------------- #
def test_window_reference_matches_the_scalar_engine_run_by_run():
    """Same traces, same answers: the reference restates the program's
    Section 4 episodes (it imports none of them; this test imports
    both), and computes the program's periods itself."""
    from repro.core import simulator as S
    from repro.core.events import EventTrace, FaultEvent, PredictionEvent
    from repro.core.waste import Platform, PredictorModel

    ref = _ref()
    cfg = bench.load_config(CONFIG, ROOT)
    I, W = cfg["window_s"], cfg["work_s"]
    rng = np.random.default_rng(11)
    for n in (2**14, 2**19):
        mu = cfg["mu_ind_s"] / n
        plat = Platform(mu=mu, C=600.0, D=60.0, R=600.0, M=300.0)
        for p, r in ((0.82, 0.85), (0.4, 0.7)):
            pm = PredictorModel(r, p, lead=3600.0, window=I)
            T_P = ref.proactive_period(600.0, p, I)
            for name in ("instant", "nockpt", "withckpt"):
                strat = getattr(S, name)(plat, pm)
                assert ref.period(name, 600.0, mu, r) == pytest.approx(
                    strat.T_R, rel=1e-15)
                if name == "withckpt":
                    assert T_P == pytest.approx(strat.T_P, rel=1e-15)
                for _ in range(4):
                    faults, preds = ref.trace(rng, "exponential", 1.0, mu, r,
                                              p, 12 * W, I)
                    tr = EventTrace(
                        horizon=12 * W,
                        faults=[FaultEvent(f) for f in faults],
                        predictions=[
                            PredictionEvent(t0=t, window=I, fault_time=f,
                                            lead=3600.0)
                            for t, f in preds
                        ],
                    )
                    want = S.simulate(W, plat, strat, tr)
                    got = ref._Job(W, 600.0, 60.0, 600.0, 300.0, strat.T_R,
                                   name, faults, preds, True, I,
                                   T_P).run(12 * W)
                    assert got[1] == pytest.approx(want.makespan, rel=1e-12)
                    assert got[2:6] == (
                        want.n_faults, want.n_regular_ckpts,
                        want.n_proactive_ckpts, want.n_migrations,
                    )


@pytest.mark.parametrize("C,p,I", [
    (600.0, 0.82, 6000.0), (600.0, 0.4, 6000.0), (600.0, 0.82, 1200.0),
    (600.0, 0.95, 650.0), (600.0, 0.5, 300.0), (60.0, 0.3, 5000.0),
])
def test_window_reference_proactive_period_is_the_integer_partition(C, p, I):
    from repro.core import periods as P

    want = P._t_p_opt(C, p, I)
    got = _ref().proactive_period(C, p, I)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want[0], rel=1e-15)
        assert got >= C and math.isclose(I / got, round(I / got))


def test_window_traces_are_in_window_order():
    ref = _ref()
    rng = np.random.default_rng(5)
    mu, I = 3944700000.0 / 2**19, 6000.0
    overtakes = 0
    for _ in range(20):
        faults, preds = ref.trace(rng, "exponential", 1.0, mu, 0.85, 0.82,
                                  1e6, I)
        t0 = [t for t, _ in preds]
        assert t0 == sorted(t0)
        for t, f in preds:
            if f is not None:
                assert max(0.0, f - I) <= t <= f
        ft = [f for _, f in preds if f is not None]
        overtakes += sum(b < a for a, b in zip(ft, ft[1:]))
    assert overtakes > 0  # windows wide enough to reorder some faults


# --------------------------------------------------------------------------- #
# The cell through run_cell, on the CPU
# --------------------------------------------------------------------------- #
def _edit_json(path, changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark cut to 3 cells of 256 runs a sweep, a
    BENCHMARK.json naming the window cell alone, and the CPU's device
    kind in the peaks table."""
    path = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(path, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    cb = os.path.join(path, "chipbench")
    _edit_json(os.path.join(cb, "configs", f"{CONFIG}.json"), SMALL_CONFIG)
    _edit_json(os.path.join(cb, "traffic", "window_sweep.json"),
               {"lanes_per_sweep": 768})
    with open(os.path.join(cb, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    with open(os.path.join(cb, "peaks.json"), "w") as f:
        json.dump(peaks, f)
    b = bench.load_benchmark(ROOT)
    b["workloads"] = [bench.find_workload(b, CELL)]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w == CELL]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return path


@pytest.fixture
def on_cpu(monkeypatch):
    import jax

    from chipbench import compare
    from chipbench import run as R
    from chipbench.references import s5window
    from repro.core import jax_sim

    monkeypatch.setattr(R, "check_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(s5window, "WORKERS", 1)
    monkeypatch.setattr(compare, "REFERENCE_RUNS", REFERENCE_RUNS)
    jax_sim._RUN_CACHE.clear()
    yield
    jax_sim._RUN_CACHE.clear()


def _run(root):
    from chipbench import run as R

    return R.run_cell(CELL, SEED, 0.5, False, root=root)


def test_sound_window_run_is_correct(root, on_cpu):
    from repro.core import jax_sim

    out = _run(root)
    assert out["correct"], out["checks"]
    assert out["window_compiles"] == 0
    assert set(out["metrics"]) == {"runs_per_s", "setup_s"}
    # the window's last sweep overtook, and nothing overflowed
    assert jax_sim.LAST_TIMINGS["tp_overtakes"].sum() > 0
    assert jax_sim.LAST_TIMINGS["tp_overflow"].sum() == 0


def test_window_run_with_an_altered_answer_is_not_correct(root, on_cpu,
                                                          monkeypatch):
    from repro.core import jax_sim

    real = jax_sim.CellSums.from_matrix.__func__

    def altered(cls, cs):
        cs = np.array(cs, copy=True)
        cs[:, 6] *= 1.2  # proactive checkpoints, as produced
        return real(cls, cs)

    monkeypatch.setattr(jax_sim.CellSums, "from_matrix", classmethod(altered))
    out = _run(root)
    assert not out["correct"]
    z = out["checks"]["max_abs_z"]
    assert z["value"] > z["limit"]


def test_a_program_without_the_window_buffer_is_refused(root, on_cpu,
                                                        monkeypatch):
    from repro.core import jax_sim

    monkeypatch.delattr(jax_sim, "WINDOW_TP_SLOTS")
    with pytest.raises(bench.BenchError, match="window-ordered buffer"):
        _run(root)


def test_window_control_is_not_correct(root, monkeypatch):
    from chipbench import compare, control
    from chipbench.references import s5window

    monkeypatch.setattr(s5window, "WORKERS", 1)
    monkeypatch.setattr(compare, "REFERENCE_RUNS", 1000)
    cfg = bench.load_config(CONFIG, root)
    traffic = bench.load_traffic("window_sweep", root)
    out = control.control_checks(cfg, traffic, SEED, root, n_ctrl=1000)
    assert not out["correct"], out["checks"]
    z = out["checks"]["max_abs_z"]
    assert z["value"] > z["limit"]


# --------------------------------------------------------------------------- #
# The buffer's readers, and the four-chip mix
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name,want", [
    ("tp_overtakes.window", 1 + 2 + 30 + 4),
    ("tp_pending_peak.window", 6.0),
])
def test_buffer_readers_read_the_last_call(monkeypatch, name, want):
    from repro.core import jax_sim

    monkeypatch.setattr(jax_sim, "LAST_TIMINGS", {
        "tp_overtakes": np.array([[1, 2], [30, 4]], np.int64),
        "tp_pending_peak": np.array([[3, 6], [5, 4]], np.int64),
    })
    assert bench.load_module("metrics", name, ROOT).read({}) == want


@pytest.mark.parametrize("name", ["tp_overtakes.window",
                                  "tp_pending_peak.window"])
@pytest.mark.parametrize("timings", [
    {}, {"loop_iters": np.array([[100]], np.int64)},
    {"tp_overtakes": np.zeros((0, 1), np.int64),
     "tp_pending_peak": np.zeros((0, 1), np.int64)},
], ids=["empty", "no-buffer-counts", "no-chunks"])
def test_buffer_readers_give_nothing_without_a_count(monkeypatch, name,
                                                     timings):
    from repro.core import jax_sim

    monkeypatch.setattr(jax_sim, "LAST_TIMINGS", timings)
    assert bench.load_module("metrics", name, ROOT).read({}) is None


def test_four_chip_mix_is_the_sweep_at_a_sweep_a_chip():
    one = bench.load_traffic("sweep", ROOT)
    four = bench.load_traffic("sweep_4chip", ROOT)
    b = bench.load_benchmark(ROOT)
    assert bench.find_workload(b, "exact-sweep-4chip")["chips"] == 4
    eng = dict(four["engine"])
    chunk = eng.pop("chunk_lanes")
    assert eng == dict(one["engine"], devices="all")
    assert four["lanes_per_sweep"] == 4 * one["lanes_per_sweep"]
    # a sweep is whole chunks: no ragged last chunk padded to full size
    assert four["lanes_per_sweep"] % chunk == 0
    assert four["kind"] == one["kind"] == "sweep"
