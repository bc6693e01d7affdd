"""JAX device engine vs NumPy batch engine vs scalar oracle.

The three engines consume the *same* generated ``BatchTraces`` (trust
filtering is deterministic for q in {0, 1}), so makespans must agree to
float rounding across all five paper strategies + migration, the
exponential / Weibull / lognormal failure laws, and both trust settings.
Also covers the chunked lane scheduler, the ``run_grid(engine="jax")``
dispatch (the per-cell waste acceptance gate), and a hypothesis property
test randomizing platforms, laws, and strategies.
"""

import dataclasses
import math

import numpy as np
import pytest

from repro.core import (
    Platform,
    PredictorModel,
    make_event_traces_batch,
    simulate_batch,
)
from repro.core import events as E
from repro.core import jax_sim
from repro.core import simulator as S
from repro.core.jax_sim import simulate_batch_jax
from repro.core.simulator import Strategy, simulate

MN = 60.0
PLAT = Platform(mu=1000 * MN, C=10 * MN, D=1 * MN, R=10 * MN, M=5 * MN)
WORK = 20 * 86400.0
PREDW = PredictorModel(recall=0.85, precision=0.82, window=3000.0)
PRED = PredictorModel(recall=0.85, precision=0.82)
PRED0 = PredictorModel(0.0, 1.0)

#: scalar-vs-vectorized tolerance (fast-forward float fusion, see
#: tests/test_batch_sim.py); jax-vs-numpy agreement is far tighter
MK_TOL = 1e-3


def _strategies():
    return [
        (S.young(PLAT), PRED0),  # q = 0 baseline
        (S.exact_prediction(PLAT, PRED), PRED),
        (S.instant(PLAT, PREDW), PREDW),
        (S.nockpt(PLAT, PREDW), PREDW),
        (S.withckpt(PLAT, PREDW), PREDW),
        (S.migration(PLAT, PRED), PRED),
        # q = 0 with predictions present in the trace: the trust filter
        # must hide them identically in both vectorized engines
        (Strategy("Distrust", S.young(PLAT).T_R, q=0.0, mode="exact"), PRED),
    ]


def _traces_for(strat, pred, dist, n=4, seed=42):
    rng = np.random.default_rng(seed)
    return make_event_traces_batch(
        rng,
        n,
        horizon=12 * WORK,
        mtbf=PLAT.mu,
        recall=pred.recall if strat.mode != "none" else 0.0,
        precision=pred.precision,
        window=pred.window,
        lead=pred.lead,
        fault_dist=dist,
    )


@pytest.mark.parametrize(
    "dist",
    [E.exponential(), E.weibull(0.7), E.lognormal(1.0)],
    ids=["exp", "weibull0.7", "lognormal"],
)
def test_jax_matches_batch_and_scalar(dist):
    """Three-way equivalence on every strategy: jax-vs-numpy to float
    rounding (identical primitive sequence), both-vs-oracle to MK_TOL."""
    for strat, pred in _strategies():
        traces = _traces_for(strat, pred, dist)
        bj = simulate_batch_jax(WORK, PLAT, strat, traces)
        bn = simulate_batch(WORK, PLAT, strat, traces)
        np.testing.assert_allclose(
            bj.makespan, bn.makespan, rtol=1e-12, atol=1e-6,
            err_msg=f"{strat.name}/{dist.name}",
        )
        np.testing.assert_array_equal(bj.n_faults, bn.n_faults)
        np.testing.assert_array_equal(bj.n_regular_ckpts, bn.n_regular_ckpts)
        np.testing.assert_array_equal(
            bj.n_proactive_ckpts, bn.n_proactive_ckpts
        )
        np.testing.assert_array_equal(bj.n_migrations, bn.n_migrations)
        np.testing.assert_array_equal(bj.trace_exhausted, bn.trace_exhausted)
        for i in range(traces.n_lanes):
            sr = simulate(WORK, PLAT, strat, traces.lane(i))
            assert bj.lane(i).makespan == pytest.approx(
                sr.makespan, abs=MK_TOL
            ), (strat.name, dist.name, i)


def test_chunked_scheduling_matches_unchunked():
    """Chunk boundaries (including a ragged final chunk) are invisible."""
    strat, pred = S.instant(PLAT, PREDW), PREDW
    traces = _traces_for(strat, pred, E.exponential(), n=7, seed=3)
    whole = simulate_batch_jax(WORK, PLAT, strat, traces, chunk=None)
    chunked = simulate_batch_jax(WORK, PLAT, strat, traces, chunk=3)
    np.testing.assert_array_equal(whole.makespan, chunked.makespan)
    np.testing.assert_array_equal(whole.n_faults, chunked.n_faults)


def test_pallas_and_jnp_paths_agree():
    """The Pallas hot step (interpret mode on CPU) and the pure-jnp
    fallback share one body — results must be bit-identical."""
    strat, pred = S.withckpt(PLAT, PREDW), PREDW
    traces = _traces_for(strat, pred, E.weibull(0.7), n=4, seed=11)
    a = simulate_batch_jax(WORK, PLAT, strat, traces, use_pallas=True)
    b = simulate_batch_jax(WORK, PLAT, strat, traces, use_pallas=False)
    np.testing.assert_array_equal(a.makespan, b.makespan)
    np.testing.assert_array_equal(a.n_regular_ckpts, b.n_regular_ckpts)


def test_heterogeneous_lanes_jax():
    """Per-lane platforms/strategies in one device call."""
    plats = [PLAT, Platform(mu=400 * MN, C=5 * MN, D=1 * MN, R=5 * MN)]
    strats = [S.young(plats[0]), S.exact_prediction(plats[1], PRED)]
    rng = np.random.default_rng(11)
    traces = make_event_traces_batch(
        rng, 2, horizon=12 * WORK,
        mtbf=[p.mu for p in plats],
        recall=[0.0, PRED.recall],
        precision=[1.0, PRED.precision],
        window=0.0,
    )
    bj = simulate_batch_jax(WORK, plats, strats, traces)
    bn = simulate_batch(WORK, plats, strats, traces)
    np.testing.assert_allclose(bj.makespan, bn.makespan, rtol=1e-12, atol=1e-6)


def test_run_grid_jax_matches_batch():
    """Acceptance gate: per-cell mean waste of the jax engine agrees with
    the NumPy batch engine to <= 1e-6 (same traces, float-rounding-level
    per-lane agreement)."""
    from repro.experiments import ExperimentCell, GridSpec, run_grid

    cells = []
    for k in range(2):
        plat = Platform(mu=(500 + 500 * k) * MN, C=10 * MN, D=1 * MN, R=10 * MN)
        pred = PredictorModel(0.85, 0.82, window=300.0, lead=3600.0)
        dist = E.exponential() if k % 2 == 0 else E.weibull(0.7)
        for strat in (
            S.young(plat),
            S.exact_prediction(plat, PredictorModel(pred.recall, pred.precision)),
            S.instant(plat, pred),
            S.nockpt(plat, pred),
            S.withckpt(plat, pred),
        ):
            cells.append(
                ExperimentCell(
                    label=f"k{k}/{strat.name}",
                    work=6 * 86400.0,
                    platform=plat,
                    predictor=pred,
                    strategy=strat,
                    fault_dist=dist,
                )
            )
    grid = GridSpec(tuple(cells), n_runs=4, seed=17)
    sj = run_grid(grid, engine="jax")
    sb = run_grid(grid, engine="batch")
    assert sj.engine == "jax"
    for cj, cb in zip(sj.cells, sb.cells):
        assert abs(cj.mean_waste - cb.mean_waste) <= 1e-6, cj.cell.label
        np.testing.assert_allclose(cj.makespan, cb.makespan, rtol=1e-12)


def _n_devices() -> int:
    import jax

    return len(jax.devices())


@pytest.mark.parametrize("devices", [1, 2, 8])
def test_device_count_invariance(devices):
    """Sharded dispatch is invisible: per-lane makespans are *identical*
    (not just close) for any device count, and counters match exactly —
    including a ragged lane count (13) that leaves uneven final shards.

    With a single local device only devices=1 runs; the CI multi-device
    job forces 8 host devices so every count is exercised."""
    if devices > _n_devices():
        pytest.skip(f"needs {devices} devices, have {_n_devices()}")
    strat, pred = S.instant(PLAT, PREDW), PREDW
    traces = _traces_for(strat, pred, E.exponential(), n=13, seed=29)
    ref = simulate_batch_jax(WORK, PLAT, strat, traces, devices=1)
    got = simulate_batch_jax(WORK, PLAT, strat, traces, devices=devices)
    np.testing.assert_array_equal(got.makespan, ref.makespan)
    np.testing.assert_array_equal(got.n_faults, ref.n_faults)
    np.testing.assert_array_equal(got.n_regular_ckpts, ref.n_regular_ckpts)
    np.testing.assert_array_equal(
        got.n_proactive_ckpts, ref.n_proactive_ckpts
    )
    bn = simulate_batch(WORK, PLAT, strat, traces)
    np.testing.assert_allclose(
        got.makespan, bn.makespan, rtol=1e-12, atol=1e-6
    )


def test_mesh_dispatch_matches_devices():
    """mesh= is shorthand for devices= over the mesh's device set."""
    import jax

    mesh = jax.make_mesh((_n_devices(),), ("lanes",))
    strat, pred = S.instant(PLAT, PREDW), PREDW
    traces = _traces_for(strat, pred, E.exponential(), n=5, seed=31)
    ref = simulate_batch_jax(WORK, PLAT, strat, traces, devices=_n_devices())
    got = simulate_batch_jax(WORK, PLAT, strat, traces, mesh=mesh)
    np.testing.assert_array_equal(got.makespan, ref.makespan)


def test_devices_validation():
    strat, pred = S.instant(PLAT, PREDW), PREDW
    traces = _traces_for(strat, pred, E.exponential(), n=2, seed=1)
    with pytest.raises(ValueError, match="device"):
        simulate_batch_jax(WORK, PLAT, strat, traces, devices=4096)
    with pytest.raises(ValueError, match="not both"):
        simulate_batch_jax(WORK, PLAT, strat, traces, devices=1, mesh=object())
    with pytest.raises(ValueError, match="expected 'all'"):
        simulate_batch_jax(WORK, PLAT, strat, traces, devices="most")
    with pytest.raises(ValueError, match="engine"):
        S.simulate_many(
            WORK, PLAT, strat, pred, n_runs=2, engine="batch", devices=1
        )
    from repro.experiments import ExperimentCell, run_cells

    cell = ExperimentCell(
        label="x", work=WORK, platform=PLAT, predictor=pred, strategy=strat
    )
    with pytest.raises(ValueError, match="engine"):
        run_cells([cell], n_runs=2, engine="batch", devices=1)


@pytest.mark.slow
def test_sharded_invariance_subprocess():
    """1/2/8 forced-host-device invariance, guaranteed even on
    single-device hosts (the device count must be fixed before jax
    initializes, hence the subprocess)."""
    import os
    import subprocess
    import sys

    if _n_devices() >= 2:
        pytest.skip("multi-device process: covered in-process above")
    script = os.path.join(
        os.path.dirname(__file__), "_jax_sharded_check.py"
    )
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert "JAX_SHARDED_OK" in proc.stdout


@pytest.mark.slow
@pytest.mark.parametrize("from_env", [True, False])
def test_persistent_compilation_cache_env(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR, where set, receives the persistent
    compilation cache and the engine sets no directory of its own;
    unset, the cache lives at the one fixed path in the checkout.

    Subprocess: the cache directory must be configured before the jax
    backend initializes, which has long happened in the test process."""
    import os
    import subprocess
    import sys

    from repro.core.jax_sim import DEFAULT_CACHE_DIR

    cache = tmp_path / "jax-cache"
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    body = (
        "import jax\n"
        "import numpy as np\n"
        "from repro.core import Platform, PredictorModel, "
        "make_event_traces_batch\n"
        "from repro.core import simulator as S\n"
        "from repro.core.jax_sim import simulate_batch_jax\n"
        "plat = Platform(mu=60000.0, C=600.0, D=60.0, R=600.0)\n"
        "pred = PredictorModel(0.0, 1.0)\n"
        "tr = make_event_traces_batch(np.random.default_rng(0), 2, "
        "horizon=1e6, mtbf=plat.mu, recall=0.0, precision=1.0, window=0.0)\n"
        "simulate_batch_jax(86400.0, plat, S.young(plat), tr)\n"
        "print('CACHE_DIR=' + jax.config.jax_compilation_cache_dir)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", body], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr
    used = proc.stdout.split("CACHE_DIR=")[1].strip()
    want = str(cache) if from_env else DEFAULT_CACHE_DIR
    assert used == want
    assert os.path.isdir(want) and os.listdir(want), (
        "persistent compilation cache is empty"
    )
    if not from_env:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert want == os.path.join(repo, ".jax_cache")


def test_simulate_many_jax_engine():
    res_j = S.simulate_many(
        WORK, PLAT, S.exact_prediction(PLAT, PRED), PRED,
        n_runs=4, seed=3, engine="jax",
    )
    res_b = S.simulate_many(
        WORK, PLAT, S.exact_prediction(PLAT, PRED), PRED,
        n_runs=4, seed=3, engine="batch",
    )
    for j, b in zip(res_j, res_b):
        assert j.makespan == pytest.approx(b.makespan, abs=1e-6)
        assert j.n_faults == b.n_faults


# ---------------------------------------------------------------------- #
# randomized three-way agreement (hypothesis when available, otherwise a
# fixed seed sweep — a bare module-level importorskip would silently skip
# the deterministic equivalence tests above too)
# ---------------------------------------------------------------------- #
_LAWS = {
    "exp": E.exponential(),
    "weibull0.7": E.weibull(0.7),
    "lognormal": E.lognormal(1.0),
}


def _check_three_way(mu_mn, c_mn, law, mode, q, seed):
    """Randomized platform x law x strategy x q in {0,1}: the scalar
    oracle, the NumPy batch engine, and the JAX device engine agree on
    every lane's makespan."""
    plat = Platform(
        mu=mu_mn * MN, C=c_mn * MN, D=1 * MN, R=c_mn * MN, M=3 * MN
    )
    work = 6 * 86400.0
    t_r = max(plat.C * 1.5, math.sqrt(2 * plat.mu * plat.C))
    strat = Strategy("Rand", t_r, q=q, mode=mode,
                     T_P=max(plat.C, 1000.0) if mode == "withckpt" else None)
    rng = np.random.default_rng(seed)
    traces = make_event_traces_batch(
        rng, 2, horizon=12 * work, mtbf=plat.mu,
        recall=0.7 if mode != "none" else 0.0, precision=0.5,
        window=2000.0, fault_dist=_LAWS[law],
    )
    bj = simulate_batch_jax(work, plat, strat, traces)
    bn = simulate_batch(work, plat, strat, traces)
    np.testing.assert_allclose(bj.makespan, bn.makespan, rtol=1e-12, atol=1e-6)
    for i in range(traces.n_lanes):
        sr = simulate(work, plat, strat, traces.lane(i))
        assert bj.lane(i).makespan == pytest.approx(sr.makespan, abs=MK_TOL)
        assert bj.lane(i).n_faults == sr.n_faults


try:
    from hypothesis import given, settings, strategies as st
except ImportError:

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_three_way_makespan_agreement(seed):
        rng = np.random.default_rng(seed)
        _check_three_way(
            mu_mn=float(rng.uniform(400.0, 2000.0)),
            c_mn=float(rng.uniform(3.0, 15.0)),
            law=sorted(_LAWS)[seed % len(_LAWS)],
            mode=["none", "exact", "nockpt", "withckpt", "migration"][
                seed % 5
            ],
            q=float(seed % 2),
            seed=seed * 977,
        )

else:

    @settings(max_examples=10, deadline=None)
    @given(
        mu_mn=st.floats(400.0, 2000.0),
        c_mn=st.floats(3.0, 15.0),
        law=st.sampled_from(sorted(_LAWS)),
        mode=st.sampled_from(
            ["none", "exact", "nockpt", "withckpt", "migration"]
        ),
        q=st.sampled_from([0.0, 1.0]),
        seed=st.integers(0, 10_000),
    )
    def test_three_way_makespan_agreement(mu_mn, c_mn, law, mode, q, seed):
        _check_three_way(mu_mn, c_mn, law, mode, q, seed)


# ---------------------------------------------------------------------- #
# device trace generation (trace_mode="device" / TraceSpec)
# ---------------------------------------------------------------------- #
def _spec_for(strat, pred, dist, n=4, seed=42, window=None):
    return E.make_trace_spec(
        n,
        horizon=12 * WORK,
        mtbf=PLAT.mu,
        recall=pred.recall if strat.mode != "none" else 0.0,
        precision=pred.precision,
        window=pred.window if window is None else window,
        lead=pred.lead,
        fault_dist=dist,
        seed=seed,
    )


@pytest.mark.parametrize(
    "dist",
    [E.exponential(), E.weibull(0.7), E.lognormal(1.0)],
    ids=["exp", "weibull0.7", "lognormal"],
)
def test_device_gen_matches_host_engines_exact(dist):
    """Exact-date predictions (window=0): the device-generated run and
    the NumPy engine on the *materialized* replay of the same counter
    streams agree to float rounding — fault dates are bit-identical and
    the TP merge order coincides, so this pins the whole generation
    pipeline (keys, counters, transforms, trust, migration cancel)."""
    for strat, pred in [
        (S.young(PLAT), PRED0),
        (S.exact_prediction(PLAT, PRED), PRED),
        (S.migration(PLAT, PRED), PRED),
    ]:
        spec = _spec_for(strat, pred, dist, window=0.0)
        bn = simulate_batch(WORK, PLAT, strat, spec.materialize())
        bj = simulate_batch_jax(WORK, PLAT, strat, spec)
        np.testing.assert_allclose(
            bj.makespan, bn.makespan, rtol=1e-12, atol=1e-6,
            err_msg=f"{strat.name}/{dist.name}",
        )
        np.testing.assert_array_equal(bj.n_faults, bn.n_faults)
        np.testing.assert_array_equal(bj.n_regular_ckpts, bn.n_regular_ckpts)
        np.testing.assert_array_equal(
            bj.n_proactive_ckpts, bn.n_proactive_ckpts
        )
        np.testing.assert_array_equal(bj.n_migrations, bn.n_migrations)


def _agree_per_lane(bj, bn, context):
    np.testing.assert_allclose(
        bj.makespan, bn.makespan, rtol=1e-12, atol=1e-6, err_msg=context,
    )
    np.testing.assert_array_equal(bj.n_faults, bn.n_faults)
    np.testing.assert_array_equal(bj.n_regular_ckpts, bn.n_regular_ckpts)
    np.testing.assert_array_equal(bj.n_proactive_ckpts, bn.n_proactive_ckpts)
    np.testing.assert_array_equal(bj.n_migrations, bn.n_migrations)


def test_device_gen_window_statistical():
    """Prediction windows: the device engine keeps each lane's pending
    true positives in window-start order, the order of the host replay's
    time sort, so every lane agrees with the NumPy engine on the
    materialized streams to float rounding, as exact-date predictions
    do — lanes where a later fault's window opens first included."""
    for strat in (S.instant(PLAT, PREDW), S.nockpt(PLAT, PREDW),
                  S.withckpt(PLAT, PREDW)):
        spec = _spec_for(strat, PREDW, E.exponential(), n=64, seed=13)
        bn = simulate_batch(WORK, PLAT, strat, spec.materialize())
        bj = simulate_batch_jax(WORK, PLAT, strat, spec)
        assert jax_sim.LAST_TIMINGS["tp_overtakes"].sum() > 0, strat.name
        _agree_per_lane(bj, bn, strat.name)


@pytest.mark.parametrize(
    "dist", [E.weibull(0.7), E.lognormal(1.0)], ids=["weibull0.7", "lognormal"]
)
def test_device_gen_window_matches_host_replay_per_lane(dist):
    """Windows wider than the mean fault gap, so pending TPs pile up and
    overtake often, under the non-exponential laws too, and (at a third
    of that width) migration's cancel slots set out of fault order: still
    float-rounding agreement with the host replay, lane by lane."""
    wide = PredictorModel(recall=0.85, precision=0.6, window=1.5 * PLAT.mu,
                          lead=3600.0)
    mig = dataclasses.replace(wide, window=0.5 * PLAT.mu)
    for strat, pred in ((S.instant(PLAT, wide), wide),
                        (S.nockpt(PLAT, wide), wide),
                        (S.withckpt(PLAT, wide), wide),
                        (S.migration(PLAT, mig), mig)):
        spec = _spec_for(strat, pred, dist, n=24, seed=7)
        bn = simulate_batch(WORK, PLAT, strat, spec.materialize())
        bj = simulate_batch_jax(WORK, PLAT, strat, spec)
        t = jax_sim.LAST_TIMINGS
        assert t["tp_overtakes"].sum() > 0 and t["tp_pending_peak"].max() > 1
        assert t["tp_overflow"].sum() == 0
        _agree_per_lane(bj, bn, f"{strat.name}/{dist.name}")


def _fault_ordered(tr):
    """The host replay ``tr`` with each lane's true positives served in
    fault order instead (a one-slot cursor's order), merged with the
    false predictions by window start, ties to the TP."""
    t0, ft = tr.pred_t0.copy(), tr.pred_fault.copy()
    for i in range(tr.n_lanes):
        n = int(tr.n_preds[i])
        tp = [(a, b) for a, b in zip(t0[i, :n], ft[i, :n]) if not np.isnan(b)]
        fp = [(a, b) for a, b in zip(t0[i, :n], ft[i, :n]) if np.isnan(b)]
        tp.sort(key=lambda e: e[1])
        out = []
        while tp or fp:
            take_tp = tp and (not fp or tp[0][0] <= fp[0][0])
            out.append((tp if take_tp else fp).pop(0))
        t0[i, :n] = [e[0] for e in out]
        ft[i, :n] = [e[1] for e in out]
    return dataclasses.replace(tr, pred_t0=t0, pred_fault=ft)


def test_device_gen_window_serves_a_later_faults_earlier_window_first():
    """A seeded lane set in which later faults' windows open before
    earlier ones': the device engine counts those overtakes and serves
    the predictions in window-start order — its lanes are the replay's
    in that order, and some differ from the replay in fault order, the
    order a single pending slot would serve."""
    wide = PredictorModel(recall=0.9, precision=0.8, window=2.0 * PLAT.mu,
                          lead=3600.0)
    strat = S.nockpt(PLAT, wide)
    spec = _spec_for(strat, wide, E.exponential(), n=16, seed=31)
    tr = spec.materialize()

    def inverted(i):
        # TPs in the replay's window-start order, fault dates falling
        ft = tr.pred_fault[i, :tr.n_preds[i]]
        return (np.diff(ft[~np.isnan(ft)]) < 0).any()

    assert any(inverted(i) for i in range(tr.n_lanes))
    bj = simulate_batch_jax(WORK, PLAT, strat, spec)
    assert jax_sim.LAST_TIMINGS["tp_overtakes"].sum() >= 1
    _agree_per_lane(bj, simulate_batch(WORK, PLAT, strat, tr), "window order")
    old = simulate_batch(WORK, PLAT, strat, _fault_ordered(tr))
    assert (np.abs(old.makespan - bj.makespan) > 1e-3).any()


def _captured_step(spec, strat):
    """The step program and packed chunk of a one-chunk lanes call."""
    got = []

    class Captured(Exception):
        pass

    def spy(runner, devs, consts, state, *acc):
        got.append((runner, consts, state))
        raise Captured

    orig = jax_sim._dispatch
    jax_sim._dispatch = spy
    try:
        with pytest.raises(Captured):
            simulate_batch_jax(WORK, PLAT, strat, spec, chunk=None)
    finally:
        jax_sim._dispatch = orig
    return got[0]


def test_no_window_keeps_one_pending_slot():
    """A chunk with no window compiles the one-slot program: its lanes
    carry one TP slot and no buffer, and it reports no overtakes and no
    buffer peak though it predicts; a chunk with windows carries
    ``WINDOW_TP_SLOTS`` slots a lane."""
    import jax

    exact = S.exact_prediction(PLAT, PRED)
    spec = _spec_for(exact, PRED, E.exponential(), n=8, seed=3)
    runner, consts, state = _captured_step(spec, exact)
    out = jax.eval_shape(runner, consts, state)
    n_pad = state["t"].shape[0]
    assert "tp_buf" not in out and out["tp_t0"].shape == (n_pad,)
    simulate_batch_jax(WORK, PLAT, exact, spec)
    t = jax_sim.LAST_TIMINGS
    assert t["walk_passes"].sum() > 0
    for k in ("tp_overtakes", "tp_pending_peak", "tp_overflow"):
        assert t[k].shape == (1, 1) and t[k].dtype == np.int64
        assert (t[k] == 0).all(), k

    inst = S.instant(PLAT, PREDW)
    spec = _spec_for(inst, PREDW, E.exponential(), n=8, seed=3)
    runner, consts, state = _captured_step(spec, inst)
    out = jax.eval_shape(runner, consts, state)
    assert "tp_t0" not in out
    assert out["tp_buf"][0].shape == (jax_sim.WINDOW_TP_SLOTS, n_pad)


def test_buffer_overflow_is_reported_not_dropped(monkeypatch):
    """A lane that needs more pending slots than the buffer has is
    reported — the count ``tp_overflow`` and an error — never served out
    of window order; the default buffer holds the same lanes."""
    wide = PredictorModel(recall=0.9, precision=0.8, window=2.0 * PLAT.mu,
                          lead=3600.0)
    strat = S.nockpt(PLAT, wide)
    spec = _spec_for(strat, wide, E.exponential(), n=8, seed=5)
    monkeypatch.setattr(jax_sim, "WINDOW_TP_SLOTS", 2)
    with pytest.raises(RuntimeError, match="pending at once"):
        simulate_batch_jax(WORK, PLAT, strat, spec)
    t = jax_sim.LAST_TIMINGS
    assert t["tp_overflow"].sum() > 0 and t["tp_pending_peak"].max() == 2
    monkeypatch.undo()
    bj = simulate_batch_jax(WORK, PLAT, strat, spec)
    t = jax_sim.LAST_TIMINGS
    assert t["tp_overflow"].sum() == 0 and t["tp_pending_peak"].max() > 2
    _agree_per_lane(bj, simulate_batch(WORK, PLAT, strat, spec.materialize()),
                    "default buffer")


def test_migration_cancel_overflow_is_reported():
    """Windows as wide as two fault gaps keep more than three migration
    cancellations pending in some lane: reported, never dropped."""
    wide = PredictorModel(recall=0.9, precision=0.8, window=2.0 * PLAT.mu,
                          lead=3600.0)
    strat = S.migration(PLAT, wide)
    spec = _spec_for(strat, wide, E.weibull(0.7), n=24, seed=7)
    with pytest.raises(RuntimeError, match="migration cancellations"):
        simulate_batch_jax(WORK, PLAT, strat, spec)
    assert jax_sim.LAST_TIMINGS["tp_overflow"].sum() > 0
    assert jax_sim.LAST_TIMINGS["tp_pending_peak"].max() <= jax_sim.WINDOW_TP_SLOTS


def test_device_gen_chunk_invariance():
    """Stream ids travel with the lanes, so chunk boundaries are
    invisible: byte-identical results for any chunk size — including
    with fractional trust (coins are counter-indexed, not sequential)."""
    strat = S.instant(PLAT, PREDW)
    spec = _spec_for(strat, PREDW, E.weibull(0.7), n=7, seed=3)
    whole = simulate_batch_jax(WORK, PLAT, strat, spec, chunk=None)
    for chunk in (2, 3):
        got = simulate_batch_jax(WORK, PLAT, strat, spec, chunk=chunk)
        np.testing.assert_array_equal(whole.makespan, got.makespan)
        np.testing.assert_array_equal(whole.n_faults, got.n_faults)
    frac = Strategy("Frac", strat.T_R, q=0.5, mode="exact")
    f1 = simulate_batch_jax(WORK, PLAT, frac, spec, chunk=None)
    f2 = simulate_batch_jax(WORK, PLAT, frac, spec, chunk=2)
    np.testing.assert_array_equal(f1.makespan, f2.makespan)


@pytest.mark.parametrize("devices", [2, 8])
def test_device_gen_device_count_invariance(devices):
    """Device-generated streams are sharding-invariant: per-lane results
    identical for any device count (the CI multi-device job forces 8
    host devices so both counts run)."""
    if devices > _n_devices():
        pytest.skip(f"needs {devices} devices, have {_n_devices()}")
    strat = S.instant(PLAT, PREDW)
    spec = _spec_for(strat, PREDW, E.exponential(), n=13, seed=29)
    ref = simulate_batch_jax(WORK, PLAT, strat, spec, devices=1)
    got = simulate_batch_jax(WORK, PLAT, strat, spec, devices=devices)
    np.testing.assert_array_equal(got.makespan, ref.makespan)
    np.testing.assert_array_equal(got.n_faults, ref.n_faults)
    np.testing.assert_array_equal(got.n_proactive_ckpts, ref.n_proactive_ckpts)


def test_device_gen_pallas_and_jnp_agree():
    """The fused sampling hot step (Pallas, interpret on CPU) and the
    pure-jnp fallback share one body: identical results."""
    strat = S.withckpt(PLAT, PREDW)
    spec = _spec_for(strat, PREDW, E.weibull(0.7), n=4, seed=11)
    a = simulate_batch_jax(WORK, PLAT, strat, spec, use_pallas=True)
    b = simulate_batch_jax(WORK, PLAT, strat, spec, use_pallas=False)
    np.testing.assert_array_equal(a.makespan, b.makespan)
    np.testing.assert_array_equal(a.n_regular_ckpts, b.n_regular_ckpts)


def test_device_gen_trust_filter():
    """mode='none' / q=0 hide every prediction (identical to a Young
    baseline on the same fault stream); fractional q lands between."""
    spec = _spec_for(S.instant(PLAT, PREDW), PREDW, E.exponential(), n=6,
                     seed=21)
    t_r = S.young(PLAT).T_R
    none = simulate_batch_jax(
        WORK, PLAT, Strategy("Y", t_r, q=0.0, mode="none"), spec
    )
    distrust = simulate_batch_jax(
        WORK, PLAT, Strategy("D", t_r, q=0.0, mode="exact"), spec
    )
    np.testing.assert_array_equal(none.makespan, distrust.makespan)
    trust = simulate_batch_jax(
        WORK, PLAT, Strategy("T", t_r, q=1.0, mode="exact"), spec
    )
    assert not np.array_equal(none.makespan, trust.makespan)


def test_device_gen_take_pairing():
    """Lanes sharing a stream id face identical traces (paired design),
    and take() reorders results consistently."""
    strat = S.exact_prediction(PLAT, PRED)
    spec = _spec_for(strat, PRED, E.exponential(), n=4, seed=8)
    paired = spec.take([2, 2, 0, 1])
    res = simulate_batch_jax(WORK, PLAT, strat, paired)
    base = simulate_batch_jax(WORK, PLAT, strat, spec)
    assert res.makespan[0] == res.makespan[1] == base.makespan[2]
    assert res.makespan[2] == base.makespan[0]


# ---------------------------------------------------------------------- #
# device-RNG statistical fidelity (fixed keys: fully deterministic)
# ---------------------------------------------------------------------- #
def _cdf(dist, mean, x):
    if dist.kind == "exponential":
        return 1.0 - np.exp(-x / mean)
    if dist.kind == "weibull":
        scale = mean / math.gamma(1.0 + 1.0 / dist.param)
        return 1.0 - np.exp(-((x / scale) ** dist.param))
    if dist.kind == "lognormal":
        mu = math.log(mean) - dist.param**2 / 2.0
        z = (np.log(x) - mu) / (dist.param * math.sqrt(2.0))
        return 0.5 * (1.0 + np.vectorize(math.erf)(z))
    if dist.kind == "uniform":
        return np.clip(x / (2.0 * mean), 0.0, 1.0)
    raise ValueError(dist.kind)


@pytest.mark.parametrize(
    "dist",
    [E.exponential(), E.weibull(0.7), E.lognormal(1.0), E.uniform()],
    ids=["exp", "weibull0.7", "lognormal", "uniform"],
)
def test_device_gen_rng_ks_interarrival(dist):
    """KS test: inter-arrival samples drawn through the device sampling
    path match the host Distribution's law (alpha = 0.01; deterministic
    via fixed keys)."""
    from repro.core.jax_sim import device_interarrival_samples

    n, mean = 4000, 6.0e4
    g = device_interarrival_samples(dist, mean, n, seed=123, stream=5)
    assert g.shape == (n,) and (g > 0).all()
    # mean sanity (lognormal sigma=1 has heavy tails: generous bound)
    assert abs(g.mean() / mean - 1.0) < 0.15
    xs = np.sort(g)
    ecdf = np.arange(1, n + 1) / n
    cdf = _cdf(dist, mean, xs)
    d = np.abs(ecdf - cdf).max()
    assert d < 1.63 / math.sqrt(n), f"KS D={d:.4f} for {dist.name}"


def test_device_gen_recall_precision_accounting():
    """Empirical recall/precision of the generated streams match the
    configured (r, p) within CI, and the materialized accounting is
    exact (every prediction is a TP with a matching fault or an FP)."""
    spec = E.make_trace_spec(
        8, horizon=3e7, mtbf=6e4, recall=0.7, precision=0.4, window=300.0,
        seed=31,
    )
    traces = spec.materialize()
    tp = fp = fn = 0
    for i in range(spec.n_lanes):
        tr = traces.lane(i)
        tp += tr.n_true_positive
        fp += tr.n_false_positive
        fn += tr.n_false_negative
        for p in tr.predictions:
            if p.fault_time is not None:
                assert p.t0 <= p.fault_time <= p.t0 + p.window + 1e-9
    assert abs(tp / (tp + fn) - 0.7) < 0.03
    assert abs(tp / (tp + fp) - 0.4) < 0.03


def test_device_gen_simulate_many_and_run_grid():
    """trace_mode='device' plumbing: simulate_many and run_grid accept
    it for every batched engine; the jax (device sampling) and batch
    (host replay of the same streams) paths agree statistically; the
    legacy engine and superposed traces are rejected."""
    from repro.experiments import ExperimentCell, GridSpec, run_grid

    strat = S.exact_prediction(PLAT, PRED)
    rj = S.simulate_many(
        WORK, PLAT, strat, PRED, n_runs=4, seed=3, engine="jax",
        trace_mode="device",
    )
    rb = S.simulate_many(
        WORK, PLAT, strat, PRED, n_runs=4, seed=3, engine="batch",
        trace_mode="device",
    )
    for j, b in zip(rj, rb):
        assert j.makespan == pytest.approx(b.makespan, abs=1e-6)
        assert j.n_faults == b.n_faults

    cells = [
        ExperimentCell(
            label=f"m{k}", work=6 * 86400.0, platform=PLAT,
            predictor=PREDW, strategy=s,
        )
        for k, s in enumerate([S.young(PLAT), S.instant(PLAT, PREDW)])
    ]
    grid = GridSpec(tuple(cells), n_runs=6, seed=5)
    sj = run_grid(grid, engine="jax", trace_mode="device")
    sb = run_grid(grid, engine="batch", trace_mode="device")
    for cj, cb in zip(sj.cells, sb.cells):
        assert abs(cj.mean_waste - cb.mean_waste) < 1e-3, cj.cell.label

    with pytest.raises(ValueError, match="trace_mode"):
        run_grid(grid, engine="legacy", trace_mode="device")
    with pytest.raises(ValueError, match="trace_mode"):
        S.simulate_many(WORK, PLAT, strat, PRED, n_runs=2,
                        trace_mode="nope")
    with pytest.raises(ValueError, match="superposed|n_components"):
        S.simulate_many(WORK, PLAT, strat, PRED, n_runs=2,
                        trace_mode="device", n_components=16)
    with pytest.raises(ValueError, match="kind"):
        E.make_trace_spec(
            2, horizon=1e6, mtbf=6e4, recall=0.5, precision=0.5,
            fault_dist=E.Distribution("custom", lambda r, m, n: r.exponential(m, n)),
        )


def test_device_gen_migration_cancel_slots_dense():
    """Adversarial migration density (M comparable to the fault gaps,
    recall ~1): several migration episodes can pend cancellations
    simultaneously; the 3-slot counter-indexed cancel tracking must
    still bit-match the NumPy engine's per-fault mask at window=0."""
    plat = Platform(mu=100 * MN, C=2 * MN, D=0.5 * MN, R=2 * MN, M=30 * MN)
    work = 4 * 86400.0
    strat = S.migration(plat, PredictorModel(0.95, 0.9))
    for seed in (0, 2, 7, 13):  # seeds that diverged with one slot
        spec = E.make_trace_spec(
            16, horizon=12 * work, mtbf=plat.mu, recall=0.95,
            precision=0.9, window=0.0, seed=seed,
        )
        bn = simulate_batch(work, plat, strat, spec.materialize())
        bj = simulate_batch_jax(work, plat, strat, spec)
        np.testing.assert_allclose(
            bj.makespan, bn.makespan, rtol=1e-12, atol=1e-6,
            err_msg=f"seed {seed}",
        )
        np.testing.assert_array_equal(bj.n_faults, bn.n_faults)
        np.testing.assert_array_equal(bj.n_migrations, bn.n_migrations)


def test_device_gen_empty_spec():
    """A 0-lane TraceSpec round-trips through every engine entry."""
    spec = E.make_trace_spec(
        0, horizon=1e6, mtbf=6e4, recall=0.5, precision=0.5
    )
    assert spec.materialize().n_lanes == 0
    strat = S.young(PLAT)
    assert simulate_batch(WORK, [], [], spec).n_lanes == 0
    assert simulate_batch_jax(WORK, [], [], spec).n_lanes == 0


# ---------------------------------------------------------------------- #
# cell multiplexing (fused experiment sweeps)
# ---------------------------------------------------------------------- #
def _cell_fixture(n_runs=4, seed=7):
    """Three heterogeneous cells (different platforms, strategies,
    predictors — one migration cell) as a cell-indexed TraceSpec plus
    the per-lane expansion reference."""
    plat2 = Platform(mu=500 * MN, C=5 * MN, D=1 * MN, R=5 * MN, M=3 * MN)
    cells_plat = [PLAT, plat2, plat2]
    cells_pred = [PREDW, PRED, PRED]
    strats = [
        S.instant(PLAT, PREDW), S.young(plat2), S.migration(plat2, PRED)
    ]
    cidx = np.repeat(np.arange(3, dtype=np.int32), n_runs)
    spec = E.make_trace_spec(
        3 * n_runs,
        horizon=[12 * WORK] * 3,
        mtbf=[p.mu for p in cells_plat],
        recall=[p.recall for p in cells_pred],
        precision=[p.precision for p in cells_pred],
        window=[p.window for p in cells_pred],
        lead=[p.lead for p in cells_pred],
        seed=seed,
        cell_index=cidx,
    )
    return cells_plat, strats, cidx, spec


def test_cell_index_matches_per_lane_dispatch():
    """The fused cell-table path gathers per-lane parameters on device;
    results are bit-identical to the expanded per-lane call — the
    gather is semantically invisible."""
    cells_plat, strats, cidx, spec = _cell_fixture()
    spec_lane = spec.expand()
    assert spec.n_cells == 3 and spec_lane.cell_index is None
    ref = simulate_batch_jax(
        WORK, [cells_plat[c] for c in cidx], [strats[c] for c in cidx],
        spec_lane,
    )
    got = simulate_batch_jax([WORK] * 3, cells_plat, strats, spec)
    np.testing.assert_array_equal(ref.makespan, got.makespan)
    np.testing.assert_array_equal(ref.n_faults, got.n_faults)
    np.testing.assert_array_equal(ref.n_migrations, got.n_migrations)
    np.testing.assert_array_equal(ref.n_proactive_ckpts, got.n_proactive_ckpts)
    # chunk boundaries cut through cells without changing anything
    for chunk in (5, 7):
        chunked = simulate_batch_jax(
            [WORK] * 3, cells_plat, strats, spec, chunk=chunk
        )
        np.testing.assert_array_equal(ref.makespan, chunked.makespan)


def test_cell_stats_collect_matches_lane_reduction():
    """collect='stats' segment-reduces per-cell moments on device; they
    equal the host-side reduction of the per-lane results."""
    from repro.core.jax_sim import CellSums

    cells_plat, strats, cidx, spec = _cell_fixture()
    ref = simulate_batch_jax([WORK] * 3, cells_plat, strats, spec)
    st = simulate_batch_jax(
        [WORK] * 3, cells_plat, strats, spec, collect="stats"
    )
    assert isinstance(st, CellSums) and st.n_cells == 3
    np.testing.assert_array_equal(st.n, [4, 4, 4])
    for c in range(3):
        sel = cidx == c
        np.testing.assert_allclose(
            st.mean_waste[c], ref.waste[sel].mean(), rtol=1e-12
        )
        np.testing.assert_allclose(
            st.ci95_waste[c],
            1.96 * ref.waste[sel].std(ddof=1) / np.sqrt(sel.sum()),
            rtol=1e-9,
        )
        assert st.n_faults[c] == ref.n_faults[sel].sum()
        assert st.n_migrations[c] == ref.n_migrations[sel].sum()
    # stats collection is chunk-invariant too (sums accumulate across
    # chunk boundaries that cut through cells)
    st2 = simulate_batch_jax(
        [WORK] * 3, cells_plat, strats, spec, collect="stats", chunk=5
    )
    np.testing.assert_allclose(st.waste_sum, st2.waste_sum, rtol=1e-12)
    np.testing.assert_array_equal(st.n, st2.n)


@pytest.mark.parametrize("devices", [2, 8])
def test_cell_index_device_count_invariance(devices):
    """Fused cell tables replicate per device; per-lane results and the
    per-cell segment sums are identical for any device count."""
    if devices > _n_devices():
        pytest.skip(f"needs {devices} devices, have {_n_devices()}")
    cells_plat, strats, cidx, spec = _cell_fixture(n_runs=5)  # ragged shards
    ref = simulate_batch_jax([WORK] * 3, cells_plat, strats, spec, devices=1)
    got = simulate_batch_jax(
        [WORK] * 3, cells_plat, strats, spec, devices=devices
    )
    np.testing.assert_array_equal(ref.makespan, got.makespan)
    st1 = simulate_batch_jax(
        [WORK] * 3, cells_plat, strats, spec, devices=1, collect="stats"
    )
    stn = simulate_batch_jax(
        [WORK] * 3, cells_plat, strats, spec, devices=devices,
        collect="stats",
    )
    np.testing.assert_allclose(st1.waste_sum, stn.waste_sum, rtol=1e-12)
    np.testing.assert_array_equal(st1.n, stn.n)


def test_cell_index_host_traces():
    """cell_index also tables the engine parameters over host-generated
    BatchTraces (events stay per-lane): same results, and stats
    collection works."""
    cells_plat, strats, cidx, spec = _cell_fixture()
    traces = spec.materialize()
    ref = simulate_batch_jax(
        WORK, [cells_plat[c] for c in cidx], [strats[c] for c in cidx],
        traces,
    )
    got = simulate_batch_jax(
        [WORK] * 3, cells_plat, strats, traces, cell_index=cidx
    )
    np.testing.assert_array_equal(ref.makespan, got.makespan)
    st = simulate_batch_jax(
        [WORK] * 3, cells_plat, strats, traces, cell_index=cidx,
        collect="stats",
    )
    for c in range(3):
        np.testing.assert_allclose(
            st.mean_waste[c], ref.waste[cidx == c].mean(), rtol=1e-12
        )


def test_cell_index_validation_errors():
    cells_plat, strats, cidx, spec = _cell_fixture()
    with pytest.raises(ValueError, match="cell_index"):
        simulate_batch_jax(
            [WORK] * 3, cells_plat, strats, spec,
            cell_index=cidx[:5],  # wrong length
        )
    with pytest.raises(ValueError, match="cell-indexed"):
        simulate_batch_jax(
            [WORK] * 3, cells_plat, strats, spec.expand(), cell_index=cidx
        )
    with pytest.raises(ValueError, match="collect"):
        simulate_batch_jax([WORK] * 3, cells_plat, strats, spec,
                           collect="rows")
    with pytest.raises(ValueError, match="cell_index"):
        simulate_batch_jax(
            WORK, PLAT, S.young(PLAT),
            _traces_for(S.young(PLAT), PRED0, E.exponential(), n=2),
            collect="stats",
        )
    bad = np.array(cidx)
    bad[0] = 7  # out of the 3-cell table
    with pytest.raises(ValueError, match="cell_index"):
        simulate_batch_jax(
            [WORK] * 3, cells_plat, strats, spec.materialize(),
            cell_index=bad,
        )
    with pytest.raises(ValueError, match="cell_index"):
        E.make_trace_spec(
            4, horizon=1e6, mtbf=6e4, recall=0.5, precision=0.5,
            cell_index=[0, 1],  # wrong shape
        )


# ---------------------------------------------------------------------- #
# mixed-law cell tables (law-multiplexed device sampling)
# ---------------------------------------------------------------------- #
_MIXED_LAWS = (
    E.exponential(), E.weibull(0.7), E.lognormal(0.5), E.uniform()
)


def _mixed_law_fixture(n_runs=4, seed=19):
    """Four cells on one platform/strategy — one per failure-law family —
    as a single mixed-law cell-indexed spec (the law is a data column)."""
    strat = S.exact_prediction(PLAT, PRED)
    cidx = np.repeat(np.arange(4, dtype=np.int32), n_runs)
    spec = E.make_trace_spec(
        4 * n_runs, horizon=12 * WORK, mtbf=PLAT.mu, recall=PRED.recall,
        precision=PRED.precision, window=0.0, lead=PRED.lead, seed=seed,
        cell_index=cidx, fault_dist=_MIXED_LAWS,
    )
    return strat, cidx, spec


def test_device_gen_mixed_law_cells_match_single_law():
    """Law multiplexing is semantically invisible: every cell of a
    4-law fused dispatch is bit-identical to a single-law run of the
    same streams through the law-indexed sampler, and matches the
    law-*specialized* static sampler exactly for the closed-form laws
    (lognormal to float rounding — XLA fuses its transcendentals
    differently per compilation context)."""
    strat, cidx, spec = _mixed_law_fixture()
    got = simulate_batch_jax([WORK] * 4, [PLAT] * 4, [strat] * 4, spec)
    for c, dist in enumerate(_MIXED_LAWS):
        sel = cidx == c
        ref_spec = E.make_trace_spec(
            int(sel.sum()), horizon=12 * WORK, mtbf=PLAT.mu,
            recall=PRED.recall, precision=PRED.precision, window=0.0,
            lead=PRED.lead, seed=19, stream=np.flatnonzero(sel),
            fault_dist=dist,
        )
        ref_ix = simulate_batch_jax(WORK, PLAT, strat, ref_spec.indexed())
        np.testing.assert_array_equal(
            got.makespan[sel], ref_ix.makespan, err_msg=dist.name
        )
        np.testing.assert_array_equal(got.n_faults[sel], ref_ix.n_faults)
        np.testing.assert_array_equal(
            got.n_proactive_ckpts[sel], ref_ix.n_proactive_ckpts
        )
        ref_st = simulate_batch_jax(WORK, PLAT, strat, ref_spec)
        if dist.kind == "lognormal":
            np.testing.assert_allclose(
                got.makespan[sel], ref_st.makespan, rtol=1e-12,
                err_msg=dist.name,
            )
        else:
            np.testing.assert_array_equal(
                got.makespan[sel], ref_st.makespan, err_msg=dist.name
            )


def test_device_gen_mixed_law_chunk_invariance():
    """Mixed-law lane packing travels with the lanes: chunk boundaries
    cutting through law families change nothing."""
    strat, cidx, spec = _mixed_law_fixture()
    whole = simulate_batch_jax(
        [WORK] * 4, [PLAT] * 4, [strat] * 4, spec, chunk=None
    )
    for chunk in (3, 7):
        got = simulate_batch_jax(
            [WORK] * 4, [PLAT] * 4, [strat] * 4, spec, chunk=chunk
        )
        np.testing.assert_array_equal(whole.makespan, got.makespan)
        np.testing.assert_array_equal(whole.n_faults, got.n_faults)


@pytest.mark.parametrize("devices", [1, 2, 8])
def test_device_gen_mixed_law_stats_invariance(devices):
    """The shard_map segment reduction accumulates per-cell sums in a
    donated replicated buffer (one psum per chunk): mixed-law per-cell
    stats are invariant to chunk size and device count — including
    ragged shards and chunk cuts through law families."""
    if devices > _n_devices():
        pytest.skip(f"needs {devices} devices, have {_n_devices()}")
    strat, cidx, spec = _mixed_law_fixture(n_runs=5)
    args = ([WORK] * 4, [PLAT] * 4, [strat] * 4, spec)
    ref = simulate_batch_jax(*args, collect="stats", devices=1)
    np.testing.assert_array_equal(ref.n, [5, 5, 5, 5])
    for chunk in (None, 7):
        got = simulate_batch_jax(
            *args, collect="stats", devices=devices, chunk=chunk
        )
        np.testing.assert_allclose(got.waste_sum, ref.waste_sum, rtol=1e-12)
        np.testing.assert_array_equal(got.n, ref.n)
        np.testing.assert_array_equal(got.n_faults, ref.n_faults)


def test_device_gen_mixed_law_stats_transfer_guard():
    """collect='stats' never materializes per-lane arrays on the host:
    after executable warmup the whole mixed-law stats call — sharded
    when the process has several devices — runs under
    ``jax.transfer_guard("disallow")``.  Packing and the O(cells) fetch
    are explicit device_put/device_get; nothing transfers implicitly."""
    import jax

    strat, cidx, spec = _mixed_law_fixture()
    args = ([WORK] * 4, [PLAT] * 4, [strat] * 4, spec)
    kw = {"collect": "stats", "devices": _n_devices()}
    ref = simulate_batch_jax(*args, **kw)  # compile outside the guard
    with jax.transfer_guard("disallow"):
        got = simulate_batch_jax(*args, **kw)
    np.testing.assert_array_equal(got.waste_sum, ref.waste_sum)
    np.testing.assert_array_equal(got.n, ref.n)


def _mixed_law_grid(n_runs=4, seed=23):
    from repro.experiments import ExperimentCell, GridSpec

    pred = PredictorModel(0.85, 0.82, window=300.0, lead=3600.0)
    cells = [
        ExperimentCell(
            label=f"{lk}/{strat.name}", work=6 * 86400.0, platform=PLAT,
            predictor=pred, strategy=strat, fault_dist=dist,
        )
        for lk, dist in (("exp", E.exponential()), ("wb", E.weibull(0.7)))
        for strat in (S.young(PLAT), S.instant(PLAT, pred))
    ]
    return GridSpec(tuple(cells), n_runs=n_runs, seed=seed)


def test_device_gen_mixed_law_run_grid_one_dispatch():
    """A mixed-law grid in device trace mode runs as exactly ONE fused
    engine dispatch; its per-cell results are bit-identical to the
    per-family baseline (same law-indexed sampler per family) and to
    per-cell dispatch (static samplers — exact for these laws), and the
    device-reduced stats agree bit-for-bit too."""
    from repro.core import jax_sim
    from repro.experiments import run_grid

    grid = _mixed_law_grid()
    fused = run_grid(grid, engine="jax", trace_mode="device")
    assert jax_sim.LAST_TIMINGS["n_chunks"] == 1
    perfam = run_grid(
        grid, engine="jax", trace_mode="device", dispatch="perfamily"
    )
    percell = run_grid(
        grid, engine="jax", trace_mode="device", dispatch="percell"
    )
    for cf, cp, cc in zip(fused.cells, perfam.cells, percell.cells):
        np.testing.assert_array_equal(
            cf.makespan, cp.makespan, err_msg=cf.cell.label
        )
        np.testing.assert_array_equal(
            cf.makespan, cc.makespan, err_msg=cf.cell.label
        )
        np.testing.assert_array_equal(cf.n_faults, cp.n_faults)
    sf = run_grid(grid, engine="jax", trace_mode="device", collect="stats")
    sp = run_grid(
        grid, engine="jax", trace_mode="device", dispatch="perfamily",
        collect="stats",
    )
    for cf, cp in zip(sf.cells, sp.cells):
        assert cf.mean_waste == cp.mean_waste, cf.cell.label
        assert cf.ci95_waste == cp.ci95_waste, cf.cell.label
    with pytest.raises(ValueError, match="perfamily"):
        run_grid(grid, engine="jax", dispatch="perfamily")


def test_mixed_law_run_grid_host_traces():
    """Host trace mode: a mixed-law grid still fuses into one engine
    dispatch over the per-group event arrays — bit-identical to
    per-cell dispatch and float-rounding-close to the batch engine."""
    from repro.experiments import run_grid

    grid = _mixed_law_grid()
    fused = run_grid(grid, engine="jax")
    percell = run_grid(grid, engine="jax", dispatch="percell")
    batch = run_grid(grid, engine="batch")
    for cf, cc, cb in zip(fused.cells, percell.cells, batch.cells):
        np.testing.assert_array_equal(
            cf.makespan, cc.makespan, err_msg=cf.cell.label
        )
        np.testing.assert_allclose(
            cf.makespan, cb.makespan, rtol=1e-12, atol=1e-6,
            err_msg=cf.cell.label,
        )


def test_run_cache_lru_eviction(monkeypatch):
    """The engine-executable cache is a bounded LRU: hits refresh
    recency and inserts over the cap evict the least recently used
    entry (long-lived advisor services can't grow it unboundedly)."""
    import jax

    from repro.core import jax_sim

    saved = jax_sim._RUN_CACHE.copy()
    jax_sim._RUN_CACHE.clear()
    monkeypatch.setattr(jax_sim, "_RUN_CACHE_MAX", 2)
    try:
        devs = tuple(jax.devices()[:1])
        r0 = jax_sim._get_runner(False, True, 100, 1e-9, False, devs)
        r1 = jax_sim._get_runner(False, True, 101, 1e-9, False, devs)
        assert len(jax_sim._RUN_CACHE) == 2
        # a hit returns the cached executable and refreshes its recency
        assert jax_sim._get_runner(
            False, True, 100, 1e-9, False, devs
        ) is r0
        jax_sim._get_runner(False, True, 102, 1e-9, False, devs)
        assert len(jax_sim._RUN_CACHE) == 2
        # the refreshed entry survived; the stale one was evicted
        assert jax_sim._get_runner(
            False, True, 100, 1e-9, False, devs
        ) is r0
        assert jax_sim._get_runner(
            False, True, 101, 1e-9, False, devs
        ) is not r1
    finally:
        jax_sim._RUN_CACHE.clear()
        jax_sim._RUN_CACHE.update(saved)


def test_best_period_search_jax_matches_batch():
    """engine='jax' brute-forces the period as ONE cell-multiplexed
    collect='stats' dispatch (one cell per candidate): identical traces,
    so the argmin and the winning waste match the batch engine."""
    for base, pred in (
        (S.exact_prediction(PLAT, PRED), PRED),
        (S.young(PLAT), PRED0),
    ):
        tb, wb = S.best_period_search(
            6 * 86400.0, PLAT, base, pred, n_runs=3, seed=5,
            grid=(0.6, 1.0, 1.6),
        )
        tj, wj = S.best_period_search(
            6 * 86400.0, PLAT, base, pred, n_runs=3, seed=5,
            grid=(0.6, 1.0, 1.6), engine="jax",
        )
        assert tj == tb, base.name
        assert wj == pytest.approx(wb, rel=1e-9), base.name


def test_cell_spec_take_and_expand():
    """take() on a cell-indexed spec selects lanes (table untouched);
    expand() is the per-lane reference layout; materialize() routes
    through it."""
    cells_plat, strats, cidx, spec = _cell_fixture()
    sub = spec.take([0, 4, 8, 9])
    assert sub.n_lanes == 4 and sub.n_cells == 3
    np.testing.assert_array_equal(sub.cell_index, [0, 1, 2, 2])
    np.testing.assert_array_equal(sub.stream, spec.stream[[0, 4, 8, 9]])
    full = spec.materialize()
    part = sub.materialize()
    assert part.n_faults[0] == full.n_faults[0]
    nf = int(full.n_faults[0])
    np.testing.assert_array_equal(
        part.fault_times[0, :nf], full.fault_times[0, :nf]
    )
    np.testing.assert_array_equal(
        part.horizon, spec.expand().horizon[[0, 4, 8, 9]]
    )
