"""Resumable campaign runner: kill/resume bit-exactness at every chunk
boundary (in-process and real SIGKILL), the chaos recovery matrix (OOM
chunk-halving, device loss, engine degradation), snapshot-period choice
via the paper's own optimize(), and the retry/chaos primitives."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.engine import EngineConfig
from repro.core.waste import Platform
from repro.experiments import run_grid
from repro.experiments.grid import GridSpec
from repro.experiments.paper_grid import paper_grid_cells
from repro.ft import (
    CampaignConfig,
    CampaignKilled,
    CampaignRunner,
    ChaosInjector,
    FailureKind,
    RetryPolicy,
    SyntheticDeviceLoss,
    SyntheticJaxFailure,
    SyntheticOOM,
    classify_failure,
    run_campaign,
)

#: chaos-fuzz budget (CI sets it higher in the chaos job)
N_FUZZ = int(os.environ.get("REPRO_CHAOS_EXAMPLES", "2"))

CHUNK = 25  # one shape for every campaign test: a single engine compile


def small_grid(n_runs=30, seed=7, n_cells=4):
    cells = paper_grid_cells("validation")[:n_cells]
    return GridSpec(cells=tuple(cells), n_runs=n_runs, seed=seed)


def cfg(trace_mode="device", collect="stats", chunk=CHUNK):
    return EngineConfig(
        engine="jax", trace_mode=trace_mode, collect=collect,
        chunk_lanes=chunk,
    )


def nosleep():
    return RetryPolicy(sleep=lambda s: None)


def key_vec(res):
    return np.stack(
        [
            [c.mean_waste for c in res.cells],
            [c.mean_makespan for c in res.cells],
            [c.mean_faults for c in res.cells],
            [c.mean_regular_ckpts for c in res.cells],
        ]
    )


@pytest.fixture(scope="module")
def grid():
    return small_grid()


@pytest.fixture(scope="module")
def ref_device(grid):
    return run_grid(grid, config=cfg("device"))


class TestCampaignEquivalence:
    def test_matches_run_grid_device(self, tmp_path, grid, ref_device):
        res = run_campaign(
            grid, CampaignConfig(ckpt_dir=str(tmp_path), ckpt_period=0.0),
            cfg("device"),
        )
        np.testing.assert_array_equal(key_vec(ref_device), key_vec(res))
        camp = res.meta["campaign"]
        assert camp["n_snapshots"] >= grid.n_lanes // CHUNK
        assert not camp["engine_degraded"]

    def test_lanes_collect_matches_run_grid(self, tmp_path, grid):
        ref = run_grid(grid, config=cfg("device", collect="lanes"))
        res = run_campaign(
            grid, CampaignConfig(ckpt_dir=str(tmp_path), ckpt_period=0.0),
            cfg("device", collect="lanes"),
        )
        for rc, cc in zip(ref.cells, res.cells):
            np.testing.assert_array_equal(rc.waste, cc.waste)
            np.testing.assert_array_equal(rc.makespan, cc.makespan)

    def test_period_none_uses_optimize(self, tmp_path, grid):
        mtbf = 1800.0
        res = run_campaign(
            grid,
            CampaignConfig(ckpt_dir=str(tmp_path), mtbf=mtbf,
                           restore_cost=2.0),
            cfg("device"),
        )
        camp = res.meta["campaign"]
        from repro.core import optimize

        want = optimize(
            "young",
            Platform(mu=mtbf, C=max(camp["snapshot_cost_est_s"], 1e-4),
                     D=0.0, R=2.0),
        ).T_R
        assert camp["snapshot_period_s"] == pytest.approx(want)
        assert camp["snapshot_period_s"] > 0


class TestKillResume:
    @pytest.mark.parametrize("trace_mode", ["device", "host"])
    def test_kill_at_every_boundary_is_bit_exact(self, tmp_path, grid,
                                                 trace_mode):
        # sync snapshots: every boundary is deterministically durable,
        # so each k>0 must actually resume (async durability is covered
        # by the SIGKILL and fuzz tests, where racing the drain is the
        # point)
        c = cfg(trace_mode)
        base = run_campaign(
            grid,
            CampaignConfig(ckpt_dir=str(tmp_path / "base"), ckpt_period=0.0,
                           async_snapshots=False),
            c,
        )
        n_chunks = -(-grid.n_lanes // CHUNK)
        for k in range(n_chunks):
            d = str(tmp_path / f"{trace_mode}_{k}")
            camp = CampaignConfig(
                ckpt_dir=d, ckpt_period=0.0, async_snapshots=False,
                chaos=ChaosInjector(kill_at=(k,)),
            )
            with pytest.raises(CampaignKilled):
                run_campaign(grid, camp, c)
            res = run_campaign(
                grid,
                CampaignConfig(ckpt_dir=d, ckpt_period=0.0,
                               async_snapshots=False),
                c,
            )
            np.testing.assert_array_equal(key_vec(base), key_vec(res))
            if k > 0:  # every prior boundary was durable before the kill
                ev = res.meta["campaign"]["events"]
                assert any(e["kind"] == "resume" for e in ev)

    def test_kill_resume_lanes_collect(self, tmp_path, grid):
        c = cfg("device", collect="lanes")
        base = run_campaign(
            grid,
            CampaignConfig(ckpt_dir=str(tmp_path / "b"), ckpt_period=0.0),
            c,
        )
        d = str(tmp_path / "k")
        with pytest.raises(CampaignKilled):
            run_campaign(
                grid,
                CampaignConfig(ckpt_dir=d, ckpt_period=0.0,
                               chaos=ChaosInjector(kill_at=(3,))),
                c,
            )
        res = run_campaign(
            grid, CampaignConfig(ckpt_dir=d, ckpt_period=0.0), c
        )
        for bc, cc in zip(base.cells, res.cells):
            np.testing.assert_array_equal(bc.waste, cc.waste)

    def test_fingerprint_mismatch_refuses_resume(self, tmp_path, grid):
        d = str(tmp_path)
        with pytest.raises(CampaignKilled):
            run_campaign(
                grid,
                CampaignConfig(ckpt_dir=d, ckpt_period=0.0,
                               chaos=ChaosInjector(kill_at=(2,))),
                cfg("device"),
            )
        other = small_grid(seed=8)
        with pytest.raises(ValueError, match="fingerprint"):
            run_campaign(
                other, CampaignConfig(ckpt_dir=d, ckpt_period=0.0),
                cfg("device"), resume=True,
            )

    def test_resume_true_requires_snapshot(self, tmp_path, grid):
        with pytest.raises(FileNotFoundError):
            run_campaign(
                grid, CampaignConfig(ckpt_dir=str(tmp_path)),
                cfg("device"), resume=True,
            )

    def test_sigkill_subprocess_resume(self, tmp_path):
        """The real thing: the CLI process dies on SIGKILL mid-campaign
        (no atexit, no flush) and a fresh process resumes bit-exactly."""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src"),
             env.get("PYTHONPATH", "")]
        )
        common = [
            sys.executable, "-m", "repro.experiments.campaign",
            "--preset", "validation", "--limit-cells", "3",
            "--n-runs", "20", "--seed", "5",
            "--chunk-lanes", str(CHUNK), "--ckpt-period", "0",
        ]
        ref = str(tmp_path / "ref.json")
        subprocess.run(
            common + ["--ckpt-dir", str(tmp_path / "r"), "--out", ref],
            env=env, check=True, timeout=300,
        )
        proc = subprocess.run(
            common + [
                "--ckpt-dir", str(tmp_path / "k"),
                "--chaos-kill-at", "2", "--chaos-kill-mode", "sigkill",
            ],
            env=env, timeout=300,
        )
        assert proc.returncode in (-9, 137)
        out = str(tmp_path / "resumed.json")
        subprocess.run(
            [sys.executable, "-m", "repro.experiments.campaign",
             "--resume", str(tmp_path / "k"), "--out", out],
            env=env, check=True, timeout=300,
        )
        with open(ref) as f:
            a = json.load(f)
        with open(out) as f:
            b = json.load(f)
        keys = ("label", "mean_waste", "mean_makespan", "mean_faults")
        assert [[c[k] for k in keys] for c in a["cells"]] == (
            [[c[k] for k in keys] for c in b["cells"]]
        )
        assert b["meta"]["campaign"]["incarnation"] >= 1


def scenario_grid(n_runs=30, seed=13):
    """A small mixed scenario grid: two-level (untrusted) + silent cells,
    exercising the DISK/DET statistics columns through the campaign."""
    from repro.experiments.paper_grid import (
        silent_grid_cells,
        two_level_grid_cells,
    )

    cells = tuple(two_level_grid_cells("validation")[:2]) + tuple(
        silent_grid_cells("validation")[:2]
    )
    return GridSpec(cells=cells, n_runs=n_runs, seed=seed)


class TestScenarioCampaign:
    """Kill/resume + snapshot-matrix coverage of the two new phase
    families (two-level checkpointing, silent errors)."""

    @pytest.fixture(scope="class")
    def sgrid(self):
        return scenario_grid()

    @pytest.mark.parametrize("trace_mode", ["device", "host"])
    def test_kill_resume_scenario_bit_exact(self, tmp_path, sgrid,
                                            trace_mode):
        c = cfg(trace_mode)
        ref = run_grid(sgrid, config=c)
        base = run_campaign(
            sgrid,
            CampaignConfig(ckpt_dir=str(tmp_path / "base"), ckpt_period=0.0,
                           async_snapshots=False),
            c,
        )
        np.testing.assert_array_equal(key_vec(ref), key_vec(base))
        for k in (1, 3):
            d = str(tmp_path / f"{trace_mode}_{k}")
            with pytest.raises(CampaignKilled):
                run_campaign(
                    sgrid,
                    CampaignConfig(ckpt_dir=d, ckpt_period=0.0,
                                   async_snapshots=False,
                                   chaos=ChaosInjector(kill_at=(k,))),
                    c,
                )
            res = run_campaign(
                sgrid,
                CampaignConfig(ckpt_dir=d, ckpt_period=0.0,
                               async_snapshots=False),
                c,
            )
            np.testing.assert_array_equal(key_vec(base), key_vec(res))
            ev = res.meta["campaign"]["events"]
            assert any(e["kind"] == "resume" for e in ev)

    def test_snapshot_matrix_carries_scenario_columns(self, tmp_path, sgrid):
        """The campaign accumulator is the full 12-column CellSums
        matrix: disk-tier recoveries on the two-level cells, silent
        detections on the silent cells, zero cross-talk."""
        from repro.core.jax_sim import CellSums

        runner = CampaignRunner(
            sgrid,
            CampaignConfig(ckpt_dir=str(tmp_path), ckpt_period=0.0),
            cfg("device"),
        )
        runner.run()
        assert runner._sums.shape == (len(sgrid.cells), 12)
        sums = CellSums.from_matrix(runner._sums)
        disk = np.asarray(sums.n_disk_recoveries)
        det = np.asarray(sums.n_detections)
        assert (disk[:2] > 0).all()  # two-level cells hit the disk tier
        assert (det[2:] > 0).all()  # silent cells detect corruptions
        assert (disk[2:] == 0).all() and (det[:2] == 0).all()

    def test_pre_scenario_snapshot_shape_refused(self, tmp_path, sgrid):
        """A snapshot written before the DISK/DET columns existed (10-col
        accumulator) must be refused, not silently mis-summed."""
        from repro.checkpoint.store import CheckpointStore

        d = str(tmp_path)
        with pytest.raises(CampaignKilled):
            run_campaign(
                sgrid,
                CampaignConfig(ckpt_dir=d, ckpt_period=0.0,
                               async_snapshots=False,
                               chaos=ChaosInjector(kill_at=(2,))),
                cfg("device"),
            )
        store = CheckpointStore(d, codec="raw")
        step, tree = store.restore_latest()
        tree["sums"] = np.asarray(tree["sums"])[:, :10]
        store.save(step + 1, tree)
        with pytest.raises(ValueError, match="refusing to resume"):
            run_campaign(
                sgrid, CampaignConfig(ckpt_dir=d, ckpt_period=0.0),
                cfg("device"), resume=True,
            )


class TestChaosRecovery:
    def test_oom_halves_chunk_and_completes(self, tmp_path, grid,
                                            ref_device):
        res = run_campaign(
            grid,
            CampaignConfig(ckpt_dir=str(tmp_path), ckpt_period=0.0,
                           retry=nosleep(),
                           chaos=ChaosInjector(oom_at=(1,))),
            cfg("device"),
        )
        camp = res.meta["campaign"]
        kinds = [e["kind"] for e in camp["events"]]
        assert "oom" in kinds and "chunk_halved" in kinds
        assert camp["chunk_lanes_final"] == CHUNK // 2
        # partition changed -> f64 summation order changed: allclose
        np.testing.assert_allclose(
            key_vec(ref_device), key_vec(res), rtol=1e-9
        )

    def test_device_loss_completes_bit_exact(self, tmp_path, grid,
                                             ref_device):
        import jax

        res = run_campaign(
            grid,
            CampaignConfig(ckpt_dir=str(tmp_path), ckpt_period=0.0,
                           retry=nosleep(),
                           chaos=ChaosInjector(device_loss_at=(2,))),
            cfg("device"),
        )
        camp = res.meta["campaign"]
        kinds = [e["kind"] for e in camp["events"]]
        assert "device_loss" in kinds
        if len(jax.devices()) > 1:
            # multi-device (CI chaos job): the dispatch shrank and the
            # result is still bit-exact (device-count invariance)
            assert "devices_shrunk" in kinds
            assert camp["n_devices_final"] < len(jax.devices())
        np.testing.assert_array_equal(key_vec(ref_device), key_vec(res))

    def test_persistent_jax_failure_degrades_to_batch(self, tmp_path, grid,
                                                      ref_device):
        res = run_campaign(
            grid,
            CampaignConfig(ckpt_dir=str(tmp_path), ckpt_period=0.0,
                           retry=nosleep(),
                           chaos=ChaosInjector(jax_fail_at=1)),
            cfg("device"),
        )
        camp = res.meta["campaign"]
        assert camp["engine_degraded"]
        assert res.engine == "batch"
        kinds = [e["kind"] for e in camp["events"]]
        assert "engine_degraded" in kinds
        assert kinds.count("transient") >= 2  # retried before degrading
        # host replay of the same counter streams: statistically equal
        np.testing.assert_allclose(
            key_vec(ref_device)[0], key_vec(res)[0], rtol=0.35
        )

    @pytest.mark.parametrize("failure", ["lowering", "nonconvergence"])
    def test_deterministic_failure_raises_not_degrades(
        self, tmp_path, grid, monkeypatch, failure
    ):
        """A kernel the backend cannot lower, or a lane machine that does
        not converge, fails every attempt alike: the campaign raises it
        instead of finishing the sweep on the host's batch engine."""
        import functools

        from repro.core import jax_sim

        if failure == "lowering":
            def engine(*a, **kw):
                raise NotImplementedError("Unsupported cast: uint32 -> float32")

            expect = NotImplementedError
        else:
            engine = functools.partial(jax_sim.simulate_batch_jax, max_iters=1)
            expect = RuntimeError
        monkeypatch.setattr(jax_sim, "simulate_batch_jax", engine)
        runner = CampaignRunner(
            grid,
            CampaignConfig(ckpt_dir=str(tmp_path), ckpt_period=0.0,
                           retry=nosleep()),
            cfg("device"),
        )
        with pytest.raises(expect):
            runner.run()
        assert not runner._degraded
        assert not runner._events

    def test_degraded_state_survives_kill(self, tmp_path, grid):
        """Degradation is durable: a campaign killed *after* degrading
        resumes on the batch engine, bit-identical to an uninterrupted
        degraded run."""
        c = cfg("device")
        base = run_campaign(
            grid,
            CampaignConfig(ckpt_dir=str(tmp_path / "b"), ckpt_period=0.0,
                           retry=nosleep(),
                           chaos=ChaosInjector(jax_fail_at=0)),
            c,
        )
        assert base.meta["campaign"]["engine_degraded"]
        d = str(tmp_path / "k")
        with pytest.raises(CampaignKilled):
            run_campaign(
                grid,
                CampaignConfig(ckpt_dir=d, ckpt_period=0.0, retry=nosleep(),
                               chaos=ChaosInjector(jax_fail_at=0,
                                                   kill_at=(3,))),
                c,
            )
        res = run_campaign(
            grid, CampaignConfig(ckpt_dir=d, ckpt_period=0.0), c
        )
        assert res.meta["campaign"]["engine_degraded"]
        np.testing.assert_array_equal(key_vec(base), key_vec(res))

    @pytest.mark.parametrize("fuzz_seed", range(N_FUZZ))
    def test_chaos_fuzz_converges(self, tmp_path, grid, ref_device,
                                  fuzz_seed):
        """Probabilistic kill/OOM/device-loss storms (bounded fire
        budget): the campaign always completes across incarnations and
        the result stays equal to the plain sweep (bit-exact unless an
        OOM changed the chunk partition)."""
        chaos = ChaosInjector(
            seed=1000 + fuzz_seed, p_kill=0.25, p_oom=0.2,
            p_device_loss=0.15, max_fires=5,
        )
        camp = CampaignConfig(
            ckpt_dir=str(tmp_path), ckpt_period=0.0, retry=nosleep(),
            chaos=chaos,
        )
        res = None
        for _ in range(chaos.max_fires + 2):
            try:
                res = CampaignRunner(grid, camp, cfg("device")).run()
                break
            except CampaignKilled:
                continue
        assert res is not None, "campaign never completed under chaos"
        np.testing.assert_allclose(
            key_vec(ref_device), key_vec(res), rtol=1e-9
        )


class TestRetryPrimitives:
    def test_classifier(self):
        assert classify_failure(SyntheticOOM(0)) is FailureKind.OOM
        assert classify_failure(SyntheticDeviceLoss(0)) is (
            FailureKind.DEVICE_LOSS
        )
        assert classify_failure(SyntheticJaxFailure(0)) is (
            FailureKind.TRANSIENT
        )
        assert classify_failure(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        ) is FailureKind.OOM
        assert classify_failure(ValueError("bad arg")) is FailureKind.FATAL
        assert classify_failure(RuntimeError("???")) is FailureKind.TRANSIENT
        # deterministic engine failures: lowering, compiling, converging
        for exc in (
            NotImplementedError("Unsupported cast: uint32 -> float32"),
            RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel"),
            RuntimeError("UNIMPLEMENTED: no lowering for this op"),
            RuntimeError("jax batch simulator did not converge: 3 lane(s)"),
        ):
            assert classify_failure(exc) is FailureKind.FATAL

    def test_backoff_deterministic_and_bounded(self):
        pol = RetryPolicy(base=0.1, factor=2.0, jitter=0.5, seed=4)
        a = [pol.backoff(k, counter=k) for k in range(4)]
        b = [pol.backoff(k, counter=k) for k in range(4)]
        assert a == b  # counter-keyed jitter replays
        for k, dt in enumerate(a):
            assert 0.1 * 2 ** k <= dt <= 0.1 * 2 ** k * 1.5

    def test_campaign_killed_is_not_an_exception(self):
        assert not issubclass(CampaignKilled, Exception)
        assert issubclass(CampaignKilled, BaseException)

    def test_chaos_scheduled_fire_once(self):
        ch = ChaosInjector(oom_at=(2,))
        ch.at_chunk_boundary(0)
        ch.at_chunk_boundary(1)
        with pytest.raises(SyntheticOOM):
            ch.at_chunk_boundary(2)
        ch.at_chunk_boundary(2)  # already fired: retry proceeds

    def test_chaos_retries_skip_scheduled(self):
        ch = ChaosInjector(oom_at=(0,), kill_at=(0,))
        ch.at_chunk_boundary(0, attempt=1)  # nothing fires on retries

    def test_chaos_jax_failure_persists_until_degraded(self):
        ch = ChaosInjector(jax_fail_at=1)
        ch.at_chunk_boundary(0)
        for attempt in range(3):
            with pytest.raises(SyntheticJaxFailure):
                ch.at_chunk_boundary(1, attempt=attempt)
        with pytest.raises(SyntheticJaxFailure):
            ch.at_chunk_boundary(5, incarnation=2, attempt=1)
        ch.at_chunk_boundary(5, engine="batch")  # bug lives in the jax path

    def test_chaos_budget_bounds_probabilistic_fires(self):
        ch = ChaosInjector(seed=3, p_oom=1.0, max_fires=2)
        fired = 0
        for k in range(10):
            try:
                ch.at_chunk_boundary(k)
            except SyntheticOOM:
                fired += 1
        assert fired == 2
