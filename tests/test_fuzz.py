"""Differential engine fuzz: random experiment cells through every engine.

Each example draws a random cell (failure law, strategy mode, window,
trust, recall/precision, platform scale), pairs it with a Young baseline
on the same traces, and runs the grid through scalar vs batch vs jax —
host *and* device trace modes, fused *and* per-cell dispatch — asserting
the engine-equivalence contracts:

* host trace mode: batch and jax consume identical event arrays, so
  per-lane makespans agree to float rounding; the scalar oracle agrees
  to the fast-forward tolerance; fused and per-cell dispatch are
  bit-identical (deterministic trust);
* device trace mode: fused and per-cell dispatch are bit-identical
  (counter streams travel with the lanes); the batch engine replaying
  the materialized streams matches to float rounding, exact-date
  predictions and windows alike;
* mixed-law grids (the drawn law + its successor): the one-dispatch
  law-indexed path is bit-identical to the per-family baseline and
  float-rounding-close to the law-specialized per-cell dispatch.

Uses hypothesis when available (the ``fuzz`` marker lets CI run a larger
budget nightly via ``REPRO_FUZZ_EXAMPLES``); falls back to a fixed-seed
parameter sweep otherwise so the differential coverage never silently
disappears.
"""

import dataclasses
import math
import os

import numpy as np
import pytest

from repro.core import Platform, PredictorModel
from repro.core import events as E
from repro.core import simulator as S
from repro.experiments import ExperimentCell, GridSpec, run_grid

pytestmark = pytest.mark.fuzz

MN = 60.0
N_EXAMPLES = int(os.environ.get("REPRO_FUZZ_EXAMPLES", "6"))

#: fixed distribution instances — the failure law statically specializes
#: the compiled device sampler, so a bounded set keeps the fuzz budget in
#: executables small while still crossing every family
LAWS = {
    "exp": E.exponential(),
    "weibull0.7": E.weibull(0.7),
    "weibull0.5": E.weibull(0.5),
    "lognormal": E.lognormal(1.0),
}
MODES = [
    "none", "exact", "two_level", "silent", "nockpt", "withckpt",
    "migration",
]
#: modes whose strategy factory fixes q itself (silent is never
#: predictor-trusted; two_level trusts iff it is built with a predictor)
_FIXED_Q_MODES = ("none", "two_level", "silent")

#: scalar-vs-vectorized tolerance (fast-forward float fusion)
MK_TOL = 1e-3


def _make_grid(mu_mn, c_mn, law_key, mode, window, q, recall, precision, seed):
    plat = Platform(
        mu=mu_mn * MN, C=c_mn * MN, D=1 * MN, R=c_mn * MN, M=3 * MN,
        # scenario knobs, inert for the paper modes: a 3x-cost disk tier
        # covering the non-buddy failures, and a half-checkpoint-cost
        # verification step
        C2=3 * c_mn * MN, R2=3 * c_mn * MN, f=0.8, V=0.5 * c_mn * MN,
    )
    work = 5 * 86400.0
    pred = PredictorModel(recall, precision, window=window, lead=3600.0)
    if mode == "none":
        strat = S.young(plat)
    elif mode == "exact":
        strat = S.instant(plat, pred) if window > 0 else S.exact_prediction(plat, pred)
    elif mode == "two_level":
        # exact-date predictions only (proactive memory checkpoints);
        # q <= 0 draws the untrusted factory variant
        epred = dataclasses.replace(pred, window=0.0)
        strat = S.two_level(plat, epred if q > 0 else None)
        pred = epred
    elif mode == "silent":
        strat = S.silent(plat)  # corruptions are never predicted: q = 0
    elif mode == "nockpt":
        strat = S.nockpt(plat, pred)
    elif mode == "withckpt":
        strat = S.withckpt(plat, pred)
    else:
        strat = S.migration(plat, pred)
    if q != strat.q and strat.mode not in _FIXED_Q_MODES:
        strat = dataclasses.replace(strat, q=q)
    cells = (
        ExperimentCell(
            "base/Young", work, plat, pred, S.young(plat),
            fault_dist=LAWS[law_key],
        ),
        ExperimentCell(
            f"rand/{strat.name}", work, plat, pred, strat,
            fault_dist=LAWS[law_key],
        ),
    )
    return GridSpec(cells, n_runs=3, seed=seed)


def _assert_lanes_equal(a, b, exact=True, context=""):
    for ca, cb in zip(a.cells, b.cells):
        if exact:
            np.testing.assert_array_equal(
                ca.makespan, cb.makespan, err_msg=f"{context}:{ca.cell.label}"
            )
        else:
            np.testing.assert_allclose(
                ca.makespan, cb.makespan, rtol=1e-12, atol=1e-6,
                err_msg=f"{context}:{ca.cell.label}",
            )
        np.testing.assert_array_equal(
            ca.n_faults, cb.n_faults, err_msg=f"{context}:{ca.cell.label}"
        )


def _check_differential(mu_mn, c_mn, law_key, mode, window, q, recall,
                        precision, seed):
    grid = _make_grid(
        mu_mn, c_mn, law_key, mode, window, q, recall, precision, seed
    )
    # ---- host trace mode: three engines, two dispatch granularities --- #
    sb = run_grid(grid, engine="batch")
    sj = run_grid(grid, engine="jax")
    _assert_lanes_equal(sj, sb, exact=False, context="jax-vs-batch")
    sjp = run_grid(grid, engine="jax", dispatch="percell")
    sbp = run_grid(grid, engine="batch", dispatch="percell")
    if q in (0.0, 1.0):  # deterministic trust: dispatch is invisible
        _assert_lanes_equal(sjp, sj, context="jax-percell-vs-fused")
        _assert_lanes_equal(sbp, sb, context="batch-percell-vs-fused")
        ss = run_grid(grid, engine="scalar")
        for cs, cb in zip(ss.cells, sb.cells):
            np.testing.assert_allclose(
                cs.makespan, cb.makespan, atol=MK_TOL,
                err_msg=f"scalar-vs-batch:{cs.cell.label}",
            )
            np.testing.assert_array_equal(cs.n_faults, cb.n_faults)

    # ---- device trace mode (counter streams) -------------------------- #
    sjd = run_grid(grid, engine="jax", trace_mode="device")
    sjdp = run_grid(grid, engine="jax", trace_mode="device", dispatch="percell")
    _assert_lanes_equal(sjdp, sjd, context="device-percell-vs-fused")
    sbd = run_grid(grid, engine="batch", trace_mode="device")
    # the materialized replay is the same event sequence, windows
    # included (pending TPs are served in window-start order on both
    # sides) — float-rounding agreement
    _assert_lanes_equal(sjd, sbd, exact=False, context="device-jax-vs-batch")
    # per-cell mean waste is engine-invariant within MC resolution
    for ca, cb in zip(sjd.cells, sbd.cells):
        assert abs(ca.mean_waste - cb.mean_waste) < 2e-3, ca.cell.label

    # ---- mixed-law grid: the drawn law + its successor in one fused
    # dispatch through the law-indexed sampler ----------------------- #
    law2 = sorted(LAWS)[(sorted(LAWS).index(law_key) + 1) % len(LAWS)]
    mixed = GridSpec(
        tuple(
            dataclasses.replace(
                c, label=f"{lk}/{c.label}", fault_dist=LAWS[lk]
            )
            for lk in (law_key, law2)
            for c in grid.cells
        ),
        n_runs=grid.n_runs, seed=grid.seed,
    )
    mf = run_grid(mixed, engine="jax", trace_mode="device")
    mpf = run_grid(
        mixed, engine="jax", trace_mode="device", dispatch="perfamily"
    )
    # per-family runs the same law-indexed sampler: bit-identical
    _assert_lanes_equal(mpf, mf, context="mixed-perfamily-vs-fused")
    mpc = run_grid(
        mixed, engine="jax", trace_mode="device", dispatch="percell"
    )
    # per-cell uses the law-*specialized* static samplers: exact up to
    # XLA's per-context transcendental fusion (lognormal ~1e-12 rel)
    for ca, cb in zip(mf.cells, mpc.cells):
        np.testing.assert_allclose(
            ca.makespan, cb.makespan, rtol=1e-9,
            err_msg=f"mixed-percell-vs-fused:{ca.cell.label}",
        )


def _params_from_seed(i: int):
    rng = np.random.default_rng(1000 + i)
    return {
        "mu_mn": float(rng.uniform(400.0, 2000.0)),
        "c_mn": float(rng.uniform(3.0, 15.0)),
        "law_key": sorted(LAWS)[i % len(LAWS)],
        "mode": MODES[i % len(MODES)],
        "window": [0.0, 1500.0, 4000.0][i % 3],
        "q": float(i % 2),
        "recall": float(rng.uniform(0.3, 0.95)),
        "precision": float(rng.uniform(0.3, 0.95)),
        "seed": int(rng.integers(0, 10_000)),
    }


try:
    from hypothesis import given, settings, strategies as st
except ImportError:

    @pytest.mark.parametrize("i", range(N_EXAMPLES))
    def test_differential_engines(i):
        _check_differential(**_params_from_seed(i))

else:

    # derandomize: the example set is deterministic per budget — same
    # contract as the fixed-seed fallback, and no irreproducible
    # CI-only failures
    @settings(max_examples=N_EXAMPLES, deadline=None, derandomize=True)
    @given(
        mu_mn=st.floats(400.0, 2000.0),
        c_mn=st.floats(3.0, 15.0),
        law_key=st.sampled_from(sorted(LAWS)),
        mode=st.sampled_from(MODES),
        window=st.sampled_from([0.0, 1500.0, 4000.0]),
        q=st.sampled_from([0.0, 1.0]),
        recall=st.floats(0.3, 0.95),
        precision=st.floats(0.3, 0.95),
        seed=st.integers(0, 10_000),
    )
    def test_differential_engines(
        mu_mn, c_mn, law_key, mode, window, q, recall, precision, seed
    ):
        _check_differential(
            mu_mn, c_mn, law_key, mode, window, q, recall, precision, seed
        )


@pytest.mark.parametrize(
    "mode,q",
    [("two_level", 0.0), ("two_level", 1.0), ("silent", 0.0)],
)
def test_scenario_modes_differential(mode, q):
    """Guaranteed coverage of the scenario phase families regardless of
    the fuzz budget: two-level (untrusted + predictor-trusted) and
    silent-error lanes through the full three-engine / two-trace-mode /
    two-dispatch differential contract."""
    _check_differential(
        mu_mn=900.0, c_mn=6.0, law_key="weibull0.7", mode=mode,
        window=0.0, q=q, recall=0.8, precision=0.7, seed=42,
    )


def test_fractional_trust_dispatch_invariance():
    """Device trace mode draws trust coins from per-event counter
    streams, so even fractional q is bit-identical between fused and
    per-cell dispatch (host mode only promises distributional agreement
    there)."""
    grid = _make_grid(
        mu_mn=800.0, c_mn=8.0, law_key="exp", mode="exact", window=0.0,
        q=0.5, recall=0.8, precision=0.6, seed=77,
    )
    fused = run_grid(grid, engine="jax", trace_mode="device")
    percell = run_grid(
        grid, engine="jax", trace_mode="device", dispatch="percell"
    )
    _assert_lanes_equal(percell, fused, context="frac-q-device")
    # and the trusted cell actually acts on some predictions
    assert sum(c.mean_proactive_ckpts for c in fused.cells) > 0


def test_fuzz_examples_budget_env():
    """The nightly knob is wired: the example budget follows the env."""
    assert N_EXAMPLES >= 1
    assert math.isfinite(N_EXAMPLES)
