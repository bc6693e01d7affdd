"""The differentiable analytic layer and the unified optimizer API.

Four contracts pinned here:

* the branchless table waste models (:mod:`repro.core.analytic`) agree
  with the scalar :mod:`repro.core.waste` dispatch to float rounding on
  the whole validation grid, and their jnp twins
  (:mod:`repro.kernels.analytic`) agree with the NumPy side under x64;
* the jnp models are *differentiable*: ``jax.grad`` matches central
  finite differences of the NumPy twin (randomized parameter draws —
  hypothesis when available, fixed-seed sweep otherwise);
* the batched safeguarded-Newton optimizer dominates the host period
  scan on every grid cell and lands on the closed-form extremizer for
  the smooth families;
* ``repro.core.optimize`` reproduces every legacy ``optimize_*`` /
  ``best_policy`` / ``best_period_search`` result (the legacy names
  still work but warn), and the :class:`EngineConfig` deprecation shims
  keep the old ad-hoc engine keywords behaviour-identical.
"""

import warnings
from dataclasses import replace

import jax
import numpy as np
import pytest

from repro.core import Platform, PredictorModel, optimize
from repro.core import analytic as A
from repro.core import periods as P
from repro.core import simulator as S
from repro.core import waste as W
from repro.core.analytic import PolicyTable
from repro.core.engine import EngineConfig
from repro.core.periods import OptimalPolicy
from repro.experiments import (
    ExperimentCell,
    GridSpec,
    paper_grid_cells,
    paper_policy_table,
    run_grid,
)
from repro.experiments.validation import analytic_waste, analytic_waste_batch
from repro.kernels import analytic as K

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False


def _x64():
    """x64 tracing context."""
    return jax.enable_x64(True)


def _scalar_waste(cell: ExperimentCell) -> float:
    """The legacy per-cell scalar dispatch (the pre-table
    ``validation.analytic_waste``), kept here as the oracle."""
    s, p, pred = cell.strategy, cell.platform, cell.predictor
    r, prec, I = pred.recall, pred.precision, pred.window
    if s.mode == "none" or s.q <= 0.0 or r <= 0.0:
        return W.waste_young(s.T_R, p.C, p.D, p.R, p.mu)
    if s.mode == "exact":
        if I > 0.0:
            return W.waste_instant(
                s.T_R, s.q, p.C, p.D, p.R, p.mu, r, prec, I, pred.e_f
            )
        return W.waste_exact(s.T_R, s.q, p.C, p.D, p.R, p.mu, r, prec)
    if s.mode == "migration":
        m = p.M if p.M is not None else p.C
        return W.waste_migration(s.T_R, s.q, p.C, p.D, p.R, m, p.mu, r, prec)
    if s.mode == "nockpt":
        return W.waste_nockpt(
            s.T_R, s.q, p.C, p.D, p.R, p.mu, r, prec, I, pred.e_f
        )
    if s.mode == "withckpt":
        return W.waste_withckpt(
            s.T_R, s.T_P, s.q, p.C, p.D, p.R, p.mu, r, prec, I, pred.e_f
        )
    raise ValueError(s.mode)


def _table_precision(tabs):
    with np.errstate(invalid="ignore"):
        return A.precision_from_fp(tabs["mtbf"], tabs["fp_mean"], tabs["recall"])


@pytest.fixture(scope="module")
def vcells():
    return paper_grid_cells("validation")


PLAT = Platform(mu=7500.0, C=600.0, D=60.0, R=300.0, M=300.0)
PREDS = [
    PredictorModel(0.85, 0.82),
    PredictorModel(0.7, 0.4),
    PredictorModel(0.85, 0.82, window=1200.0),
    PredictorModel(0.7, 0.4, window=6000.0),
    PredictorModel(0.0, 1.0),
]


# --------------------------------------------------------------------------- #
# Table waste models vs the scalar formulas
# --------------------------------------------------------------------------- #
class TestTableWaste:
    def test_matches_scalar_dispatch(self, vcells):
        got = A.analytic_waste_cells(vcells)
        want = np.array([_scalar_waste(c) for c in vcells])
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)

    def test_validation_reroute(self, vcells):
        batch = analytic_waste_batch(vcells)
        for wa, c in zip(batch, vcells):
            assert analytic_waste(c) == pytest.approx(float(wa), abs=1e-15)
            assert abs(float(wa) - _scalar_waste(c)) <= 1e-12

    def test_validation_batch_empty(self):
        out = analytic_waste_batch([])
        assert out.shape == (0,)

    def test_validation_unknown_mode(self, vcells):
        bad = replace(
            vcells[0], strategy=replace(vcells[0].strategy, mode="bogus")
        )
        with pytest.raises(ValueError, match="no analytic model"):
            analytic_waste_batch([bad])

    def test_precision_roundtrip(self):
        mu = np.array([7500.0, 3600.0, 1e5])
        r = np.array([0.85, 0.7, 0.0])
        p = np.array([0.82, 0.4, 1.0])
        from repro.core.events import false_prediction_mtbf_batch

        fp = false_prediction_mtbf_batch(mu, r, p)
        np.testing.assert_allclose(
            A.precision_from_fp(mu, fp, r), p, rtol=1e-12
        )

    def test_two_level_matches_scalar(self):
        rng = np.random.default_rng(3)
        for _ in range(20):
            C_m, C_d = rng.uniform(20, 200), rng.uniform(200, 900)
            D, R_m, R_d = rng.uniform(10, 90), rng.uniform(10, 90), rng.uniform(90, 500)
            mu = rng.uniform(2e3, 1e5)
            f = rng.uniform(0.1, 0.95)
            r, q, p = rng.uniform(0.05, 0.95), 1.0, rng.uniform(0.1, 0.95)
            T_m, T_d = rng.uniform(400, 3000), rng.uniform(3000, 2e4)
            want = W.waste_two_level(T_m, T_d, C_m, C_d, D, R_m, R_d, mu, f, r, q, p)
            got = A.two_level_waste(
                T_m, T_d, C_m, C_d, D, R_m, R_d, mu, f, r, q, p
            )
            assert got == pytest.approx(want, rel=1e-12)


class TestJnpTwins:
    @pytest.mark.parametrize("scale", [0.6, 1.0, 1.9])
    def test_cell_waste_twin_parity(self, vcells, scale):
        tabs = A.tables_from_cells(vcells)
        T = tabs["T_R"] * scale
        want = A.table_waste(T, tabs)
        p = _table_precision(tabs)
        with _x64():
            got = np.asarray(
                K.cell_waste(
                    T, tabs["mode"].astype(np.int32), tabs["q_eff"],
                    tabs["C"], tabs["DR"], tabs["lead_act"], tabs["mtbf"],
                    tabs["recall"], p, tabs["window"], tabs["T_P"],
                    tabs["tp_eff_default"], tabs["C2"], tabs["DR2"],
                    tabs["V"], tabs["fmem"], tabs["rho"], tabs["kv"],
                )
            )
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)

    def test_precision_twin_parity(self):
        mu = np.array([7500.0, 3600.0])
        fp = np.array([2e4, np.inf])
        r = np.array([0.85, 0.0])
        with _x64():
            got = np.asarray(K.precision_from_fp(mu, fp, r))
        np.testing.assert_allclose(
            got, A.precision_from_fp(mu, fp, r), rtol=1e-12
        )


# --------------------------------------------------------------------------- #
# Differentiability: jax.grad vs central finite differences
# --------------------------------------------------------------------------- #
def _model_cases(m, d):
    """(name, T -> waste) closures over a parameter draw, for module
    ``m`` (the NumPy or the jnp twin — identical signatures)."""
    C, DR, mu = d["C"], d["DR"], d["mu"]
    r, p, I = d["r"], d["p"], d["I"]
    E_f, q, M = I / 2.0, 1.0, 1.3 * d["C"]
    tp = max(1.5 * C, I / 3.0)
    return [
        ("young", lambda T: m.young_waste(T, C, DR, mu)),
        ("exact", lambda T: m.exact_waste(T, q, C, DR, mu, r, p)),
        ("migration", lambda T: m.migration_waste(T, q, C, DR, M, mu, r, p)),
        ("instant", lambda T: m.instant_waste(T, q, C, DR, mu, r, p, E_f)),
        ("nockpt", lambda T: m.nockpt_waste(T, q, C, DR, mu, r, p, I, E_f)),
        ("withckpt", lambda T: m.withckpt_waste(T, tp, q, C, DR, mu, r, p, I, E_f)),
    ]


def _check_grads(seed: int) -> None:
    rng = np.random.default_rng(seed)
    d = {
        "C": rng.uniform(60.0, 1200.0),
        "DR": rng.uniform(30.0, 500.0),
        "mu": rng.uniform(1800.0, 1e5),
        "r": rng.uniform(0.05, 0.95),
        "p": rng.uniform(0.05, 0.98),
        "I": rng.uniform(100.0, 8000.0),
    }
    T = rng.uniform(1.2, 4.0) * np.sqrt(2.0 * d["mu"] * d["C"])
    # stay off the Instant kink at T = I (min(E_f, T/2) switches there)
    if abs(T - d["I"]) < 0.05 * max(T, d["I"]):
        T *= 1.2
    h = 1e-5 * T
    np_cases = dict(_model_cases(A, d))
    with _x64():
        for name, f_jnp in _model_cases(K, d):
            f_np = np_cases[name]
            got = float(jax.grad(f_jnp)(T))
            want = (f_np(T + h) - f_np(T - h)) / (2.0 * h)
            np.testing.assert_allclose(
                got, want, rtol=1e-6, atol=1e-10,
                err_msg=f"grad mismatch for {name} (seed {seed})",
            )


class TestGradients:
    if HAVE_HYPOTHESIS:

        @settings(max_examples=25, deadline=None)
        @given(seed=st.integers(0, 2**31 - 1))
        def test_grad_matches_finite_differences(self, seed):
            _check_grads(seed)

    else:

        @pytest.mark.parametrize("seed", range(25))
        def test_grad_matches_finite_differences(self, seed):
            _check_grads(seed)

    @pytest.mark.parametrize("scale", [0.8, 1.6])
    def test_table_grad_matches_finite_differences(self, vcells, scale):
        tabs = A.tables_from_cells(vcells)
        T = tabs["T_R"] * scale
        # mask the Instant kink cells whose evaluation point sits on it
        kink = (
            (tabs["mode"] == 1)
            & (tabs["window"] > 0.0)
            & (np.abs(T - tabs["window"]) < 0.02 * np.maximum(T, tabs["window"]))
        )
        h = 1e-5 * T
        want = (A.table_waste(T + h, tabs) - A.table_waste(T - h, tabs)) / (2 * h)
        p = _table_precision(tabs)
        cols = (
            tabs["mode"].astype(np.int32), tabs["q_eff"], tabs["C"],
            tabs["DR"], tabs["lead_act"], tabs["mtbf"], tabs["recall"], p,
            tabs["window"], tabs["T_P"], tabs["tp_eff_default"],
            tabs["C2"], tabs["DR2"], tabs["V"], tabs["fmem"],
            tabs["rho"], tabs["kv"],
        )
        with _x64():
            grad_v = jax.vmap(jax.grad(K.cell_waste), in_axes=(0,) * 18)
            got = np.asarray(grad_v(T, *cols))
        np.testing.assert_allclose(
            got[~kink], want[~kink], rtol=1e-6, atol=1e-10
        )


# --------------------------------------------------------------------------- #
# The batched Newton optimizer
# --------------------------------------------------------------------------- #
class TestNewtonOptimizer:
    @pytest.fixture(scope="class")
    def newton_sol(self, vcells):
        tabs = A.tables_from_cells(vcells)
        return tabs, A.newton_optimize_tables(tabs)

    def test_dominates_host_period_scan(self, vcells, newton_sol):
        _, sol = newton_sol
        worse = []
        for i, c in enumerate(vcells):
            periods = [
                max(c.platform.C * 1.01, c.strategy.T_R * m)
                for m in S.PERIOD_GRID
            ]
            best = min(
                min(
                    _scalar_waste(
                        replace(c, strategy=replace(c.strategy, T_R=t))
                    ),
                    1.0,
                )
                for t in periods
            )
            if sol["waste"][i] > best + 1e-9:
                worse.append((c.label, float(sol["waste"][i]), best))
        assert not worse, f"Newton beaten by the host scan on {worse}"

    def test_period_matches_closed_form_extremizer(self, vcells, newton_sol):
        tabs, sol = newton_sol
        te = A.analytic_period_cells(vcells)
        # smooth families only: the Instant objective is kinked at T = I,
        # and cells whose q case analysis dropped to q=0 optimize Young's
        # model, not the q_eff one the closed form describes
        smooth = (
            (tabs["q_eff"] > 0.0)
            & (tabs["recall"] > 0.0)
            & (sol["q"] == tabs["q_eff"])
            & ~((tabs["mode"] == 1) & (tabs["window"] > 0.0))
        )
        assert smooth.any()
        np.testing.assert_allclose(
            sol["T_R"][smooth], te[smooth], rtol=1e-9
        )

    @pytest.mark.parametrize("pred", PREDS)
    @pytest.mark.parametrize(
        "family",
        ["young", "daly", "exact", "instant", "nockpt", "withckpt",
         "migration", "best"],
    )
    def test_newton_vs_analytic_policies(self, family, pred):
        newt = optimize(family, PLAT, pred, method="newton")
        assert isinstance(newt, OptimalPolicy)
        if family == "exact" and pred.window > 0.0:
            # a window predictor has no exact dates: the shared table
            # marks such a cell as the Instant objective (the lost-time
            # term q r min(E_f, T/2) is physically there), so the host
            # counterpart of the Newton answer is the Instant analysis
            inst = optimize("instant", PLAT, pred, method="newton")
            assert newt.waste == pytest.approx(inst.waste, abs=1e-12)
            host = optimize("instant", PLAT, pred, method="analytic")
        else:
            host = optimize(family, PLAT, pred, method="analytic")
        assert newt.waste <= host.waste + 1e-9
        # equality breaks where the two sides model different things:
        # Daly's period is not the model extremizer, and a degenerate
        # (window-free) WithCkptI falls back to q=0 on the host side but
        # degenerates to the exact-date strategy in the simulator tables
        if family == "daly" or (family == "withckpt" and pred.window <= 0.0):
            return
        assert newt.waste == pytest.approx(host.waste, abs=1e-9)
        if newt.q == host.q:
            assert newt.T_R == pytest.approx(host.T_R, rel=1e-6)

    def test_batched_newton_matches_scalar_calls(self):
        names = ["exact", "young", "best", "nockpt"]
        preds = [PREDS[0], PREDS[1], PREDS[2], PREDS[3]]
        table = optimize(names, PLAT, preds, method="newton")
        assert isinstance(table, PolicyTable)
        assert len(table) == 4
        for i, (name, pm) in enumerate(zip(names, preds)):
            one = optimize(name, PLAT, pm, method="newton")
            assert table.waste[i] == pytest.approx(one.waste, abs=1e-12)
            assert table.T_R[i] == pytest.approx(one.T_R, rel=1e-12)

    def test_padding_rows_do_not_leak(self, vcells):
        # a 3-cell table pads to 8 benign rows; results must match the
        # same cells solved inside the full grid
        sub = list(vcells[:3])
        sol3 = A.newton_optimize_tables(A.tables_from_cells(sub))
        soln = A.newton_optimize_tables(A.tables_from_cells(vcells))
        for k in ("T_R", "q", "waste"):
            assert sol3[k].shape == (3,)
            np.testing.assert_allclose(sol3[k], soln[k][:3], rtol=1e-12)


# --------------------------------------------------------------------------- #
# The unified optimizer API and its deprecated aliases
# --------------------------------------------------------------------------- #
def _same_policy(a: OptimalPolicy, b: OptimalPolicy) -> None:
    assert a.strategy == b.strategy
    assert a.q == b.q
    assert a.T_R == pytest.approx(b.T_R, rel=1e-15)
    assert a.waste == pytest.approx(b.waste, rel=1e-15)
    assert a.T_P == b.T_P and a.k_P == b.k_P


class TestOptimizeAPI:
    @pytest.mark.parametrize(
        "family,legacy",
        [
            ("exact", "optimize_exact"),
            ("migration", "optimize_migration"),
            ("instant", "optimize_instant"),
            ("nockpt", "optimize_nockpt"),
            ("withckpt", "optimize_withckpt"),
            ("best", "best_policy"),
        ],
    )
    @pytest.mark.parametrize("pred", PREDS)
    def test_matches_legacy_alias(self, family, legacy, pred):
        with pytest.warns(DeprecationWarning, match=f"{legacy}.*deprecated"):
            old = getattr(P, legacy)(PLAT, pred)
        new = optimize(family, PLAT, pred)
        _same_policy(new, old)
        assert new.objective == "waste" and new.value == new.waste

    def test_young_daly_match_legacy_periods(self):
        with pytest.warns(DeprecationWarning):
            ty = P.t_young(PLAT.mu, PLAT.C)
        assert optimize("young", PLAT, capped=True).T_R == pytest.approx(ty)
        with pytest.warns(DeprecationWarning):
            td = P.t_daly(PLAT.mu, PLAT.R, PLAT.C)
        assert optimize("daly", PLAT).T_R == pytest.approx(max(td, PLAT.C))
        with pytest.warns(DeprecationWarning):
            te = P.t_extr(PLAT.mu, PLAT.C)
        assert optimize("young", PLAT).T_R == pytest.approx(max(te, PLAT.C))

    def test_availability_objective(self):
        w = optimize("exact", PLAT, PREDS[0])
        av = optimize("exact", PLAT, PREDS[0], objective="availability")
        assert av.objective == "availability"
        assert av.T_R == w.T_R and av.q == w.q  # same argmin
        assert av.value == pytest.approx(1.0 - av.waste)
        table = optimize(
            ("exact", "young"), PLAT, PREDS[0], objective="availability"
        )
        np.testing.assert_allclose(table.value, 1.0 - table.waste)

    def test_policy_table_container(self):
        table = optimize(["young", "daly", "exact", "best"], PLAT, PREDS[0])
        assert len(table) == 4
        pols = list(table)
        assert all(isinstance(p, OptimalPolicy) for p in pols)
        assert table[2].strategy == "exact"
        assert pols[0].strategy == "young"

    def test_search_matches_deprecated_best_period_search(self):
        work, pred = 4 * 3600.0, PREDS[0]
        base = S.exact_prediction(PLAT, pred)
        with pytest.warns(DeprecationWarning, match="best_period_search"):
            t_old, w_old = S.best_period_search(
                work, PLAT, base, pred, n_runs=2, seed=5
            )
        pol = optimize(
            "exact", PLAT, pred, method="search", work=work, n_runs=2, seed=5
        )
        assert pol.T_R == t_old
        assert pol.waste == min(w_old, 1.0)

    def test_errors(self):
        with pytest.raises(ValueError, match="unknown strategy 'quantum'"):
            optimize("quantum", PLAT)
        with pytest.raises(ValueError, match="unknown objective 'speed'"):
            optimize("exact", PLAT, PREDS[0], objective="speed")
        with pytest.raises(ValueError, match="unknown method 'sgd'"):
            optimize("exact", PLAT, PREDS[0], method="sgd")
        with pytest.raises(ValueError, match="not supported with method='search'"):
            optimize("best", PLAT, PREDS[0], method="search")
        with pytest.raises(ValueError, match="sequence length"):
            optimize(["exact", "young"], [PLAT], PREDS[0])
        with pytest.raises(ValueError, match="not both"):
            optimize(
                "exact", PLAT, PREDS[0], method="search",
                config=EngineConfig(), engine="batch",
            )

    def test_optimize_cells(self, vcells):
        table = A.optimize_cells(vcells)
        assert len(table) == len(vcells)
        aw = np.minimum(A.analytic_waste_cells(vcells), 1.0)
        assert np.all(table.waste <= aw + 1e-9)
        with pytest.raises(ValueError, match="method='newton' only"):
            A.optimize_cells(vcells[:2], method="analytic")

    def test_paper_policy_table(self, vcells):
        table = paper_policy_table()
        assert isinstance(table, PolicyTable)
        assert len(table) == len(vcells)
        assert table.T_P is not None and len(table.T_P) == len(vcells)


# --------------------------------------------------------------------------- #
# EngineConfig and the legacy-keyword deprecation shims
# --------------------------------------------------------------------------- #
def _tiny_grid():
    plat = Platform(mu=5000.0, C=120.0, D=60.0, R=120.0)
    pred = PredictorModel(0.85, 0.82)
    cell = ExperimentCell(
        "tiny/exact", 4 * 3600.0, plat, pred, S.exact_prediction(plat, pred)
    )
    return GridSpec((cell,), n_runs=3, seed=2)


class TestEngineConfig:
    def test_run_grid_legacy_kwargs_warn_and_match(self):
        grid = _tiny_grid()
        want = run_grid(grid, EngineConfig())
        with pytest.warns(DeprecationWarning, match="run_grid.*deprecated"):
            got = run_grid(grid, engine="batch")
        assert got.cells[0].mean_waste == want.cells[0].mean_waste

    def test_run_grid_positional_engine_string(self):
        grid = _tiny_grid()
        want = run_grid(grid, EngineConfig())
        with pytest.warns(DeprecationWarning):
            got = run_grid(grid, "batch")
        assert got.cells[0].mean_waste == want.cells[0].mean_waste

    def test_simulate_many_legacy_kwargs_warn_and_match(self):
        plat, pred = PLAT, PREDS[0]
        strat = S.exact_prediction(plat, pred)
        want = S.simulate_many(
            4 * 3600.0, plat, strat, pred, n_runs=2, seed=1,
            config=EngineConfig(),
        )
        with pytest.warns(DeprecationWarning, match="simulate_many.*deprecated"):
            got = S.simulate_many(
                4 * 3600.0, plat, strat, pred, n_runs=2, seed=1, engine="batch"
            )
        assert [r.waste for r in got] == [r.waste for r in want]

    def test_config_plus_legacy_is_an_error(self):
        with pytest.raises(ValueError, match="not both"):
            run_grid(_tiny_grid(), EngineConfig(), engine="batch")

    def test_bad_config_type(self):
        with pytest.raises(TypeError, match="must be an EngineConfig"):
            run_grid(_tiny_grid(), 42)

    def test_historical_engine_error_message(self):
        with pytest.raises(ValueError, match="unknown engine 'quantum'"):
            run_grid(_tiny_grid(), EngineConfig(engine="quantum"))

    def test_validate(self):
        with pytest.raises(ValueError, match="require engine='jax'"):
            EngineConfig(devices="all").validate()
        with pytest.raises(ValueError, match="unknown trace_mode"):
            EngineConfig(trace_mode="bogus").validate()
        with pytest.raises(ValueError, match="unknown collect"):
            EngineConfig(collect="bogus").validate()
        cfg = EngineConfig().replace(engine="jax", collect="stats")
        assert cfg.validate() is cfg
        assert cfg.engine == "jax" and cfg.collect == "stats"

    def test_internal_callers_emit_no_deprecations(self):
        # the repo's own entry points all pass EngineConfig explicitly
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            run_grid(_tiny_grid(), EngineConfig())


# --------------------------------------------------------------------------- #
# Shared table layout and the grid's analytic columns
# --------------------------------------------------------------------------- #
class TestTableLayout:
    def test_tables_from_cells_columns(self, vcells):
        tabs = A.tables_from_cells(vcells)
        n = len(vcells)
        for key in A.TABLE_COLS + ("T_R", "fp_mean"):
            assert key in tabs, key
            assert tabs[key].shape[0] == n, key
        assert np.issubdtype(tabs["mode"].dtype, np.integer)
        assert set(np.unique(tabs["mode"])) <= {0, 1, 2, 3, 4}

    def test_sweep_rows_carry_analytic_columns(self):
        sweep = run_grid(_tiny_grid(), EngineConfig())
        row = sweep.to_rows()[0]
        assert "analytic_waste" in row and "analytic_period" in row
        cr = sweep.cells[0]
        assert row["analytic_waste"] == pytest.approx(
            analytic_waste(cr.cell), rel=1e-12
        )
        assert cr.analytic_waste == pytest.approx(
            analytic_waste(cr.cell), rel=1e-12
        )
        assert cr.analytic_period == pytest.approx(
            float(A.analytic_period_cells([cr.cell])[0]), rel=1e-12
        )
