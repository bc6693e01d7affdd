"""Statistical acceptance suite: simulation vs the paper's analytic models.

Runs the paper grid (both predictors, exact + window strategies, all
platform sizes of the ``validation`` preset) through the fused device
engine and asserts, cell by cell, that the simulated waste is
statistically compatible with the closed-form :mod:`repro.core.waste`
predictions under validity-scaled equivalence margins, with
Holm–Bonferroni control pinning the suite's family-wise false-alarm rate
(see :mod:`repro.experiments.validation` for the contract).

The per-cell z-score table is written to ``$REPRO_VALIDATION_DIR`` when
set (the CI validation job uploads it as an artifact).

Environment knobs: ``REPRO_VALIDATION_RUNS`` (Monte-Carlo repetitions per
cell, default 200) lets nightly jobs buy more power.
"""

import csv
import math
import os

import numpy as np
import pytest

from repro.core import Platform, PredictorModel
from repro.core import simulator as S
from repro.core import waste as W
from repro.experiments import (
    ExperimentCell,
    GridSpec,
    SweepResult,
    paper_grid_cells,
    run_grid,
)
from repro.experiments.paper_grid import silent_grid_cells, two_level_grid_cells
from repro.experiments.validation import (
    analytic_waste,
    cell_z_rows,
    holm_bonferroni,
    model_validity,
    validate_sweep,
    write_z_table,
)

N_RUNS = int(os.environ.get("REPRO_VALIDATION_RUNS", "200"))
SEED = 11
ALPHA = 0.01


@pytest.fixture(scope="module")
def paper_sweep():
    """One fused device-engine sweep of the validation paper grid,
    shared by every test in the module (device-reduced statistics: the
    suite itself exercises the tentpole collect='stats' path)."""
    grid = GridSpec(
        tuple(paper_grid_cells("validation")), n_runs=N_RUNS, seed=SEED
    )
    return run_grid(grid, engine="jax", trace_mode="device", collect="stats")


@pytest.fixture(scope="module")
def paper_rows(paper_sweep):
    """Full-grid z-table, written as the CI artifact before any
    assertion can fail."""
    rows, _ = validate_sweep(paper_sweep, alpha=ALPHA)
    art = os.environ.get("REPRO_VALIDATION_DIR")
    if art:
        os.makedirs(art, exist_ok=True)
        write_z_table(
            rows,
            os.path.join(art, "validation_ztable.csv"),
            os.path.join(art, "validation_ztable.json"),
        )
    return rows


@pytest.fixture(scope="module")
def scenario_sweep():
    """The two new phase families — two-level (memory + disk tiers, with
    and without a trusted predictor) and silent errors (verified
    checkpoints, detection-latency rollback) — through the SAME fused
    device dispatch with device-reduced statistics as the paper grid."""
    cells = tuple(two_level_grid_cells("validation")) + tuple(
        silent_grid_cells("validation")
    )
    grid = GridSpec(cells, n_runs=N_RUNS, seed=SEED)
    return run_grid(grid, engine="jax", trace_mode="device", collect="stats")


def _subset(sweep: SweepResult, keep) -> SweepResult:
    cells = [cr for cr in sweep.cells if keep(cr.cell)]
    return SweepResult(
        grid=sweep.grid, cells=cells, engine=sweep.engine,
        wall_time_s=0.0, dispatch=sweep.dispatch, collect=sweep.collect,
    )


def _assert_no_rejects(sweep):
    rows, fails = validate_sweep(sweep, alpha=ALPHA)
    assert not fails, "cells out of the analytic envelope:\n" + "\n".join(
        f"  {r.label}: sim={r.mean_sim:.4f} analytic={r.analytic:.4f} "
        f"margin={r.margin:.4f} z={r.z:.2f}"
        for r in fails
    )
    return rows


def test_exact_predictor_cells_match_theory(paper_sweep):
    """Equations (1)/(3) + Young: every exact-date-predictor cell (and
    the q=0 baselines and migration cells) sits inside its margin."""
    sub = _subset(paper_sweep, lambda c: c.predictor.window == 0.0)
    assert len(sub.cells) >= 18
    rows = _assert_no_rejects(sub)
    # the grid genuinely exercises prediction: trusted cells beat their
    # Young baseline where theory says they should (large mu)
    assert any(r.strategy in ("ExactPrediction", "Migration") for r in rows)


def test_window_predictor_cells_match_theory(paper_sweep):
    """Equations (4)/(5)/(6): every window-predictor cell (Instant /
    NoCkptI / WithCkptI at both window lengths) sits inside its margin."""
    sub = _subset(paper_sweep, lambda c: c.predictor.window > 0.0)
    assert len(sub.cells) >= 36
    rows = _assert_no_rejects(sub)
    assert {r.strategy for r in rows} >= {"Instant", "NoCkptI", "WithCkptI"}


def test_full_grid_family_controlled(paper_rows):
    """The headline gate: Holm over the *entire* paper grid rejects
    nothing, and the z-table covers every cell with finite statistics."""
    assert not [r for r in paper_rows if r.reject]
    assert all(math.isfinite(r.z) for r in paper_rows)
    assert all(r.se_sim > 0 for r in paper_rows)


def test_two_level_cells_match_theory(scenario_sweep):
    """The corrected two-level model (prediction shields only the memory
    tier): every untrusted AND predictor-trusted two-level cell sits
    inside its margin.  The trusted cells are the regression sentinel —
    under the old (1-rq)-scaled disk term they overshot by up to +0.30
    absolute waste (z ~ +58)."""
    sub = _subset(scenario_sweep, lambda c: c.label.startswith("tl/"))
    assert len(sub.cells) >= 18
    rows = _assert_no_rejects(sub)
    trusted = [r for r in rows if r.label.count("/") == 4]
    untrusted = [r for r in rows if r.label.count("/") == 3]
    assert trusted and untrusted
    assert all(r.strategy == "TwoLevel" for r in rows)


def test_silent_cells_match_theory(scenario_sweep):
    """The silent-error model (arXiv:1310.8486 detection-latency
    rollback): every verified-checkpoint cell sits inside its margin at
    both verification costs.  Under the strike-cursor clobbering bug the
    fused device path simulated zero corruptions (zero variance,
    z = +inf); these cells pin the counter-stream contract."""
    sub = _subset(scenario_sweep, lambda c: c.label.startswith("sil/"))
    assert len(sub.cells) >= 6
    rows = _assert_no_rejects(sub)
    assert all(r.strategy == "Silent" for r in rows)
    # the cells genuinely corrupt: Monte-Carlo noise is present
    assert all(r.se_sim > 0 for r in rows)


def test_scenario_grid_family_controlled(scenario_sweep):
    """Holm over the combined two-level + silent grid rejects nothing,
    with finite statistics in every cell (the acceptance gate of the
    scenario phase families)."""
    rows, fails = validate_sweep(scenario_sweep, alpha=ALPHA)
    assert not fails
    assert all(math.isfinite(r.z) for r in rows)
    assert all(r.se_sim > 0 for r in rows)
    assert len(rows) >= 24


def test_suite_catches_an_engine_regression(paper_sweep):
    """Power check: shifting one cell's simulated waste just past its
    overshoot margin (by 10 standard errors — the scale a lost-work
    accounting bug produces at any Monte-Carlo budget) is flagged by the
    Holm pass.  Stated relative to the cell's own margin and se so the
    check holds for every REPRO_VALIDATION_RUNS setting."""
    import copy

    from repro.experiments.validation import ABS_MARGIN, REL_MARGIN_HI

    tampered = copy.deepcopy(paper_sweep)
    victim = tampered.cells[7]
    wa = analytic_waste(victim.cell)
    se = victim.ci95_waste / 1.96
    victim.stats["mean_waste"] = (
        wa + REL_MARGIN_HI * abs(wa) + ABS_MARGIN + 10.0 * se
    )
    _, fails = validate_sweep(tampered, alpha=ALPHA)
    assert any(r.label == victim.cell.label for r in fails), (
        "a margin+10se waste shift went undetected"
    )


def test_z_table_artifact_roundtrip(paper_rows, tmp_path):
    """The artifact writer emits a parseable CSV + JSON with one row per
    cell and the Holm verdict column."""
    csv_path = tmp_path / "ztable.csv"
    json_path = tmp_path / "ztable.json"
    write_z_table(paper_rows, csv_path, str(json_path))
    with open(csv_path) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == len(paper_rows)
    assert {"label", "z", "p", "margin", "reject", "validity"} <= set(rows[0])
    import json

    payload = json.loads(json_path.read_text())
    assert payload["n_cells"] == len(paper_rows)
    assert payload["n_rejected"] == 0


# ---------------------------------------------------------------------- #
# unit tests of the statistical machinery
# ---------------------------------------------------------------------- #
def test_holm_bonferroni_step_down():
    # m=4, alpha=0.05 -> step-down thresholds .0125 / .0167 / .025 / .05:
    # 0.04 > 0.025 stops the walk, retaining everything larger too
    rej = holm_bonferroni([0.010, 0.013, 0.04, 0.20], alpha=0.05)
    assert rej.tolist() == [True, True, False, False]
    # rejection set is order-independent (sorted internally)
    rej = holm_bonferroni([0.010, 0.020, 0.011, 0.9], alpha=0.05)
    assert rej.tolist() == [True, True, True, False]
    assert holm_bonferroni([], alpha=0.05).shape == (0,)
    # uniformly more powerful than plain Bonferroni, never less
    p = [0.001, 0.012, 0.3]
    bonf = [pi <= 0.05 / 3 for pi in p]
    holm = holm_bonferroni(p, alpha=0.05)
    assert all(h or not b for h, b in zip(holm, bonf))


def test_holm_bonferroni_pins_family_wise_error():
    """Monte-Carlo FWER check: under the global null (uniform p-values)
    the fraction of families with >= 1 rejection stays ~alpha."""
    rng = np.random.default_rng(5)
    alpha, m, fam = 0.05, 20, 2000
    hits = sum(
        holm_bonferroni(rng.random(m), alpha=alpha).any() for _ in range(fam)
    )
    # FWER <= alpha; allow 4 sigma of binomial noise above it
    assert hits / fam <= alpha + 4 * math.sqrt(alpha * (1 - alpha) / fam)


def test_analytic_waste_dispatch():
    MN = 60.0
    plat = Platform(mu=1000 * MN, C=10 * MN, D=1 * MN, R=10 * MN, M=5 * MN)
    pred = PredictorModel(0.85, 0.82)
    predw = PredictorModel(0.85, 0.82, window=3000.0)

    def cell(strat, p=pred):
        return ExperimentCell("x", 6 * 86400.0, plat, p, strat)

    y = S.young(plat)
    assert analytic_waste(cell(y)) == pytest.approx(
        W.waste_young(y.T_R, plat.C, plat.D, plat.R, plat.mu)
    )
    e = S.exact_prediction(plat, pred)
    assert analytic_waste(cell(e)) == pytest.approx(
        W.waste_exact(e.T_R, 1.0, plat.C, plat.D, plat.R, plat.mu, 0.85, 0.82)
    )
    m = S.migration(plat, pred)
    assert analytic_waste(cell(m)) == pytest.approx(
        W.waste_migration(
            m.T_R, 1.0, plat.C, plat.D, plat.R, plat.M, plat.mu, 0.85, 0.82
        )
    )
    i = S.instant(plat, predw)
    assert analytic_waste(cell(i, predw)) == pytest.approx(
        W.waste_instant(
            i.T_R, 1.0, plat.C, plat.D, plat.R, plat.mu, 0.85, 0.82,
            3000.0, 1500.0,
        )
    )
    wc = S.withckpt(plat, predw)
    assert analytic_waste(cell(wc, predw)) == pytest.approx(
        W.waste_withckpt(
            wc.T_R, wc.T_P, 1.0, plat.C, plat.D, plat.R, plat.mu,
            0.85, 0.82, 3000.0, 1500.0,
        )
    )
    # the scenario families dispatch through the same one-cell table:
    # two-level maps (T_m = T_R, T_d = rho T_R) with D+R folded per tier
    plat2 = Platform(
        mu=1000 * MN, C=10 * MN, D=1 * MN, R=10 * MN,
        C2=30 * MN, R2=30 * MN, f=0.85,
    )
    tl = S.two_level(plat2)

    def cell2(strat, p=pred):
        return ExperimentCell("x", 6 * 86400.0, plat2, p, strat)

    def w_tl(s, r=0.0, q=0.0, prec=1.0):
        return W.waste_two_level(
            s.T_R, s.rho * s.T_R, plat2.C, plat2.C2, 0.0,
            plat2.D + plat2.R, plat2.D + plat2.R2, plat2.mu, plat2.f,
            r, q, prec,
        )

    assert analytic_waste(cell2(tl)) == pytest.approx(w_tl(tl))
    tlt = S.two_level(plat2, pred)
    assert tlt.q == 1.0
    assert analytic_waste(cell2(tlt)) == pytest.approx(
        w_tl(tlt, 0.85, 1.0, 0.82)
    )
    plats = Platform(mu=1000 * MN, C=10 * MN, D=1 * MN, R=10 * MN, V=5 * MN)
    sil = S.silent(plats)
    assert analytic_waste(
        ExperimentCell("x", 6 * 86400.0, plats, pred, sil)
    ) == pytest.approx(
        W.waste_silent(
            sil.T_R, plats.C, plats.V, plats.D, plats.R, plats.mu, sil.k_V
        )
    )


def test_model_validity_scales_with_period_and_window():
    MN = 60.0
    pred = PredictorModel(0.85, 0.82)
    predw = PredictorModel(0.85, 0.82, window=6000.0)
    big = Platform(mu=4000 * MN, C=10 * MN, D=1 * MN, R=10 * MN)
    small = Platform(mu=250 * MN, C=10 * MN, D=1 * MN, R=10 * MN)

    def v(plat, p, strat):
        return model_validity(ExperimentCell("x", 1e5, plat, p, strat))

    # shorter MTBF -> larger T/mu_e -> farther from validity
    assert v(small, pred, S.exact_prediction(small, pred)) > v(
        big, pred, S.exact_prediction(big, pred)
    )
    # a window adds proactive occupancy on top of the exact-date value
    assert v(small, predw, S.instant(small, predw)) > v(
        small, pred, S.exact_prediction(small, pred)
    )
    # untrusted baselines never see prediction events
    assert v(big, pred, S.young(big)) == pytest.approx(
        S.young(big).T_R / big.mu
    )


def test_model_validity_scenario_spans():
    """The scenario families widen the validity distance by their actual
    rollback span: two-level by the rho-weighted mixture of tier losses,
    silent by 2 k_V periods (a struck pattern forfeits its full wall
    time, not the T/2 mean loss of a fail-stop fault)."""
    MN = 60.0
    plat = Platform(
        mu=250 * MN, C=10 * MN, D=1 * MN, R=10 * MN,
        C2=40 * MN, R2=40 * MN, f=0.6, V=10 * MN,
    )

    def v(strat):
        return model_validity(
            ExperimentCell("x", 1e5, plat, PredictorModel(0.0, 1.0), strat)
        )

    tl = S.two_level(plat)
    f = plat.f
    assert v(tl) == pytest.approx(
        tl.T_R * (f + (1.0 - f) * tl.rho) / plat.mu
    )
    assert tl.rho > 1  # the span genuinely exceeds one memory period
    sil = S.silent(plat)
    assert v(sil) == pytest.approx(2.0 * sil.T_R * sil.k_V / plat.mu)
    assert v(sil) > sil.T_R / plat.mu


def test_cell_z_rows_margin_sides(paper_sweep):
    """The asymmetric margin: overshoot cells get the tight hi margin,
    undershoot cells the validity-scaled lo margin (>= the base)."""
    rows = cell_z_rows(paper_sweep)
    for r in rows:
        if r.delta > 0:
            assert r.margin == pytest.approx(0.12 * abs(r.analytic) + 0.004)
        else:
            assert r.margin >= 0.10 * abs(r.analytic) + 0.004 - 1e-12
            assert r.margin <= 0.55 * abs(r.analytic) + 0.004 + 1e-12


def test_x32_paper_length_cell_matches_theory():
    """The TPU's float32 clock finishes a paper-length job and keeps the
    contract: p82r85/N16384/Young (8-day job, makespans near 7e5 s, where
    a float32 clock steps 0.0625 s) converges at ``precision="x32"`` with
    no exhausted lane, and its mean waste sits inside the cell's
    validation margin.  With an absolute 1e-6 s end-of-period tolerance
    the lanes stop advancing and never finish."""
    from repro.core.jax_sim import LAST_TIMINGS, simulate_batch_jax
    from repro.experiments.runner import _stats_cell_result, build_fused_layout

    cell = next(
        c for c in paper_grid_cells("validation")
        if c.label == "p82r85/N16384/Young"
    )
    assert cell.work == 8 * 86400.0
    grid = GridSpec((cell,), n_runs=64, seed=SEED)
    lay = build_fused_layout(grid, "device")
    sums = simulate_batch_jax(
        lay.work_c, lay.plats_c, lay.strats_c, lay.concat_spec(),
        precision="x32", collect="stats", max_iters=100_000,
    )
    assert LAST_TIMINGS["precision"] == "x32"
    assert sums.n[0] == 64 and sums.n_exhausted[0] == 0
    assert np.asarray(sums.mean_makespan)[0] > 6e5
    sweep = SweepResult(
        grid=grid, cells=[_stats_cell_result(cell, sums, 0)], engine="jax",
        wall_time_s=0.0, dispatch="fused", collect="stats",
    )
    rows, fails = validate_sweep(sweep, alpha=ALPHA)
    assert not fails and rows[0].se_sim > 0
