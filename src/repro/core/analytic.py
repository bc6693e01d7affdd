"""Differentiable analytic waste layer over the fused per-cell tables.

Every closed-form waste model of :mod:`repro.core.waste` exists here a
second time as a *branchless, vectorizable* function of per-cell
parameter columns — the exact ``(n_cells,)`` table layout that
:func:`repro.core.jax_sim._cell_tables` ships to the fused device engine
(``C``/``DR``/``T_R``/``T_P``/``mode``/``window``/``lead_act``/
``mtbf``/``fp_mean``/``recall``/``q_eff`` and the law columns) — so ONE
parameter table drives both the analytic and the simulated half of the
reproduction, with no reshaping in between.  Each function has a jnp
twin in :mod:`repro.kernels.analytic` (registered in
``analysis.twins.TWIN_REGISTRY``); the jnp side is differentiable, which
is what the batched safeguarded-Newton period optimizer runs
:func:`jax.grad` through.

On top sits the unified optimizer entry point

    optimize(strategy, platform, pred, *,
             objective="waste" | "availability",
             method="analytic" | "newton" | "search", ...)

which collapses the per-strategy ``optimize_*`` case analyses, the
``t_*`` period helpers and the simulated ``best_period_search`` behind
one API (those legacy names live on as thin deprecated aliases).
Scalar inputs return an :class:`~repro.core.periods.OptimalPolicy`;
sequence inputs return a :class:`PolicyTable` whose ``method="newton"``
path solves every cell's period in one jitted device dispatch.

Precision note: the predictor's precision is *derived* from the table's
``fp_mean`` column (inverting
:func:`repro.core.events.false_prediction_mtbf`), exactly because the
fused engine ships ``fp_mean`` and not ``precision`` — the analytic
layer consumes the engine's table as-is.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from . import batch_sim as B
from . import events as E
from . import periods as P
from . import waste as W
from .periods import OptimalPolicy
from .waste import Platform, PredictorModel, i_prime

__all__ = [
    "precision_from_fp",
    "young_waste",
    "exact_waste",
    "migration_waste",
    "instant_waste",
    "nockpt_waste",
    "withckpt_waste",
    "two_level_waste",
    "silent_waste",
    "cell_waste",
    "table_waste",
    "cell_tables",
    "tables_from_cells",
    "analytic_waste_cells",
    "analytic_period_cells",
    "newton_optimize_tables",
    "PolicyTable",
    "optimize",
    "optimize_cells",
]

#: integer strategy-mode codes of the engine tables (values of
#: ``repro.core.batch_sim.MODE_CODES``, fixed by the packing format)
_M_NONE, _M_EXACT, _M_NOCKPT, _M_WITHCKPT, _M_MIGRATION = 0, 1, 2, 3, 4
_M_TWO_LEVEL, _M_SILENT = 5, 6

#: table columns the analytic layer consumes (subset of
#: ``jax_sim._CELL_TABLE_KEYS``), in the positional order of
#: :func:`cell_waste`'s column arguments after ``T``
TABLE_COLS = (
    "mode", "q_eff", "C", "DR", "lead_act", "mtbf", "recall",
    "window", "T_P", "tp_eff_default",
    "C2", "DR2", "V", "fmem", "rho", "kv",
)


# --------------------------------------------------------------------------- #
# Branchless waste models (NumPy side of the jnp twins)
# --------------------------------------------------------------------------- #
# repro-twin: repro.kernels.analytic.precision_from_fp
def precision_from_fp(mu, fp_mean, r):
    """Precision from the table's false-prediction mean inter-arrival.

    Inverts ``fp_mean = p mu / (r (1 - p))`` to ``p = r fp / (mu + r fp)``;
    an infinite ``fp_mean`` (no false predictions) means precision 1."""
    fin = np.isfinite(fp_mean)
    fp = np.where(fin, fp_mean, 1.0)
    return np.where(fin, r * fp / (mu + r * fp), 1.0)


# repro-twin: repro.kernels.analytic.young_waste
def young_waste(T, C, DR, mu):
    """WASTE^{q=0} (Section 3.3): Young's model over table columns."""
    return C / T + (T / 2.0 + DR) / mu


# repro-twin: repro.kernels.analytic.exact_waste
def exact_waste(T, q, C, DR, mu, r, p):
    """Equation (1): exact-date predictions, branchless."""
    p_safe = np.where(r > 0.0, p, 1.0)
    pred_term = np.where(r > 0.0, (q * r / p_safe) * C, 0.0)
    return C / T + ((1.0 - r * q) * T / 2.0 + DR + pred_term) / mu


# repro-twin: repro.kernels.analytic.migration_waste
def migration_waste(T, q, C, DR, M, mu, r, p):
    """Equation (3): proactive migration, branchless."""
    p_safe = np.where(r > 0.0, p, 1.0)
    pred_term = np.where(r > 0.0, (q * r / p_safe) * M, 0.0)
    return C / T + ((1.0 - r * q) * (T / 2.0 + DR) + pred_term) / mu


# repro-twin: repro.kernels.analytic.instant_waste
def instant_waste(T, q, C, DR, mu, r, p, E_f):
    """Equation (5): strategy Instant, branchless."""
    p_safe = np.where(r > 0.0, p, 1.0)
    pred_term = np.where(r > 0.0, (q * r / p_safe) * C, 0.0)
    lost = q * r * np.minimum(E_f, T / 2.0)
    return C / T + ((1.0 - r * q) * T / 2.0 + DR + pred_term + lost) / mu


# repro-twin: repro.kernels.analytic.nockpt_waste
def nockpt_waste(T, q, C, DR, mu, r, p, I, E_f):
    """Equation (6): strategy NoCkptI, branchless.

    The ``r <= 0`` fallback and the validity clamp ``I' <= mu_P`` of the
    scalar model become selects; divisor inputs are substituted with
    benign values on untaken branches so the jnp twin stays
    NaN-free under :func:`jax.grad`."""
    r_safe = np.where(r > 0.0, r, 0.5)
    p_safe = np.where(r > 0.0, p, 1.0)
    m_p = p_safe * mu / r_safe
    m_np = mu / (1.0 - r_safe)
    ip = np.minimum(i_prime(q, p_safe, I, E_f), m_p)
    reg_frac = 1.0 - ip / m_p
    w = (reg_frac / T + q / m_p) * C
    w = w + (p_safe * (1.0 - q) / m_p) * (T / 2.0)
    w = w + (p_safe * q / m_p) * E_f
    w = w + reg_frac / m_np * (T / 2.0)
    w = w + (p_safe / m_p + reg_frac / m_np) * DR
    return np.where(r > 0.0, w, young_waste(T, C, DR, mu))


# repro-twin: repro.kernels.analytic.withckpt_waste
def withckpt_waste(T, T_P, q, C, DR, mu, r, p, I, E_f):
    """Equation (4): strategy WithCkptI, branchless (see nockpt_waste)."""
    r_safe = np.where(r > 0.0, r, 0.5)
    p_safe = np.where(r > 0.0, p, 1.0)
    m_p = p_safe * mu / r_safe
    m_np = mu / (1.0 - r_safe)
    ip = np.minimum(i_prime(q, p_safe, I, E_f), m_p)
    reg_frac = 1.0 - ip / m_p
    w = (reg_frac / T + (ip / m_p) / T_P + q / m_p) * C
    w = w + (p_safe * (1.0 - q) / m_p) * (T / 2.0)
    w = w + (p_safe * q / m_p) * T_P
    w = w + reg_frac / m_np * (T / 2.0)
    w = w + (p_safe / m_p + reg_frac / m_np) * DR
    return np.where(r > 0.0, w, young_waste(T, C, DR, mu))


# repro-twin: repro.kernels.analytic.two_level_waste
def two_level_waste(T_m, T_d, C_m, C_d, D, R_m, R_d, mu, f, r, q, p):
    """Beyond-paper two-level model, branchless over per-cell columns.

    Canonical signature: ``D``/``R_m``/``R_d`` kept separate, exactly as
    in :func:`repro.core.waste.waste_two_level` (callers holding folded
    ``DR`` columns pass ``D=0``: the terms only ever appear summed).
    Prediction shields only the memory-tier work loss — a disk-tier
    failure destroys the proactive memory checkpoint along with the
    tier."""
    w = C_m / T_m + C_d / T_d
    w = w + (
        f * ((1.0 - r * q) * T_m / 2.0 + D + R_m)
        + (1.0 - f) * (T_d / 2.0 + D + R_d)
    ) / mu
    p_safe = np.where(r > 0.0, p, 1.0)
    pred = np.where((r > 0.0) & (q > 0.0), (q * r / p_safe) * C_m / mu, 0.0)
    return w + pred


# repro-twin: repro.kernels.analytic.silent_waste
def silent_waste(T, C, V, DR, mu, k):
    """Silent-error waste (arXiv:1310.8486, see ``waste.waste_silent``)
    branchless over per-cell columns: ``k`` periods per verification, a
    latent corruption forfeits the whole pattern plus recovery ``DR``."""
    return (k * C + V) / (k * T) + (k * T + V + DR) / mu


# repro-twin: repro.kernels.analytic.cell_waste
def cell_waste(
    T, mode, q, C, DR, lead_act, mu, r, p, window, T_P, tp_eff,
    C2, DR2, V, fmem, rho, kv,
):
    """Mode-dispatched waste over the fused engine's per-cell columns.

    Mirrors ``experiments.validation.analytic_waste``'s dispatch as one
    select chain: mode "exact" means Equation (1), or Equation (5) when
    the predictor is window-based; ``lead_act`` is the engine's
    premade migration-or-checkpoint lead column (M for migration cells,
    C otherwise); a NaN ``T_P`` (non-WithCkptI cells' fill) is replaced
    by the table's benign default so every branch stays finite under
    differentiation; and mode "none" / untrusted / recall-free cells
    fall back to Young's model exactly like the scalar dispatch."""
    E_f = 0.5 * window
    tp = np.where(np.isnan(T_P), tp_eff, T_P)
    w_y = young_waste(T, C, DR, mu)
    w = np.where(
        window > 0.0,
        instant_waste(T, q, C, DR, mu, r, p, E_f),
        exact_waste(T, q, C, DR, mu, r, p),
    )
    w = np.where(
        mode == _M_MIGRATION, migration_waste(T, q, C, DR, lead_act, mu, r, p), w
    )
    w = np.where(
        mode == _M_NOCKPT, nockpt_waste(T, q, C, DR, mu, r, p, window, E_f), w
    )
    w = np.where(
        mode == _M_WITHCKPT,
        withckpt_waste(T, tp, q, C, DR, mu, r, p, window, E_f),
        w,
    )
    w = np.where((mode == _M_NONE) | (q <= 0.0) | (r <= 0.0), w_y, w)
    w = np.where(
        mode == _M_TWO_LEVEL,
        two_level_waste(T, rho * T, C, C2, 0.0, DR, DR2, mu, fmem, r, q, p),
        w,
    )
    return np.where(mode == _M_SILENT, silent_waste(T, C, V, DR, mu, kv), w)


def table_waste(T, tables: Dict[str, np.ndarray]) -> np.ndarray:
    """:func:`cell_waste` applied to a ``_cell_tables`` column dict, with
    precision recovered from the ``fp_mean`` column.  Tables predating
    the two-level/silent columns get their benign fills (0/0/0/0/1/1)."""
    C = np.asarray(tables["C"], np.float64)
    z, one = np.zeros_like(C), np.ones_like(C)
    with np.errstate(divide="ignore", invalid="ignore"):
        p = precision_from_fp(tables["mtbf"], tables["fp_mean"], tables["recall"])
        return cell_waste(
            T, tables["mode"], tables["q_eff"], tables["C"], tables["DR"],
            tables["lead_act"], tables["mtbf"], tables["recall"], p,
            tables["window"], tables["T_P"], tables["tp_eff_default"],
            tables.get("C2", z), tables.get("DR2", z), tables.get("V", z),
            tables.get("fmem", z), tables.get("rho", one), tables.get("kv", one),
        )


# --------------------------------------------------------------------------- #
# The shared per-cell parameter table
# --------------------------------------------------------------------------- #
def cell_tables(
    work,
    platforms: Sequence[Platform],
    predictors: Sequence[PredictorModel],
    strategies: Sequence,
    horizon,
    fault_dists=None,
    fp_dists=None,
    n_tab: Optional[int] = None,
    dtype=np.float64,
) -> Dict[str, np.ndarray]:
    """Build the fused engine's per-cell parameter table host-side.

    Delegates to :func:`repro.core.jax_sim._cell_tables` — the one
    packing routine the device dispatch uses — so the analytic layer and
    the simulator consume byte-identical columns.  ``n_tab`` pads with
    the engine's benign rows (for pow2 executable sharing); default is
    no padding."""
    from . import jax_sim as J  # NumPy-only at import; kept lazy like core.__init__

    n = len(strategies)
    Wk, C, D, R, M, T_R, T_P, mode, q, C2, R2, V, fmem, rho, kv = (
        B._lane_params(work, list(platforms), list(strategies), n)
    )
    mtbf = np.asarray([p.mu for p in platforms], dtype=np.float64)
    recall = np.asarray([p.recall for p in predictors], dtype=np.float64)
    precision = np.asarray([p.precision for p in predictors], dtype=np.float64)
    window = np.asarray([p.window for p in predictors], dtype=np.float64)
    fp_mean = E.false_prediction_mtbf_batch(mtbf, recall, precision)
    # silent-error cells never trust the fail-stop predictor
    q_eff = np.where(
        (mode == B._M_NONE) | (mode == B._M_SILENT),
        0.0, np.clip(q, 0.0, 1.0),
    )
    fault_laws = E.law_table(fault_dists) if fault_dists is not None else None
    fp_laws = E.law_table(fp_dists) if fp_dists is not None else None
    return J._cell_tables(
        n, n_tab if n_tab is not None else n, dtype,
        Wk, C, D, R, M, T_R, T_P, mode,
        np.broadcast_to(np.asarray(horizon, np.float64), (n,)), window, -1.0,
        mtbf=mtbf, fp_mean=fp_mean, recall=recall, q_eff=q_eff,
        fault_laws=fault_laws, fp_laws=fp_laws,
        C2=C2, R2=R2, V=V, fmem=fmem, rho=rho, kv=kv,
    )


def tables_from_cells(
    cells: Sequence, n_tab: Optional[int] = None, dtype=np.float64
) -> Dict[str, np.ndarray]:
    """The shared table of a sequence of experiment cells (anything with
    ``work``/``platform``/``predictor``/``strategy``/``horizon_factor``
    and the grid's ``dist`` attributes, i.e.
    :class:`repro.experiments.grid.ExperimentCell`)."""
    dists = [getattr(c, "dist", None) for c in cells]
    have_laws = all(d is not None for d in dists) and len(cells) > 0
    if have_laws:
        try:
            for d in dists:
                E.require_inverse_cdf(d)
        except ValueError:
            have_laws = False
    return cell_tables(
        [c.work for c in cells],
        [c.platform for c in cells],
        [c.predictor for c in cells],
        [c.strategy for c in cells],
        [c.horizon_factor * c.work for c in cells],
        fault_dists=dists if have_laws else None,
        n_tab=n_tab,
        dtype=dtype,
    )


def analytic_waste_cells(cells: Sequence) -> np.ndarray:
    """First-order analytic waste of every cell at its operating period —
    the vectorized replacement of the per-cell strategy dispatch that
    :func:`repro.experiments.validation.analytic_waste` used to run."""
    tabs = tables_from_cells(cells)
    return table_waste(tabs["T_R"], tabs)


def analytic_period_cells(cells: Sequence) -> np.ndarray:
    """Closed-form uncapped optimal period per cell: ``T_extr^{q_eff}``
    (Section 3.3's unified formula, floored at C), evaluated on the
    shared table columns."""
    tabs = tables_from_cells(cells)
    with np.errstate(divide="ignore"):
        denom = 1.0 - tabs["recall"] * tabs["q_eff"]
        te = np.where(
            denom > 0.0,
            np.sqrt(2.0 * tabs["mtbf"] * tabs["C"] / np.where(denom > 0.0, denom, 1.0)),
            np.inf,
        )
    return np.maximum(te, tabs["C"])


# --------------------------------------------------------------------------- #
# Batched on-device period optimization (safeguarded Newton)
# --------------------------------------------------------------------------- #
def _mu_e_np(mu, r, p):
    """Vectorized :func:`repro.core.events.mu_e` (harmonic event rate)."""
    with np.errstate(divide="ignore"):
        inv_p = np.where(r > 0.0, r / (p * mu), 0.0)
        inv_np = np.where(r < 1.0, (1.0 - r) / mu, 0.0)
        inv = inv_p + inv_np
        return np.where(inv > 0.0, 1.0 / np.where(inv > 0.0, inv, 1.0), np.inf)


def _newton_bounds(
    tables: Dict[str, np.ndarray], alpha: float, capped: bool
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-cell period domains ``(lo, hi0, hi1)`` for the q=0 / q=q_eff
    Newton solves, mirroring the host case analyses: uncapped (the
    paper's Section 5 default) brackets generously past every extremal
    period; ``capped=True`` reproduces ``t_young`` / ``t_one``'s
    Section 3.2/4.3 validity caps (``_clamp`` semantics: hi >= lo)."""
    C, mu = tables["C"], tables["mtbf"]
    r, q, I = tables["recall"], tables["q_eff"], tables["window"]
    lo = np.asarray(C, np.float64)
    if capped:
        with np.errstate(invalid="ignore"):
            p = precision_from_fp(mu, tables["fp_mean"], r)
        cap1 = np.where(
            r > 0.0,
            np.maximum(alpha * _mu_e_np(mu, r, p) - I, C),
            np.maximum(alpha * mu, C),
        )
        cap0 = np.where(
            (I > 0.0) & (r > 0.0),
            np.maximum(alpha * _mu_e_np(mu, r, p) - I, C),
            np.maximum(alpha * mu, C),
        )
        return lo, np.maximum(cap0, lo), np.maximum(cap1, lo)
    te0 = np.sqrt(2.0 * mu * C)
    te1 = np.sqrt(2.0 * mu * C / np.maximum(1.0 - r * q, 0.015625))
    hi = 64.0 * np.maximum(te0, te1) + I + C
    if "fmem" in tables:  # two-level cells: T_m* grows like 1/sqrt(f)
        fm = np.maximum(np.asarray(tables["fmem"], np.float64), 0.015625)
        hi = np.where(
            np.asarray(tables["mode"]) == _M_TWO_LEVEL, hi / np.sqrt(fm), hi
        )
    return lo, hi, hi


def newton_optimize_tables(
    tables: Dict[str, np.ndarray],
    alpha: float = W.ALPHA,
    capped: bool = False,
    iters: int = 60,
    devices=None,
) -> Dict[str, np.ndarray]:
    """Solve every cell's optimal period in ONE jitted device dispatch.

    Runs :func:`repro.kernels.analytic.newton_policy` — per-cell
    safeguarded Newton with ``jax.grad``/hessian steps and bisection
    fallback on a shrinking derivative bracket, split at the Instant
    kink ``T = I`` — over the shared table, then the q in {0, q_eff}
    case analysis, exactly like the host ``optimize_*`` functions but
    for the whole grid at once.  Returns per-cell ``T_R``, ``q``,
    ``waste`` (min'd with 1), plus both branches' raw solutions.

    The table is padded to a pow2 row count with the engine's benign
    rows before dispatch so similarly-sized grids share one compiled
    executable; padding rows are dropped from the result."""
    import jax

    from ..kernels import analytic as K

    defaults = {"C2": 0.0, "DR2": 0.0, "V": 0.0, "fmem": 0.0,
                "rho": 1.0, "kv": 1.0}
    if any(k not in tables for k in defaults):
        tables = dict(tables)
        base = np.asarray(tables["C"], np.float64)
        for k, v in defaults.items():
            tables.setdefault(k, np.full_like(base, v))

    n = int(np.asarray(tables["C"]).shape[0])
    n_tab = max(8, 1 << max(int(n) - 1, 0).bit_length())
    if n and n_tab != n:
        padded = dict(tables)
        fills = {"T_P": np.nan, "fp_mean": np.inf, "C": 1.0, "mtbf": 1.0,
                 "T_R": 2.0, "lead_act": 1.0, "tp_eff_default": 1.0,
                 "rho": 1.0, "kv": 1.0}
        for k in TABLE_COLS + ("T_R", "fp_mean"):
            col = np.asarray(tables[k])
            pad = np.full(n_tab - n, fills.get(k, 0.0), col.dtype)
            padded[k] = np.concatenate([col, pad])
        tables_p = padded
    else:
        tables_p = tables
    lo, hi0, hi1 = _newton_bounds(tables_p, alpha, capped)

    with jax.enable_x64(True):
        dev = None
        if devices:
            dev = devices[0] if isinstance(devices, (list, tuple)) else devices
        t = {
            k: np.asarray(tables_p[k]).astype(
                np.int32 if k == "mode" else np.float64
            )
            for k in TABLE_COLS + ("fp_mean",)
        }
        with np.errstate(invalid="ignore"):
            p = precision_from_fp(t["mtbf"], t["fp_mean"], t["recall"])
        args = [
            t["mode"], t["q_eff"], t["C"], t["DR"], t["lead_act"],
            t["mtbf"], t["recall"], p, t["window"], t["T_P"],
            t["tp_eff_default"], t["C2"], t["DR2"], t["V"], t["fmem"],
            t["rho"], t["kv"], lo, hi0, hi1,
        ]
        if dev is not None:
            args = [jax.device_put(a, dev) for a in args]
        out = K.newton_policy(*args, iters=iters)
        T, qs, waste, T0, w0, T1, w1 = (np.asarray(a)[:n] for a in out)
    return {
        "T_R": T, "q": qs, "waste": waste,
        "T0": T0, "waste0": w0, "T1": T1, "waste1": w1,
    }


# --------------------------------------------------------------------------- #
# The unified optimizer API
# --------------------------------------------------------------------------- #
@dataclass(frozen=True, eq=False)
class PolicyTable:
    """Batched :class:`OptimalPolicy`: one optimized operating point per
    cell, plus the shared parameter table that produced it."""

    strategy: Tuple[str, ...]
    q: np.ndarray
    T_R: np.ndarray
    waste: np.ndarray
    value: np.ndarray
    objective: str = "waste"
    method: str = "newton"
    T_P: Optional[np.ndarray] = None
    k_P: Optional[np.ndarray] = None
    tables: Optional[Dict[str, np.ndarray]] = None

    def __len__(self) -> int:
        return len(self.strategy)

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def __getitem__(self, i: int) -> OptimalPolicy:
        tp = None if self.T_P is None or np.isnan(self.T_P[i]) else float(self.T_P[i])
        kp = None
        if self.k_P is not None and self.k_P[i] > 0:
            kp = int(self.k_P[i])
        return OptimalPolicy(
            self.strategy[i], int(round(float(self.q[i]))), float(self.T_R[i]),
            float(self.waste[i]), T_P=tp, k_P=kp,
            objective=self.objective, value=float(self.value[i]),
        )


_ANALYTIC_DISPATCH = {
    "exact": P._optimize_exact,
    "migration": P._optimize_migration,
    "instant": P._optimize_instant,
    "nockpt": P._optimize_nockpt,
    "withckpt": P._optimize_withckpt,
    "two_level": P._optimize_two_level,
    "silent": P._optimize_silent,
    "best": P._best_policy,
}

_STRATEGY_NAMES = (
    "young", "daly", "exact", "instant", "nockpt", "withckpt",
    "migration", "two_level", "silent", "best",
)


def _optimize_young(platform, pred, alpha, capped):
    ty = P._t0(platform.mu, platform.C, alpha, capped)
    w0 = W.waste_young(ty, platform.C, platform.D, platform.R, platform.mu)
    return OptimalPolicy("young", 0, ty, min(w0, 1.0))


def _optimize_daly(platform, pred, alpha, capped):
    td = max(P._t_daly(platform.mu, platform.R, platform.C), platform.C)
    if capped:
        td = P._clamp(td, platform.C, max(alpha * platform.mu, platform.C))
    w0 = W.waste_young(td, platform.C, platform.D, platform.R, platform.mu)
    return OptimalPolicy("daly", 0, td, min(w0, 1.0))


def _with_objective(policy: OptimalPolicy, objective: str) -> OptimalPolicy:
    value = policy.waste if objective == "waste" else 1.0 - policy.waste
    return replace(policy, objective=objective, value=value)


def _strategy_stub(name: str, platform, pred):
    """Strategy object of a named family at a placeholder period (the
    optimizer solves T_R; T_P comes from the host integer partition,
    matching the simulator factories' degenerate-window fallback)."""
    from . import simulator as S

    factory = {
        "young": lambda: S.young(platform),
        "daly": lambda: S.daly(platform),
        "exact": lambda: S.exact_prediction(platform, pred),
        "instant": lambda: S.instant(platform, pred),
        "nockpt": lambda: S.nockpt(platform, pred),
        "withckpt": lambda: S.withckpt(platform, pred),
        "migration": lambda: S.migration(platform, pred),
        "two_level": lambda: S.two_level(platform, pred),
        "silent": lambda: S.silent(platform),
    }[name]
    return factory()


def _newton_policies(
    names: List[str],
    platforms: List[Platform],
    preds: List[PredictorModel],
    alpha: float,
    capped: bool,
    devices,
    objective: str,
) -> PolicyTable:
    """Batched method="newton": expand "best" items into their candidate
    families (Equation (12) pruning included), solve every candidate in
    one dispatch, then reduce back to one winner per item."""
    cand_names: List[str] = []
    cand_items: List[int] = []
    for i, (name, plat, pred) in enumerate(zip(names, platforms, preds)):
        if name == "best":
            if pred.window <= 0.0:
                fams = ["exact"]
            else:
                fams = ["instant", "nockpt"]
                if not P._nockpt_dominates(
                    plat.C, pred.precision, pred.window, pred.e_f
                ):
                    fams.append("withckpt")
        else:
            fams = [name]
        for f in fams:
            cand_names.append(f)
            cand_items.append(i)
    strategies = [
        _strategy_stub(f, platforms[i], preds[i])
        for f, i in zip(cand_names, cand_items)
    ]
    tabs = cell_tables(
        0.0,
        [platforms[i] for i in cand_items],
        [preds[i] for i in cand_items],
        strategies,
        0.0,
    )
    sol = newton_optimize_tables(tabs, alpha=alpha, capped=capped, devices=devices)
    n = len(names)
    best = np.full(n, np.inf)
    idx = np.full(n, -1, np.int64)
    for j, i in enumerate(cand_items):
        if sol["waste"][j] < best[i]:
            best[i] = sol["waste"][j]
            idx[i] = j
    T_P = np.array(
        [s.T_P if s.T_P is not None else np.nan for s in strategies]
    )[idx]
    waste = sol["waste"][idx]
    value = waste if objective == "waste" else 1.0 - waste
    return PolicyTable(
        strategy=tuple(cand_names[j] for j in idx),
        q=sol["q"][idx],
        T_R=sol["T_R"][idx],
        waste=waste,
        value=value,
        objective=objective,
        method="newton",
        T_P=T_P,
        tables=tabs,
    )


def optimize(
    strategy,
    platform,
    pred=None,
    *,
    objective: str = "waste",
    method: str = "analytic",
    alpha: float = W.ALPHA,
    capped: bool = False,
    engine=None,
    devices=None,
    mesh=None,
    config=None,
    work: float = 8 * 86400.0,
    n_runs: int = 20,
    seed: int = 0,
    fault_dist=None,
    grid=None,
) -> Union[OptimalPolicy, "PolicyTable"]:
    """The unified period optimizer (this PR's single entry point).

    strategy    a family name — "young", "daly", "exact", "instant",
                "nockpt", "withckpt", "migration" — or "best" (the
                paper's Section 4.3 recipe with Equation (12) pruning);
                a sequence of names batches (with ``platform`` / ``pred``
                broadcast or zipped) and returns a :class:`PolicyTable`.
    objective   "waste" minimizes the closed-form waste; "availability"
                maximizes 1 - waste (same argmin, the reported ``value``
                flips to availability).
    method      "analytic"  the paper's closed-form case analyses
                            (host; exact reproduction of the legacy
                            ``optimize_*`` results);
                "newton"    batched safeguarded Newton on the jnp twin
                            models — the whole batch solves in ONE
                            jitted device dispatch (``devices=`` pins
                            the device);
                "search"    simulated brute force (the legacy
                            ``best_period_search``): ``work``,
                            ``n_runs``, ``seed``, ``fault_dist``,
                            ``grid`` and ``engine``/``devices``/
                            ``mesh``/``config`` apply.
    capped      restrict periods to the Section 3.2/4.3 validity domain
                (the paper's own simulations use the uncapped default).
    """
    if objective not in ("waste", "availability"):
        raise ValueError(
            f"unknown objective {objective!r} "
            "(expected 'waste' or 'availability')"
        )
    if method not in ("analytic", "newton", "search"):
        raise ValueError(
            f"unknown method {method!r} "
            "(expected 'analytic', 'newton' or 'search')"
        )
    batched = isinstance(strategy, (list, tuple))
    names = list(strategy) if batched else [strategy]
    n = len(names)

    def _bcast(x, kind):
        if isinstance(x, (list, tuple)):
            if len(x) != n:
                raise ValueError(
                    f"{kind} sequence length {len(x)} != {n} strategies"
                )
            return list(x)
        return [x] * n

    platforms = _bcast(platform, "platform")
    preds = [
        p if p is not None else PredictorModel(0.0, 1.0)
        for p in _bcast(pred, "pred")
    ]
    for name in names:
        if name not in _STRATEGY_NAMES:
            raise ValueError(
                f"unknown strategy {name!r} "
                f"(expected one of {sorted(_STRATEGY_NAMES)})"
            )

    if method == "analytic":
        policies = []
        for name, plat, pm in zip(names, platforms, preds):
            if name == "young":
                pol = _optimize_young(plat, pm, alpha, capped)
            elif name == "daly":
                pol = _optimize_daly(plat, pm, alpha, capped)
            else:
                pol = _ANALYTIC_DISPATCH[name](plat, pm, alpha, capped)
            policies.append(_with_objective(pol, objective))
        if not batched:
            return policies[0]
        return PolicyTable(
            strategy=tuple(p.strategy for p in policies),
            q=np.array([p.q for p in policies], np.float64),
            T_R=np.array([p.T_R for p in policies]),
            waste=np.array([p.waste for p in policies]),
            value=np.array([p.value for p in policies]),
            objective=objective,
            method="analytic",
            T_P=np.array(
                [p.T_P if p.T_P is not None else np.nan for p in policies]
            ),
            k_P=np.array(
                [p.k_P if p.k_P is not None else 0 for p in policies],
                np.int64,
            ),
        )

    if method == "newton":
        table = _newton_policies(
            names, platforms, preds, alpha, capped, devices, objective
        )
        if batched:
            return table
        return table[0]

    # method == "search": the simulated brute force, per item
    from .engine import EngineConfig, resolve_engine_config

    cfg = config
    if cfg is None:
        cfg = EngineConfig(
            engine=engine if engine is not None else "batch",
            devices=devices, mesh=mesh,
        )
    elif engine is not None or devices is not None or mesh is not None:
        raise ValueError(
            "optimize: pass either config= or engine=/devices=/mesh=, not both"
        )
    from . import simulator as S

    policies = []
    for name, plat, pm in zip(names, platforms, preds):
        if name == "best":
            raise ValueError("strategy 'best' is not supported with method='search'")
        base = _strategy_stub(name, plat, pm)
        kwargs = {} if grid is None else {"grid": grid}
        best_t, best_w = S._best_period_search(
            work, plat, base, pm, n_runs=n_runs, seed=seed,
            fault_dist=fault_dist, config=cfg, **kwargs,
        )
        pol = OptimalPolicy(
            name, int(round(base.q)), best_t, min(best_w, 1.0), T_P=base.T_P
        )
        policies.append(_with_objective(pol, objective))
    if not batched:
        return policies[0]
    return PolicyTable(
        strategy=tuple(p.strategy for p in policies),
        q=np.array([p.q for p in policies], np.float64),
        T_R=np.array([p.T_R for p in policies]),
        waste=np.array([p.waste for p in policies]),
        value=np.array([p.value for p in policies]),
        objective=objective,
        method="search",
        T_P=np.array([p.T_P if p.T_P is not None else np.nan for p in policies]),
    )


def optimize_cells(
    cells: Sequence,
    objective: str = "waste",
    method: str = "newton",
    alpha: float = W.ALPHA,
    capped: bool = False,
    devices=None,
) -> PolicyTable:
    """Optimize the periods of a prebuilt experiment-cell sequence (the
    grid consumers' entry point): the cells' own strategies fix the
    family/q/T_P, only the regular period is re-solved."""
    if method != "newton":
        raise ValueError("optimize_cells supports method='newton' only")
    tabs = tables_from_cells(cells)
    sol = newton_optimize_tables(tabs, alpha=alpha, capped=capped, devices=devices)
    waste = sol["waste"]
    value = waste if objective == "waste" else 1.0 - waste
    return PolicyTable(
        strategy=tuple(c.strategy.name for c in cells),
        q=sol["q"],
        T_R=sol["T_R"],
        waste=waste,
        value=value,
        objective=objective,
        method="newton",
        T_P=tabs["T_P"][: len(cells)].copy(),
        tables=tabs,
    )
