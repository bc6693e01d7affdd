"""Event and trace generation for faults and fault predictions.

Implements the event model of the paper (Aupy, Robert, Vivien, Zaidouni,
"Impact of fault prediction on checkpointing strategies", 2012), Section 2:

* Faults arrive as a renewal process with mean inter-arrival time ``mu``
  (the platform MTBF).  Distributions: Exponential (theory), Weibull with
  shape 0.5 / 0.7 (representative of real platforms), LogNormal (extra).
* A predictor with recall ``r`` and precision ``p`` predicts each fault
  independently with probability ``r`` (true positives).  False positives
  form an independent renewal process with mean inter-arrival time
  ``p * mu / (r * (1 - p))`` so that the three rate identities of Section
  2.3 hold:

      (1 - r) / mu = 1 / mu_NP
      r / mu       = p / mu_P
      1 / mu_e     = 1 / mu_P + 1 / mu_NP

* Window predictions cover an interval ``[t0, t0 + I]``; the true fault is
  uniformly distributed inside its window (the paper's default, giving
  ``E_I^f = I / 2``).  Exact-date predictions are the ``I = 0`` special
  case.  Every prediction is announced ``lead`` seconds before ``t0``
  (the paper requires ``lead >= C`` so a proactive checkpoint fits).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

__all__ = [
    "FaultEvent",
    "PredictionEvent",
    "EventTrace",
    "BatchTraces",
    "TraceSpec",
    "pad_sentinel",
    "Distribution",
    "exponential",
    "weibull",
    "lognormal",
    "uniform",
    "make_fault_trace",
    "make_event_trace",
    "make_event_traces_batch",
    "make_trace_spec",
    "LAW_INDEX",
    "LAW_EXPONENTIAL",
    "LAW_WEIBULL",
    "LAW_LOGNORMAL",
    "LAW_UNIFORM",
    "law_table",
    "gap_transform_indexed_np",
    "superposed_fault_times",
    "superposed_fault_times_batch",
    "mu_np",
    "mu_p",
    "mu_e",
    "false_prediction_mtbf",
    "false_prediction_mtbf_batch",
]


# --------------------------------------------------------------------------- #
# Rate identities (Section 2.3)
# --------------------------------------------------------------------------- #
def mu_np(mu: float, r: float) -> float:
    """Mean time between *unpredicted* faults: mu / (1 - r)."""
    if r >= 1.0:
        return math.inf
    return mu / (1.0 - r)


def mu_p(mu: float, r: float, p: float) -> float:
    """Mean time between *predicted events* (true + false positives): p mu / r."""
    if r <= 0.0:
        return math.inf
    return p * mu / r


def mu_e(mu: float, r: float, p: float) -> float:
    """Mean time between events of any type: 1/mu_e = 1/mu_P + 1/mu_NP."""
    inv = 0.0
    mp = mu_p(mu, r, p)
    mnp = mu_np(mu, r)
    if math.isfinite(mp):
        inv += 1.0 / mp
    if math.isfinite(mnp):
        inv += 1.0 / mnp
    if inv == 0.0:
        return math.inf
    return 1.0 / inv


def false_prediction_mtbf(mu: float, r: float, p: float) -> float:
    """Mean inter-arrival time of *false* predictions: p mu / (r (1 - p)).

    Derivation: prediction rate = r/(p mu); true-positive rate = r/mu;
    false-positive rate = r (1 - p) / (p mu).
    """
    if r <= 0.0 or p >= 1.0:
        return math.inf
    return p * mu / (r * (1.0 - p))


def false_prediction_mtbf_batch(
    mtbf: np.ndarray, recall: np.ndarray, precision: np.ndarray
) -> np.ndarray:
    """Vectorized :func:`false_prediction_mtbf` (``+inf`` where no false
    predictions occur) — shared by the host trace generator and the
    device-generation packing path."""
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        return np.where(
            (recall > 0.0) & (precision < 1.0),
            precision * mtbf / np.maximum(recall * (1.0 - precision), 1e-300),
            np.inf,
        )


# --------------------------------------------------------------------------- #
# Inter-arrival distributions
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class Distribution:
    """A positive inter-arrival distribution with a given mean.

    ``kind``/``param`` identify the family for the device trace generator
    (:class:`TraceSpec`): the on-device inverse-CDF samplers dispatch on
    them statically.  Custom distributions may leave ``kind`` empty; they
    then work with every host path but not with ``trace_mode="device"``.
    """

    name: str
    sampler: Callable[[np.random.Generator, float, int], np.ndarray]
    kind: str = ""
    param: float = 0.0

    def sample(self, rng: np.random.Generator, mean: float, n: int) -> np.ndarray:
        return self.sampler(rng, mean, n)


def _exp_sample(rng: np.random.Generator, mean: float, n: int) -> np.ndarray:
    return rng.exponential(mean, size=n)


def _weibull_sampler(shape: float) -> Callable:
    # scale so that E[X] = scale * Gamma(1 + 1/shape) = mean
    gamma_term = math.gamma(1.0 + 1.0 / shape)

    def sample(rng: np.random.Generator, mean: float, n: int) -> np.ndarray:
        scale = mean / gamma_term
        return scale * rng.weibull(shape, size=n)

    return sample


def _lognormal_sampler(sigma: float) -> Callable:
    def sample(rng: np.random.Generator, mean: float, n: int) -> np.ndarray:
        # E[X] = exp(mu + sigma^2/2) = mean
        mu_ln = math.log(mean) - sigma * sigma / 2.0
        return rng.lognormal(mu_ln, sigma, size=n)

    return sample


def _uniform_sample(rng: np.random.Generator, mean: float, n: int) -> np.ndarray:
    # U(0, 2*mean) has mean `mean`
    return rng.uniform(0.0, 2.0 * mean, size=n)


def exponential() -> Distribution:
    return Distribution("exponential", _exp_sample, kind="exponential")


def weibull(shape: float) -> Distribution:
    return Distribution(
        f"weibull(k={shape})", _weibull_sampler(shape),
        kind="weibull", param=shape,
    )


def lognormal(sigma: float = 1.0) -> Distribution:
    return Distribution(
        f"lognormal(sigma={sigma})", _lognormal_sampler(sigma),
        kind="lognormal", param=sigma,
    )


def uniform() -> Distribution:
    return Distribution("uniform", _uniform_sample, kind="uniform")


# --------------------------------------------------------------------------- #
# Events
# --------------------------------------------------------------------------- #
@dataclass(order=True)
class FaultEvent:
    """A fault striking the platform at absolute ``time``.

    ``predicted`` marks true positives (the matching PredictionEvent carries
    the same ``fault_time``).  ``tier_u`` is the recovery-tier uniform of
    two-level checkpointing strategies (``tier_u >= f`` sends the recovery
    to the disk tier; the 1.0 default means "disk", so legacy traces stay
    valid for every strategy with ``f = 0``).
    """

    time: float
    predicted: bool = field(default=False, compare=False)
    tier_u: float = field(default=1.0, compare=False)


@dataclass(order=True)
class PredictionEvent:
    """A prediction with window ``[t0, t0 + window]`` announced at
    ``t0 - lead``.  ``fault_time`` is None for false positives."""

    t0: float
    window: float = field(default=0.0, compare=False)
    fault_time: Optional[float] = field(default=None, compare=False)
    lead: float = field(default=math.inf, compare=False)

    @property
    def is_true_positive(self) -> bool:
        return self.fault_time is not None

    @property
    def announce_time(self) -> float:
        if math.isinf(self.lead):
            return -math.inf
        return self.t0 - self.lead


@dataclass
class EventTrace:
    """A merged trace of faults and predictions over ``[0, horizon]``."""

    horizon: float
    faults: List[FaultEvent]
    predictions: List[PredictionEvent]

    @property
    def n_true_positive(self) -> int:
        return sum(1 for p in self.predictions if p.is_true_positive)

    @property
    def n_false_positive(self) -> int:
        return sum(1 for p in self.predictions if not p.is_true_positive)

    @property
    def n_false_negative(self) -> int:
        return sum(1 for f in self.faults if not f.predicted)

    def empirical_recall(self) -> float:
        tp = self.n_true_positive
        fn = self.n_false_negative
        return tp / (tp + fn) if tp + fn else 0.0

    def empirical_precision(self) -> float:
        tp = self.n_true_positive
        fp = self.n_false_positive
        return tp / (tp + fp) if tp + fp else 0.0


def _arrival_times(
    rng: np.random.Generator, dist: Distribution, mean: float, horizon: float
) -> np.ndarray:
    """Cumulative renewal arrivals in (0, horizon]."""
    if not math.isfinite(mean):
        return np.empty(0)
    times: List[float] = []
    t = 0.0
    # draw in blocks for speed
    expected = max(16, int(horizon / mean * 1.5) + 8)
    while t < horizon:
        block = dist.sample(rng, mean, expected)
        block = np.maximum(block, 1e-9)  # guard zero inter-arrivals
        cum = t + np.cumsum(block)
        keep = cum[cum <= horizon]
        times.extend(keep.tolist())
        if len(keep) < len(cum):
            break
        t = float(cum[-1])
    return np.asarray(times)


def make_fault_trace(
    rng: np.random.Generator,
    horizon: float,
    mtbf: float,
    dist: Distribution | None = None,
) -> List[FaultEvent]:
    dist = dist or exponential()
    return [FaultEvent(float(t)) for t in _arrival_times(rng, dist, mtbf, horizon)]


def superposed_fault_times(
    rng: np.random.Generator,
    horizon: float,
    mtbf: float,
    n_components: int,
    dist: Distribution | None = None,
    stationary: bool = False,
) -> np.ndarray:
    """Platform trace as the superposition of ``n_components`` i.i.d.
    component renewal processes, each with MTBF ``n_components * mtbf``
    (Section 2.1: mu = mu_ind / N).

    The paper's Section 5 text ("a random trace of failures ... scaled so
    that its expectation corresponds to the platform MTBF") is ambiguous
    between a single renewal stream and this superposition.  The two differ
    enormously for Weibull shape < 1: with every component *fresh* at t = 0
    the early platform hazard diverges (burn-in), which is the only
    mechanism consistent with the paper's very large Weibull-k=0.5
    slowdowns.  ``stationary=True`` instead draws each component's first
    arrival from the inspection-paradox equilibrium (age-biased) law, under
    which the superposition is asymptotically Poisson.
    """
    dist = dist or exponential()
    mu_ind = n_components * mtbf
    if stationary:
        # equilibrium first arrival: stationary residual life = U * X with
        # X drawn *length-biased* (a random time instant lands in a gap
        # with probability proportional to the gap's length)
        pool = dist.sample(rng, mu_ind, max(4 * n_components, 20000))
        pool = np.maximum(pool, 1e-9)
        gaps = rng.choice(pool, size=n_components, p=pool / pool.sum())
        first = rng.uniform(0.0, 1.0, n_components) * gaps
    else:
        first = dist.sample(rng, mu_ind, n_components)
    times: List[float] = []
    frontier = first[first < horizon]
    times.extend(frontier.tolist())
    while len(frontier):
        nxt = frontier + np.maximum(
            dist.sample(rng, mu_ind, len(frontier)), 1e-9
        )
        nxt = nxt[nxt < horizon]
        times.extend(nxt.tolist())
        frontier = nxt
    return np.sort(np.asarray(times))


def make_event_trace(
    rng: np.random.Generator,
    horizon: float,
    mtbf: float,
    recall: float,
    precision: float,
    window: float = 0.0,
    lead: float = math.inf,
    fault_dist: Distribution | None = None,
    false_pred_dist: Distribution | None = None,
    n_components: Optional[int] = None,
    stationary: bool = False,
) -> EventTrace:
    """Generate the paper's merged trace (Section 5 methodology).

    1. Draw the fault trace from ``fault_dist`` scaled to mean ``mtbf``
       (single renewal stream), or — when ``n_components`` is given — as the
       superposition of per-component renewals (see
       :func:`superposed_fault_times`).
    2. Mark each fault predicted with probability ``recall``.
    3. Draw a false-prediction trace from ``false_pred_dist`` (default: same
       distribution family as the faults) scaled to mean
       ``p * mu / (r (1-p))``.
    4. Merge.  True-positive windows are placed so the fault is uniformly
       distributed inside the window.
    """
    fault_dist = fault_dist or exponential()
    false_pred_dist = false_pred_dist or fault_dist

    if n_components:
        times = superposed_fault_times(
            rng, horizon, mtbf, n_components, fault_dist, stationary
        )
        faults = [FaultEvent(float(t)) for t in times]
    else:
        faults = make_fault_trace(rng, horizon, mtbf, fault_dist)
    predictions: List[PredictionEvent] = []

    for f in faults:
        if rng.random() < recall:
            f.predicted = True
            offset = rng.uniform(0.0, window) if window > 0 else 0.0
            t0 = max(0.0, f.time - offset)
            predictions.append(
                PredictionEvent(t0=t0, window=window, fault_time=f.time, lead=lead)
            )

    fp_mean = false_prediction_mtbf(mtbf, recall, precision)
    for t in _arrival_times(rng, false_pred_dist, fp_mean, horizon):
        predictions.append(
            PredictionEvent(t0=float(t), window=window, fault_time=None, lead=lead)
        )

    faults.sort()
    predictions.sort()
    return EventTrace(horizon=horizon, faults=faults, predictions=predictions)


# --------------------------------------------------------------------------- #
# Batched trace generation (lane-per-trace arrays)
# --------------------------------------------------------------------------- #
def pad_sentinel(
    a: np.ndarray,
    counts: np.ndarray,
    fill,
    round_pow2: bool = False,
    min_width: int = 1,
) -> np.ndarray:
    """Cursor-ready event array: guarantee at least one all-``fill``
    column past every lane's ``counts[i]`` valid events.

    Both vectorized engines walk event rows with per-lane cursors and rely
    on a terminating sentinel column instead of bounds checks.  Arrays that
    are already wide enough are adopted unchanged (zero copy — the engines
    never write them).  ``round_pow2`` rounds the column count up to a
    power of two so device engines see bucketed shapes and re-use their
    compiled executables across batches of slightly different widths.
    """
    need = (int(counts.max()) if counts.size else 0) + 1
    need = max(need, min_width)
    if round_pow2:
        need = 1 << (need - 1).bit_length()
    if a.shape[1] >= need:
        return a
    pad = np.full((a.shape[0], need - a.shape[1]), fill, dtype=a.dtype)
    return np.concatenate([a, pad], axis=1)


@dataclass
class BatchTraces:
    """``n_traces`` merged event traces as padded 2-D arrays (one lane per
    trace, one column per event).

    Rows are sorted in time; columns beyond a lane's event count are padded
    with ``+inf`` (``NaN`` for ``pred_fault``).  Generated batches carry at
    least one all-padding trailing column, which the vectorized engine uses
    as its cursor sentinel (adopting the arrays without copying).
    ``lane(i)`` materializes the scalar :class:`EventTrace` view of lane
    ``i`` — the exact trace the reference engine consumes in
    batched-vs-scalar equivalence checks.
    """

    horizon: np.ndarray  # (L,) per-lane horizon
    fault_times: np.ndarray  # (L, F) sorted fault dates, +inf padded
    fault_predicted: np.ndarray  # (L, F) bool, true-positive marks
    n_faults: np.ndarray  # (L,) valid fault count per lane
    pred_t0: np.ndarray  # (L, P) sorted window starts, +inf padded
    pred_fault: np.ndarray  # (L, P) matched fault date, NaN for false positives
    n_preds: np.ndarray  # (L,) valid prediction count per lane
    window: np.ndarray  # (L,) prediction-window length
    lead: np.ndarray  # (L,) announce lead
    #: (L, F) per-fault recovery-tier uniforms (two-level strategies;
    #: ``None`` on batches generated without ``tier=True``)
    fault_tier: Optional[np.ndarray] = None

    @property
    def n_lanes(self) -> int:
        return int(self.fault_times.shape[0])

    def lane(self, i: int) -> EventTrace:
        """Scalar :class:`EventTrace` view of lane ``i``."""
        nf = int(self.n_faults[i])
        npred = int(self.n_preds[i])
        tiers = (
            self.fault_tier[i, :nf]
            if self.fault_tier is not None
            else np.ones(nf)
        )
        faults = [
            FaultEvent(float(t), predicted=bool(p), tier_u=float(u))
            for t, p, u in zip(
                self.fault_times[i, :nf], self.fault_predicted[i, :nf], tiers
            )
        ]
        w, ld = float(self.window[i]), float(self.lead[i])
        preds = []
        for j in range(npred):
            ft = float(self.pred_fault[i, j])
            preds.append(
                PredictionEvent(
                    t0=float(self.pred_t0[i, j]),
                    window=w,
                    fault_time=None if math.isnan(ft) else ft,
                    lead=ld,
                )
            )
        return EventTrace(horizon=float(self.horizon[i]), faults=faults, predictions=preds)

    def tile(self, reps: int) -> "BatchTraces":
        """Repeat the whole batch ``reps`` times (lane block order preserved:
        lanes [0..L) then [0..L) again, ...) — used to evaluate several
        strategies on identical traces in a single engine call."""
        return BatchTraces(
            horizon=np.tile(self.horizon, reps),
            fault_times=np.tile(self.fault_times, (reps, 1)),
            fault_predicted=np.tile(self.fault_predicted, (reps, 1)),
            n_faults=np.tile(self.n_faults, reps),
            pred_t0=np.tile(self.pred_t0, (reps, 1)),
            pred_fault=np.tile(self.pred_fault, (reps, 1)),
            n_preds=np.tile(self.n_preds, reps),
            window=np.tile(self.window, reps),
            lead=np.tile(self.lead, reps),
            fault_tier=(
                None
                if self.fault_tier is None
                else np.tile(self.fault_tier, (reps, 1))
            ),
        )

    def take(self, rows) -> "BatchTraces":
        """New batch whose lane ``i`` is lane ``rows[i]`` of this batch
        (rows may repeat — several strategies sharing identical traces)."""
        rows = np.asarray(rows)
        return BatchTraces(
            horizon=self.horizon[rows],
            fault_times=self.fault_times[rows],
            fault_predicted=self.fault_predicted[rows],
            n_faults=self.n_faults[rows],
            pred_t0=self.pred_t0[rows],
            pred_fault=self.pred_fault[rows],
            n_preds=self.n_preds[rows],
            window=self.window[rows],
            lead=self.lead[rows],
            fault_tier=(
                None if self.fault_tier is None else self.fault_tier[rows]
            ),
        )

    @staticmethod
    def concat(parts: Sequence["BatchTraces"]) -> "BatchTraces":
        """Stack several batches into one (event columns padded to the
        widest part) so heterogeneous groups share a single engine call."""

        def cat2(arrs: List[np.ndarray], fill) -> np.ndarray:
            width = max(a.shape[1] for a in arrs)
            padded = [
                a
                if a.shape[1] == width
                else np.concatenate(
                    [a, np.full((a.shape[0], width - a.shape[1]), fill, a.dtype)],
                    axis=1,
                )
                for a in arrs
            ]
            return np.concatenate(padded, axis=0)

        if any(p.fault_tier is not None for p in parts):
            # lanes without draws fall back to the 1.0 ("disk") fill
            tier = cat2(
                [
                    p.fault_tier
                    if p.fault_tier is not None
                    else np.ones(p.fault_times.shape)
                    for p in parts
                ],
                1.0,
            )
        else:
            tier = None
        return BatchTraces(
            horizon=np.concatenate([p.horizon for p in parts]),
            fault_times=cat2([p.fault_times for p in parts], np.inf),
            fault_predicted=cat2([p.fault_predicted for p in parts], False),
            n_faults=np.concatenate([p.n_faults for p in parts]),
            pred_t0=cat2([p.pred_t0 for p in parts], np.inf),
            pred_fault=cat2([p.pred_fault for p in parts], np.nan),
            n_preds=np.concatenate([p.n_preds for p in parts]),
            window=np.concatenate([p.window for p in parts]),
            lead=np.concatenate([p.lead for p in parts]),
            fault_tier=tier,
        )


def _arrival_times_batch(
    rng: np.random.Generator,
    dist: Distribution,
    means: np.ndarray,
    horizons: np.ndarray,
    max_block: int = 4_000_000,
) -> tuple[np.ndarray, np.ndarray]:
    """Batched renewal arrivals: one vectorized sampling pass per round.

    Relies on every :class:`Distribution` being a scale family — sampling at
    mean 1 and multiplying by the per-lane mean yields the per-lane law.
    Returns ``(times (L, W) +inf padded, counts (L,))`` with arrivals in
    ``(0, horizon_i]`` per lane.

    The first round draws a full ``(L, m)`` block sized to the expected
    per-lane count; *refill* rounds (lanes whose cumulative arrivals have
    not yet crossed their horizon — the heavy-tail stragglers) draw one
    vectorized ``(n_unfinished, m)`` block over just those lanes, in
    ascending lane order, and their arrivals are scattered into the
    output in one pass at the end — both the sampling and the assembly
    cost O(stragglers), not O(L), per round.  On 100k-lane grids the
    refill rounds used to dominate generation time.  Traces at a given
    seed are unchanged when no refill occurs and differ (same law) when
    one does.
    """
    means = np.asarray(means, dtype=np.float64)
    horizons = np.asarray(horizons, dtype=np.float64)
    L = means.shape[0]
    finite = np.isfinite(means) & (means > 0.0)
    if L == 0 or not finite.any():
        return np.empty((L, 0)), np.zeros(L, dtype=np.int64)
    expected = np.where(finite, horizons / means, 0.0)

    # heterogeneous lanes: split fast lanes from slow ones so the block
    # width tracks each bucket's own expected count instead of the max
    if L >= 8 and expected.max() > 4.0 * max(np.median(expected), 1.0):
        cut = np.median(expected)
        lo = np.flatnonzero(expected <= cut)
        hi = np.flatnonzero(expected > cut)
        t_lo, c_lo = _arrival_times_batch(rng, dist, means[lo], horizons[lo], max_block)
        t_hi, c_hi = _arrival_times_batch(rng, dist, means[hi], horizons[hi], max_block)
        width = max(t_lo.shape[1], t_hi.shape[1])
        out = np.full((L, width), np.inf)
        out[lo, : t_lo.shape[1]] = t_lo
        out[hi, : t_hi.shape[1]] = t_hi
        counts = np.zeros(L, dtype=np.int64)
        counts[lo] = c_lo
        counts[hi] = c_hi
        return out, counts

    cap = max(16, max_block // L)
    m = int(np.clip(expected.max() * 1.25 + 8, 16, cap))
    block = dist.sample(rng, 1.0, (L, m)) * means[:, None]
    block = np.maximum(block, 1e-9)  # guard zero inter-arrivals
    block[~finite] = np.inf
    times = np.cumsum(block, axis=1)
    keep = times <= horizons[:, None]  # monotone rows: kept is a prefix
    counts = keep.sum(axis=1).astype(np.int64)
    tail = times[:, -1]
    ex_lanes: List[np.ndarray] = []
    ex_times: List[np.ndarray] = []
    act = np.flatnonzero(finite & (tail <= horizons))
    tail = tail[act]  # act-aligned from here on
    while act.size:
        m = max(16, m // 3)
        sub = np.maximum(
            dist.sample(rng, 1.0, (act.size, m)) * means[act, None], 1e-9
        )
        sub_t = tail[:, None] + np.cumsum(sub, axis=1)
        sk = sub_t <= horizons[act, None]
        cnt = sk.sum(axis=1)
        ex_lanes.append(np.repeat(act, cnt))
        ex_times.append(sub_t[sk])  # row-major: grouped by lane, sorted
        counts[act] += cnt
        tail = sub_t[:, -1]
        live = tail <= horizons[act]
        act, tail = act[live], tail[live]
    width = int(counts.max(initial=0))
    out = np.full((L, max(width, times.shape[1])), np.inf)
    out[:, : times.shape[1]] = np.where(keep, times, np.inf)
    if ex_lanes:
        lanes_cat = np.concatenate(ex_lanes)
        times_cat = np.concatenate(ex_times)
        # refill rounds append in time order per lane; a stable sort by
        # lane turns (round, lane) order into per-lane sorted runs
        order = np.argsort(lanes_cat, kind="stable")
        lanes_s = lanes_cat[order]
        base = keep.sum(axis=1)
        starts = np.concatenate([[0], np.cumsum(counts - base)[:-1]])
        pos = base[lanes_s] + np.arange(lanes_s.size) - starts[lanes_s]
        out[lanes_s, pos] = times_cat[order]
    return out[:, :width], counts


def _bc(x, L: int) -> np.ndarray:
    return np.broadcast_to(np.asarray(x, dtype=np.float64), (L,)).copy()


def superposed_fault_times_batch(
    rng: np.random.Generator,
    horizons: np.ndarray,
    mtbfs: np.ndarray,
    n_components: int,
    dist: Distribution | None = None,
    stationary: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Batched fresh-start :func:`superposed_fault_times`: every lane's
    component frontier advances in one flattened sampling pass per round
    (the frontier shrinks geometrically, so a handful of rounds covers the
    horizon).  Returns ``(times (L, W) +inf padded sorted, counts)``.

    ``stationary=True`` draws each component's first arrival from the
    equilibrium (length-biased residual-life) law, like the scalar path —
    but vectorized: each lane gets its *own* pool of unit-mean gaps
    (lanes are independent Monte-Carlo runs, so pools must not be shared
    — a shared pool's heavy atoms would correlate every run of a sweep
    and understate the per-cell CIs), and the length-biased choice runs
    as one offset-``searchsorted`` pass per lane block instead of a
    per-lane Python loop."""
    dist = dist or exponential()
    horizons = np.asarray(horizons, dtype=np.float64)
    mtbfs = np.asarray(mtbfs, dtype=np.float64)
    L = horizons.shape[0]
    mu_ind = mtbfs * n_components
    if stationary:
        # pool size trades length-biased fidelity (ratio bias O(1/K))
        # against the (block, K) memory of per-lane pools
        K = int(min(max(4 * n_components, 2048), 20000))
        first = np.empty((L, n_components))
        B = max(1, 4_000_000 // K)
        for lo in range(0, L, B):
            sl = slice(lo, min(lo + B, L))
            nb = sl.stop - sl.start
            pool = np.maximum(dist.sample(rng, 1.0, (nb, K)), 1e-9)
            cdf = np.cumsum(pool / pool.sum(axis=1, keepdims=True), axis=1)
            cdf[:, -1] = 1.0  # guard float-rounding shortfall
            rows = np.arange(nb)[:, None]
            u = rng.random((nb, n_components))
            # rows offset by 2 keep the flattened cdf globally sorted, so
            # one searchsorted inverts every lane's CDF at once
            idx = np.searchsorted(
                (cdf + 2.0 * rows).ravel(), (u + 2.0 * rows).ravel(),
                side="right",
            ).reshape(nb, n_components) - rows * K
            idx = np.minimum(idx, K - 1)
            gaps = pool[rows, idx] * mu_ind[sl][:, None]
            first[sl] = rng.uniform(0.0, 1.0, (nb, n_components)) * gaps
    else:
        first = dist.sample(rng, 1.0, (L, n_components)) * mu_ind[:, None]
    lane0, comp0 = np.nonzero(first < horizons[:, None])
    f_lane = lane0
    f_time = first[lane0, comp0]
    all_lanes = [f_lane]
    all_times = [f_time]
    while f_lane.size:
        gaps = np.maximum(
            dist.sample(rng, 1.0, f_lane.size) * mu_ind[f_lane], 1e-9
        )
        nxt = f_time + gaps
        keep = nxt < horizons[f_lane]
        f_lane = f_lane[keep]
        f_time = nxt[keep]
        all_lanes.append(f_lane)
        all_times.append(f_time)
    lanes_cat = np.concatenate(all_lanes)
    times_cat = np.concatenate(all_times)
    counts = np.bincount(lanes_cat, minlength=L).astype(np.int64)
    width = int(counts.max()) if lanes_cat.size else 0
    out = np.full((L, width), np.inf)
    order = np.lexsort((times_cat, lanes_cat))
    lanes_s = lanes_cat[order]
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(lanes_s.size) - starts[lanes_s]
    out[lanes_s, pos] = times_cat[order]
    return out, counts


def make_event_traces_batch(
    rng: np.random.Generator,
    n_traces: int,
    horizon,
    mtbf,
    recall,
    precision,
    window=0.0,
    lead=math.inf,
    fault_dist: Distribution | None = None,
    false_pred_dist: Distribution | None = None,
    n_components: Optional[int] = None,
    stationary: bool = False,
    tier: bool = False,
) -> BatchTraces:
    """Batched :func:`make_event_trace`: one array-of-events generation pass
    per distribution instead of ``n_traces`` Python loops.

    All trace parameters broadcast to per-lane ``(n_traces,)`` arrays, so a
    single call can carry a heterogeneous sweep (mixed MTBFs, predictors and
    windows).  The generated traces are distributionally identical to the
    scalar path but consume the RNG in a different order, so individual
    traces differ draw-for-draw from :func:`make_event_trace` at equal seeds.

    ``tier=True`` additionally draws per-fault recovery-tier uniforms
    (two-level checkpointing strategies).  The draw happens *after* every
    other draw, so traces at a given seed are unchanged when ``tier`` is
    left off.
    """
    L = int(n_traces)
    horizon = _bc(horizon, L)
    mtbf = _bc(mtbf, L)
    recall = _bc(recall, L)
    precision = _bc(precision, L)
    window = _bc(window, L)
    lead = _bc(lead, L)
    fault_dist = fault_dist or exponential()
    false_pred_dist = false_pred_dist or fault_dist

    if n_components:
        fault_times, n_faults = superposed_fault_times_batch(
            rng, horizon, mtbf, n_components, fault_dist, stationary
        )
    else:
        fault_times, n_faults = _arrival_times_batch(rng, fault_dist, mtbf, horizon)

    cols = np.arange(fault_times.shape[1])[None, :]
    valid = cols < n_faults[:, None]
    predicted = valid & (rng.random(fault_times.shape) < recall[:, None])

    # true-positive windows: fault uniformly distributed inside [t0, t0 + I]
    offsets = rng.random(fault_times.shape) * window[:, None]
    tp_t0 = np.where(predicted, np.maximum(0.0, fault_times - offsets), np.inf)
    tp_ft = np.where(predicted, fault_times, np.nan)

    fp_mean = false_prediction_mtbf_batch(mtbf, recall, precision)
    fp_t0, n_fp = _arrival_times_batch(rng, false_pred_dist, fp_mean, horizon)

    t0 = np.concatenate([tp_t0, fp_t0], axis=1)
    ft = np.concatenate([tp_ft, np.full(fp_t0.shape, np.nan)], axis=1)
    order = np.argsort(t0, axis=1, kind="stable")
    t0 = np.take_along_axis(t0, order, axis=1)
    ft = np.take_along_axis(ft, order, axis=1)
    n_preds = predicted.sum(axis=1).astype(np.int64) + n_fp

    # keep >= 1 trailing padding column: the engine's cursor sentinel
    pwidth = (int(n_preds.max()) if L else 0) + 1
    t0 = t0[:, :pwidth] if t0.shape[1] >= pwidth else np.concatenate(
        [t0, np.full((L, pwidth - t0.shape[1]), np.inf)], axis=1
    )
    ft = ft[:, :pwidth] if ft.shape[1] >= pwidth else np.concatenate(
        [ft, np.full((L, pwidth - ft.shape[1]), np.nan)], axis=1
    )
    fwidth = (int(n_faults.max()) if L else 0) + 1
    if fault_times.shape[1] < fwidth:
        fault_times = np.concatenate(
            [fault_times, np.full((L, fwidth - fault_times.shape[1]), np.inf)],
            axis=1,
        )
        predicted = np.concatenate(
            [predicted, np.zeros((L, fwidth - predicted.shape[1]), bool)], axis=1
        )

    return BatchTraces(
        horizon=horizon,
        fault_times=fault_times,
        fault_predicted=predicted[:, : fault_times.shape[1]],
        n_faults=n_faults,
        pred_t0=t0,
        pred_fault=ft,
        n_preds=n_preds,
        window=window,
        lead=lead,
        fault_tier=rng.random(fault_times.shape) if tier else None,
    )


# --------------------------------------------------------------------------- #
# Counter-based RNG trace specifications (device-side generation)
# --------------------------------------------------------------------------- #
#: stream kinds of the per-lane counter-based RNG layout.  Every lane owns
#: six independent streams, one per kind (the TP coin stream's two output
#: words carry the predicted coin and the window offset); draw ``i`` of a
#: stream never depends on any other draw, so the device engine, the NumPy
#: :meth:`TraceSpec.materialize` reference, and any cursor replaying the
#: stream see identical events regardless of chunking or device count.
(
    STREAM_FAULT_GAP,  # fault inter-arrival time i
    STREAM_TP_COIN,  # fault i: word0 = predicted coin, word1 = window offset
    STREAM_FP_GAP,  # false-prediction inter-arrival time j
    STREAM_TP_TRUST,  # trust coin for fault i's prediction (0 < q < 1 only)
    STREAM_FP_TRUST,  # trust coin for false prediction j (0 < q < 1 only)
    STREAM_TIER,  # recovery-tier coin for fault i (two-level strategies)
) = range(6)

#: Threefry-2x32 key-schedule parity constant (Salmon et al., SC'11)
_TF_PARITY = 0x1BD11BDA
#: Threefry-2x32 rotation schedule (repeating groups of four rounds)
_TF_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
#: Random123 default round count (known-answer tested)
THREEFRY_ROUNDS = 20

#: SplitMix64 constants (Vigna; Stafford Mix13 finalizer).  Subkeys are
#: derived with Threefry (quality key spacing, once per lane per stream
#: kind); per-*counter* draws — the hot path, one evaluation per lane per
#: event — use the ~10-op SplitMix64 mix, which passes BigCrush, instead
#: of an ~80-op cipher.
_SM_GAMMA = 0x9E3779B97F4A7C15
_SM_MIX1 = 0xBF58476D1CE4E5B9
_SM_MIX2 = 0x94D049BB133111EB


# repro-twin: repro.kernels.sim_step.threefry2x32
def threefry2x32(k0, k1, c0, c1, rounds: int = THREEFRY_ROUNDS):
    """Vectorized Threefry-2x32 block cipher (NumPy reference).

    Round/key-injection layout follows Random123 (injection after every
    fourth round).  The device engine re-implements the identical
    function in jnp (:func:`repro.kernels.sim_step.threefry2x32`); a
    bit-equality test pins the two together.  All inputs broadcast;
    returns two ``uint32`` words.
    """
    k0 = np.asarray(k0, np.uint32)
    k1 = np.asarray(k1, np.uint32)
    x0 = np.asarray(c0, np.uint32)
    x1 = np.asarray(c1, np.uint32)
    with np.errstate(over="ignore"):
        ks = (k0, k1, k0 ^ k1 ^ np.uint32(_TF_PARITY))
        x0 = x0 + ks[0]
        x1 = x1 + ks[1]
        for i in range(rounds):
            r = _TF_ROTATIONS[(i // 4) % 2][i % 4]
            x0 = x0 + x1
            x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
            x1 = x1 ^ x0
            if i % 4 == 3:
                s = i // 4 + 1
                x0 = x0 + ks[s % 3]
                x1 = x1 + ks[(s + 1) % 3] + np.uint32(s)
    return x0, x1


# repro-twin: repro.kernels.sim_step.splitmix64
def splitmix64(key64, ctr):
    """Counter-indexed SplitMix64 draw (NumPy reference): output ``ctr``
    of the stream whose state orbit starts at ``key64`` — i.e.
    ``mix(key64 + (ctr + 1) * GAMMA)``, exactly Vigna's generator with a
    random starting state.  Returns the (high, low) uint32 words of the
    64-bit output.  The jnp twin lives in :mod:`repro.kernels.sim_step`;
    a known-answer test pins both to the reference sequence
    (``key64 = 0`` -> ``0xE220A8397B1DCDAF, ...``)."""
    key64 = np.asarray(key64, np.uint64)
    with np.errstate(over="ignore"):
        z = key64 + (np.asarray(ctr, np.uint64) + np.uint64(1)) * np.uint64(
            _SM_GAMMA
        )
        z = (z ^ (z >> np.uint64(30))) * np.uint64(_SM_MIX1)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(_SM_MIX2)
        z = z ^ (z >> np.uint64(31))
    return (z >> np.uint64(32)).astype(np.uint32), z.astype(np.uint32)


# repro-twin: repro.kernels.sim_step.uniform24
def uniform24(bits, dtype=np.float64):
    """Map ``uint32`` words to uniforms in the *open* interval (0, 1):
    the top 24 bits, centered by half an ulp — so ``log`` and ``log1p``
    transforms never see an endpoint.  24-bit granularity is ~6e-8 of the
    mean, far below Monte-Carlo resolution, and keeps the f32 (TPU) and
    f64 paths on one code shape."""
    return ((bits >> np.uint32(8)).astype(dtype) + dtype(0.5)) * dtype(2.0**-24)


# repro-twin: repro.kernels.sim_step.gap_transform
def gap_transform_np(kind: str, param: float, mean, x0, x1):
    """Inverse-CDF inter-arrival transform of one counter draw (NumPy
    reference; mirrors :func:`repro.kernels.sim_step.gap_transform`).

    ``x0``/``x1`` are the two threefry output words; only the lognormal
    family consumes the second (Box–Muller phase).  Matches the host
    :class:`Distribution` families: same mean parameterization, same
    ``1e-9`` zero-gap guard."""
    u = uniform24(x0)
    if kind == "exponential":
        g = -np.log1p(-u) * mean
    elif kind == "weibull":
        scale = 1.0 / math.gamma(1.0 + 1.0 / param)
        g = (np.asarray(mean) * scale) * (-np.log1p(-u)) ** (1.0 / param)
    elif kind == "lognormal":
        z = np.sqrt(-2.0 * np.log(u)) * np.cos(2.0 * np.pi * uniform24(x1))
        with np.errstate(over="ignore"):
            g = np.exp(np.log(mean) - 0.5 * param * param + param * z)
    elif kind == "uniform":
        g = 2.0 * np.asarray(mean) * u
    else:
        raise ValueError(
            f"device trace generation supports exponential/weibull/"
            f"lognormal/uniform, got kind={kind!r}"
        )
    return np.maximum(g, 1e-9)


#: law indices of the cell-table ``law_index`` column — the per-cell
#: *data* encoding of the failure-law family (mixed-law fused dispatch)
LAW_EXPONENTIAL, LAW_WEIBULL, LAW_LOGNORMAL, LAW_UNIFORM = range(4)

#: ``Distribution.kind`` -> law index
LAW_INDEX = {
    "exponential": LAW_EXPONENTIAL,
    "weibull": LAW_WEIBULL,
    "lognormal": LAW_LOGNORMAL,
    "uniform": LAW_UNIFORM,
}


def law_table(dists):
    """Per-cell law table of a distribution sequence: ``(law, lp)`` with
    ``law`` an ``(n,)`` int32 law-index column and ``lp`` an ``(n, 4)``
    float64 unified parameter row ``[param, s1, s2, 0]``.

    The shape slots are pre-folded exactly as the compile-time-specialized
    transforms fold them (same Python-float expressions), so the indexed
    samplers reproduce the specialized paths bit-for-bit: Weibull ``s1 =
    1/Γ(1 + 1/k)``, ``s2 = 1/k``; lognormal ``s1 = σ``, ``s2 = σ²/2``;
    exponential/uniform need no shape (all-zero slots).  Slot 3 is
    reserved."""
    dists = tuple(dists)
    law = np.zeros(len(dists), np.int32)
    lp = np.zeros((len(dists), 4), np.float64)
    for i, d in enumerate(dists):
        require_inverse_cdf(d)
        law[i] = LAW_INDEX[d.kind]
        if d.kind == "weibull":
            lp[i, 0] = d.param
            lp[i, 1] = 1.0 / math.gamma(1.0 + 1.0 / d.param)
            lp[i, 2] = 1.0 / d.param
        elif d.kind == "lognormal":
            lp[i, 0] = d.param
            lp[i, 1] = d.param
            lp[i, 2] = 0.5 * d.param * d.param
    return law, lp


# repro-twin: repro.kernels.sim_step.gap_transform_indexed
def gap_transform_indexed_np(law, s1, s2, mean, x0, x1):
    """Law-multiplexed :func:`gap_transform_np` (NumPy reference; mirrors
    :func:`repro.kernels.sim_step.gap_transform_indexed`): ``law`` selects
    the family per element and ``(s1, s2)`` carry the pre-folded shape
    slots of :func:`law_table`.  All inputs broadcast.  Every family's
    branch evaluates (masked errstate) and a ``where`` chain selects — the
    same select order as the jnp twin, and each branch the same expression
    as the specialized transform, so a single-family slice is bit-identical
    to :func:`gap_transform_np`."""
    u = uniform24(x0)
    nlog = -np.log1p(-u)
    with np.errstate(over="ignore", invalid="ignore", divide="ignore"):
        g_exp = nlog * mean
        # mirror ndarray.__pow__'s scalar fast paths (x ** 2.0 -> x * x,
        # x ** 0.5 -> sqrt) so the data-driven exponent reproduces the
        # specialized transform's bits for those shapes too
        p = np.power(nlog, s2)
        p = np.where(s2 == 2.0, nlog * nlog, p)
        p = np.where(s2 == 0.5, np.sqrt(nlog), p)
        g_wei = (np.asarray(mean) * s1) * p
        z = np.sqrt(-2.0 * np.log(u)) * np.cos(2.0 * np.pi * uniform24(x1))
        g_log = np.exp(np.log(mean) - s2 + s1 * z)
        g_uni = 2.0 * np.asarray(mean) * u
    g = np.where(
        law == LAW_WEIBULL, g_wei,
        np.where(
            law == LAW_LOGNORMAL, g_log,
            np.where(law == LAW_UNIFORM, g_uni, g_exp),
        ),
    )
    return np.maximum(g, 1e-9)


def require_inverse_cdf(dist: Distribution) -> None:
    """Raise unless ``dist`` names a family the device sampler supports
    (single point of truth for the supported-family list)."""
    if not dist.kind:
        raise ValueError(
            f"distribution {dist.name!r} has no inverse-CDF kind; "
            "device trace generation supports exponential/weibull/"
            "lognormal/uniform"
        )


def stream_subkey_np(seed: int, stream, kind: int):
    """Per-(lane-stream, kind) subkey derivation (NumPy reference).

    ``seed`` is split into two key words; the counter words carry the
    64-bit stream id (low word verbatim, high word shifted past the
    4-bit kind tag), so distinct (stream, kind) pairs map to distinct
    cipher inputs."""
    stream = np.asarray(stream, np.int64)
    s0 = np.uint32(seed & 0xFFFFFFFF)
    s1 = np.uint32((seed >> 32) & 0xFFFFFFFF)
    c0 = (stream & 0xFFFFFFFF).astype(np.uint32)
    c1 = ((((stream >> 32) << 4) | kind) & 0xFFFFFFFF).astype(np.uint32)
    return threefry2x32(s0, s1, c0, c1)


def stream_key64_np(seed: int, stream, kind: int) -> np.ndarray:
    """The 64-bit SplitMix stream key: the two Threefry subkey words
    packed ``(high << 32) | low``."""
    k0, k1 = stream_subkey_np(seed, stream, kind)
    return (k0.astype(np.uint64) << np.uint64(32)) | k1.astype(np.uint64)


@dataclass
class TraceSpec:
    """A *generative* trace batch: per-lane parameters plus a counter-based
    RNG stream layout, in place of materialized event arrays.

    Where :class:`BatchTraces` stores ``(lanes, events)`` slabs sampled on
    the host, a ``TraceSpec`` stores only the O(lanes) parameters and lets
    the consumer sample events on demand: lane ``i``'s events are a pure
    function of ``(seed, stream[i])`` through the six counter-indexed
    streams above.  The JAX engine (``trace_mode="device"``) walks these
    streams with O(1) per-lane cursors; :meth:`materialize` replays the
    identical streams into a :class:`BatchTraces` on the host (NumPy), so
    host engines — and exactness tests — can consume the same traces.

    Lanes sharing a ``stream`` id face identical faults and predictions
    (the paired experiment design); ``take``/``tile`` preserve that by
    carrying the ids.

    **Cell-indexed layout** (the fused experiment sweep): with
    ``cell_index`` set, the six parameter arrays hold one row per *cell*
    (shape ``(n_cells,)``) and ``cell_index[i]`` names lane ``i``'s cell
    — only ``stream`` (and ``cell_index`` itself) stay per-lane, so a
    grid of hundreds of cells ships O(cells) parameters + O(lanes) int32
    to the device instead of O(lanes) float64 per parameter.  Lane
    semantics are *identical* to :meth:`expand`'s per-lane view; host
    consumers go through ``expand()``, the device engine gathers rows by
    ``cell_index`` on device.

    **Mixed-law layout**: ``fault_dist`` / ``false_pred_dist`` may each
    be a *tuple* of distributions — one per cell row (or per lane in the
    per-lane layout).  The failure law then rides the cell tables as data
    (an int32 ``law_index`` column plus the unified 4-slot parameter row
    of :func:`law_table`) and every consumer switches to the
    law-multiplexed transform, so grids mixing exponential / Weibull /
    lognormal / uniform families run as ONE device dispatch.  Build such
    specs with :meth:`concat_cells` or by passing distribution sequences
    to :func:`make_trace_spec`."""

    horizon: np.ndarray  # (L,) — or (n_cells,) when cell-indexed
    mtbf: np.ndarray  # (L,) | (n_cells,)
    recall: np.ndarray  # (L,) | (n_cells,)
    precision: np.ndarray  # (L,) | (n_cells,)
    window: np.ndarray  # (L,) | (n_cells,)
    lead: np.ndarray  # (L,) | (n_cells,)
    fault_dist: "Distribution | tuple"  # one per cell/lane when a tuple
    false_pred_dist: "Distribution | tuple"
    seed: int
    stream: np.ndarray  # (L,) int64 global RNG stream ids
    cell_index: Optional[np.ndarray] = None  # (L,) int32 lane -> cell row

    @property
    def n_lanes(self) -> int:
        return int(self.stream.shape[0])

    @property
    def n_cells(self) -> Optional[int]:
        """Cell-table row count (``None`` for the per-lane layout)."""
        if self.cell_index is None:
            return None
        return int(self.horizon.shape[0])

    @property
    def fp_mean(self) -> np.ndarray:
        """False-prediction mean inter-arrival; aligned with the parameter
        arrays (per-cell in the cell-indexed layout)."""
        return false_prediction_mtbf_batch(self.mtbf, self.recall, self.precision)

    @staticmethod
    def _gather_dists(d, rows):
        """Row-gather a per-row distribution tuple (identity for the
        shared-`Distribution` layout)."""
        if isinstance(d, tuple):
            return tuple(d[int(r)] for r in rows)
        return d

    def expand(self) -> "TraceSpec":
        """Per-lane view of a cell-indexed spec (identity otherwise):
        parameter rows gathered by ``cell_index``, same streams — the
        reference layout every host consumer sees."""
        if self.cell_index is None:
            return self
        ci = self.cell_index
        return TraceSpec(
            horizon=self.horizon[ci], mtbf=self.mtbf[ci],
            recall=self.recall[ci], precision=self.precision[ci],
            window=self.window[ci], lead=self.lead[ci],
            fault_dist=self._gather_dists(self.fault_dist, ci),
            false_pred_dist=self._gather_dists(self.false_pred_dist, ci),
            seed=self.seed, stream=self.stream,
        )

    def take(self, rows) -> "TraceSpec":
        rows = np.asarray(rows)
        if self.cell_index is not None:
            # lane selection: the cell table is untouched, lanes re-map
            return TraceSpec(
                horizon=self.horizon, mtbf=self.mtbf,
                recall=self.recall, precision=self.precision,
                window=self.window, lead=self.lead,
                fault_dist=self.fault_dist,
                false_pred_dist=self.false_pred_dist,
                seed=self.seed, stream=self.stream[rows],
                cell_index=self.cell_index[rows],
            )
        return TraceSpec(
            horizon=self.horizon[rows], mtbf=self.mtbf[rows],
            recall=self.recall[rows], precision=self.precision[rows],
            window=self.window[rows], lead=self.lead[rows],
            fault_dist=self._gather_dists(self.fault_dist, rows),
            false_pred_dist=self._gather_dists(self.false_pred_dist, rows),
            seed=self.seed, stream=self.stream[rows],
        )

    @classmethod
    def concat_cells(cls, specs) -> "TraceSpec":
        """Concatenate cell-indexed specs (one per failure-law family,
        disjoint stream-id ranges, shared seed) into ONE mixed-law
        cell-indexed spec: cell tables stack, lane ``cell_index`` offsets
        into the stacked table, and the per-cell distribution tuples make
        the law a data column — the single-dispatch input of the fused
        mixed-law sweep.  Lane order is the concatenation order; every
        lane keeps its stream id, so events are unchanged."""
        specs = list(specs)
        if not specs:
            raise ValueError("concat_cells needs at least one spec")
        seed = specs[0].seed
        if any(s.seed != seed for s in specs):
            raise ValueError("concat_cells requires a shared seed")
        if any(s.cell_index is None for s in specs):
            raise ValueError("concat_cells requires cell-indexed specs")

        def rows(d, n):
            return tuple(d) if isinstance(d, tuple) else (d,) * n

        fd: list = []
        fpd: list = []
        ci = []
        off = 0
        for s in specs:
            n = s.n_cells
            fd += rows(s.fault_dist, n)
            fpd += rows(s.false_pred_dist, n)
            ci.append(s.cell_index.astype(np.int64) + off)
            off += n

        def cat(name):
            return np.concatenate([getattr(s, name) for s in specs])

        return cls(
            horizon=cat("horizon"), mtbf=cat("mtbf"),
            recall=cat("recall"), precision=cat("precision"),
            window=cat("window"), lead=cat("lead"),
            fault_dist=tuple(fd), false_pred_dist=tuple(fpd),
            seed=seed, stream=cat("stream"),
            cell_index=np.concatenate(ci).astype(np.int32),
        )

    def tile(self, reps: int) -> "TraceSpec":
        return self.take(np.tile(np.arange(self.n_lanes), reps))

    def indexed(self) -> "TraceSpec":
        """Force the law-indexed sampler: broadcast a shared
        ``Distribution`` to the per-row tuple layout (identity when
        already tuple-valued).  Events are drawn from the same streams
        through the law-multiplexed transform instead of the
        law-specialized one — the bit-exact control for
        one-dispatch-vs-per-family dispatch comparisons."""
        n = self.n_cells if self.cell_index is not None else self.n_lanes

        def tup(d):
            return d if isinstance(d, tuple) else (d,) * n

        return replace(
            self,
            fault_dist=tup(self.fault_dist),
            false_pred_dist=tup(self.false_pred_dist),
        )

    def _grow_stream(self, kind: int, means: np.ndarray, max_events: int):
        """Replay one gap stream to (just past) every lane's horizon:
        ``(times (L, W), valid (L, W), counts (L,))``.  Sequential
        accumulation order matches the device cursors, so the times are
        bit-identical to what the engine observes (f64)."""
        L = self.n_lanes
        key = stream_key64_np(self.seed, self.stream, kind)
        dist = self.fault_dist if kind == STREAM_FAULT_GAP else self.false_pred_dist
        if isinstance(dist, tuple):  # mixed-law: per-lane law column
            law, lp = law_table(dist)
            law_c = law[:, None]
            s1_c, s2_c = lp[:, 1][:, None], lp[:, 2][:, None]
        with np.errstate(invalid="ignore"):
            expected = np.where(
                np.isfinite(means) & (means > 0), self.horizon / means, 0.0
            )
        K = int(np.clip(
            expected.max(initial=0.0) * 1.4 + 16, 16, max(max_events, 16)
        ))
        # ``max_events`` is a floor for the runaway guard, which scales
        # with the expected count so any cell the device path can run is
        # also replayable on the host (memory permitting)
        cap = max(max_events, int(expected.max(initial=0.0) * 4) + 64)
        last = np.zeros(L)
        start = 0
        cols: List[np.ndarray] = []
        while True:
            ctr = np.broadcast_to(
                np.arange(start, start + K, dtype=np.int64), (L, K)
            )
            x0, x1 = splitmix64(key[:, None], ctr)
            if isinstance(dist, tuple):
                gaps = gap_transform_indexed_np(
                    law_c, s1_c, s2_c, means[:, None], x0, x1
                )
            else:
                gaps = gap_transform_np(
                    dist.kind, dist.param, means[:, None], x0, x1
                )
            # seed the cumulative sum with `last` so later blocks keep
            # the cursor's sequential (last + g1) + g2 association —
            # bit-identical to the device accumulation, not last + (g1+g2)
            t = np.cumsum(
                np.concatenate([last[:, None], gaps], axis=1), axis=1
            )[:, 1:]
            cols.append(t)
            last = t[:, -1]
            if np.all(last > self.horizon):
                break
            start += K
            if start > cap:
                raise ValueError(
                    f"lane needs more than {cap} events to cover its "
                    "horizon; raise max_events"
                )
            K = max(16, K // 2)
        times = np.concatenate(cols, axis=1)
        valid = times <= self.horizon[:, None]
        return times, valid, valid.sum(axis=1).astype(np.int64)

    def materialize(self, max_events: int = 1 << 17) -> BatchTraces:
        """Replay the counter streams on the host into a
        :class:`BatchTraces` — the exact events the device engine samples
        lazily (fault dates bit-identical in f64; merged predictions
        time-sorted as in :func:`make_event_traces_batch`, the order the
        device engine's window-ordered buffer serves them in).

        Trust coins (fractional ``q``) are *not* applied here: host
        engines draw trust from their own RNG, so fractional-``q`` runs
        agree with the device path only in distribution.  ``q`` in
        {0, 1} — every paper strategy — is filter-exact."""
        if self.cell_index is not None:
            return self.expand().materialize(max_events=max_events)
        L = self.n_lanes
        fault_times, valid, n_faults = self._grow_stream(
            STREAM_FAULT_GAP, self.mtbf, max_events
        )
        W = fault_times.shape[1]
        ctr = np.broadcast_to(np.arange(W, dtype=np.int64), (L, W))
        ckey = stream_key64_np(self.seed, self.stream, STREAM_TP_COIN)
        cw0, cw1 = splitmix64(ckey[:, None], ctr)
        predicted = valid & (uniform24(cw0) < self.recall[:, None])
        off = uniform24(cw1) * self.window[:, None]
        tp_t0 = np.where(
            predicted, np.maximum(0.0, fault_times - off), np.inf
        )
        tp_ft = np.where(predicted, fault_times, np.nan)
        fault_times = np.where(valid, fault_times, np.inf)

        fp_times, fp_valid, n_fp = self._grow_stream(
            STREAM_FP_GAP, self.fp_mean, max_events
        )
        fp_t0 = np.where(fp_valid, fp_times, np.inf)

        t0 = np.concatenate([tp_t0, fp_t0], axis=1)
        ft = np.concatenate([tp_ft, np.full(fp_t0.shape, np.nan)], axis=1)
        order = np.argsort(t0, axis=1, kind="stable")
        t0 = np.take_along_axis(t0, order, axis=1)
        ft = np.take_along_axis(ft, order, axis=1)
        n_preds = predicted.sum(axis=1).astype(np.int64) + n_fp

        pwidth = (int(n_preds.max()) if L else 0) + 1
        t0 = t0[:, :pwidth] if t0.shape[1] >= pwidth else np.concatenate(
            [t0, np.full((L, pwidth - t0.shape[1]), np.inf)], axis=1
        )
        ft = ft[:, :pwidth] if ft.shape[1] >= pwidth else np.concatenate(
            [ft, np.full((L, pwidth - ft.shape[1]), np.nan)], axis=1
        )
        fwidth = (int(n_faults.max()) if L else 0) + 1
        if fault_times.shape[1] < fwidth:
            fault_times = np.concatenate(
                [fault_times, np.full((L, fwidth - fault_times.shape[1]), np.inf)],
                axis=1,
            )
        else:
            fault_times = fault_times[:, :fwidth]
        # recovery-tier uniforms: counter draw i of the tier stream belongs
        # to fault column i — bit-identical to the device engine's
        # counter_uniform(tier_key, sf_ctr) read at each consumed fault
        tkey = stream_key64_np(self.seed, self.stream, STREAM_TIER)
        tctr = np.broadcast_to(
            np.arange(fault_times.shape[1], dtype=np.int64), fault_times.shape
        )
        fault_tier = uniform24(splitmix64(tkey[:, None], tctr)[0])
        return BatchTraces(
            horizon=self.horizon,
            fault_times=fault_times,
            fault_predicted=predicted[:, : fault_times.shape[1]],
            n_faults=n_faults,
            pred_t0=t0,
            pred_fault=ft,
            n_preds=n_preds,
            window=self.window,
            lead=self.lead,
            fault_tier=fault_tier,
        )


def make_trace_spec(
    n_traces: int,
    horizon,
    mtbf,
    recall,
    precision,
    window=0.0,
    lead=math.inf,
    fault_dist: "Distribution | Sequence[Distribution] | None" = None,
    false_pred_dist: "Distribution | Sequence[Distribution] | None" = None,
    seed: int = 0,
    stream=None,
    cell_index=None,
) -> TraceSpec:
    """Counter-RNG counterpart of :func:`make_event_traces_batch`: same
    broadcastable per-lane parameters, but returns the O(lanes)
    :class:`TraceSpec` instead of sampling events on the host.

    ``stream`` assigns the per-lane RNG stream ids (default
    ``arange(n_traces)``); pass disjoint ranges to make several specs
    independent under one seed, or repeated ids to pair lanes on
    identical traces.  Superposed component traces (``n_components``) are
    host-generation only.

    ``cell_index`` switches to the cell-indexed layout: the trace
    parameters then describe *cells* (broadcast to the cell-table length
    ``max(cell_index) + 1``) and ``n_traces`` lanes are mapped onto them
    by ``cell_index[i]`` — see :class:`TraceSpec`.

    ``fault_dist`` / ``false_pred_dist`` each also accept a *sequence* of
    distributions — one per cell row (per lane without ``cell_index``) —
    selecting the mixed-law layout."""
    L = int(n_traces)
    if stream is None:
        stream = np.arange(L, dtype=np.int64)
    else:
        stream = np.asarray(stream, dtype=np.int64)
        if stream.shape != (L,):
            raise ValueError(f"stream must have shape ({L},), got {stream.shape}")
    n_par = L
    if cell_index is not None:
        cell_index = np.asarray(cell_index, dtype=np.int32)
        if cell_index.shape != (L,):
            raise ValueError(
                f"cell_index must have shape ({L},), got {cell_index.shape}"
            )
        if L and cell_index.min() < 0:
            raise ValueError("cell_index entries must be >= 0")
        n_par = int(cell_index.max()) + 1 if L else 0

    def _dists(d, name):
        if isinstance(d, Distribution):
            require_inverse_cdf(d)
            return d
        d = tuple(d)
        if len(d) != n_par:
            raise ValueError(
                f"{name} sequence must have one entry per "
                f"{'cell' if cell_index is not None else 'lane'} "
                f"({n_par}), got {len(d)}"
            )
        for x in d:
            require_inverse_cdf(x)
        return d

    fault_dist = _dists(
        exponential() if fault_dist is None else fault_dist, "fault_dist"
    )
    false_pred_dist = _dists(
        fault_dist if false_pred_dist is None else false_pred_dist,
        "false_pred_dist",
    )
    return TraceSpec(
        horizon=_bc(horizon, n_par),
        mtbf=_bc(mtbf, n_par),
        recall=_bc(recall, n_par),
        precision=_bc(precision, n_par),
        window=_bc(window, n_par),
        lead=_bc(lead, n_par),
        fault_dist=fault_dist,
        false_pred_dist=false_pred_dist,
        seed=int(seed),
        stream=stream,
        cell_index=cell_index,
    )
