"""Per-lane golden results of the device-trace lane machine.

``tests/data/lane_golden.npz`` holds, for every case below, each lane's
makespan and counters and each chunk's outer-loop count, as the engine
computed them on XLA's CPU backend in float64 (``precision="x64"``) and in
float32 with Threefry draws (``precision="x32"``), in one chunk and in
several.  A change to how the engine walks its event streams must leave
every one of them bit for bit as it was.

The cases cover the paper's strategies at both predictors of the
Section 5 benchmark, window strategies, fractional trust, two-level and
silent-error lanes, and mixed failure laws, so that every gap-draw
specialisation of the prediction walk runs: one law for faults and false
predictions, two static laws, a mixed-law table against a static law, and
two mixed-law tables.

Regenerate the fixture only when results are meant to change::

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/test_lane_golden.py
"""

import os

import numpy as np
import pytest

from repro.core import Platform, PredictorModel
from repro.core import events as E
from repro.core import jax_sim
from repro.core import simulator as S
from repro.core.jax_sim import simulate_batch_jax

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "lane_golden.npz")

#: Section 5: per-node MTBF 125 years, C = R = 600 s, D = 60 s, M = 300 s
MU_IND = 3944700000.0
S5_WORK = 691200.0
P82 = PredictorModel(recall=0.85, precision=0.82, lead=3600.0)
P40 = PredictorModel(recall=0.70, precision=0.40, lead=3600.0)

MN = 60.0
PLAT = Platform(
    mu=1000 * MN, C=10 * MN, D=1 * MN, R=10 * MN, M=5 * MN,
    C2=30 * MN, R2=30 * MN, f=0.8, V=5 * MN,
)
WORK = 10 * 86400.0
PREDW = PredictorModel(recall=0.85, precision=0.82, window=3000.0, lead=3600.0)
PRED = PredictorModel(recall=0.85, precision=0.82, lead=3600.0)

MIXED = (E.exponential(), E.weibull(0.7), E.lognormal(0.5), E.uniform())

PRECISIONS = ("x64", "x32")
#: lanes per engine call: one chunk, then several (chunk boundaries cut
#: through cells, so chunks differ in which phase families they compile)
CHUNKS = {"whole": None, "chunked": 40}
FIELDS = (
    "makespan", "n_faults", "n_proactive_ckpts", "n_regular_ckpts",
    "n_migrations", "trace_exhausted", "n_disk_recoveries", "n_detections",
)


def _s5_cells():
    out = []
    for n in (2**16, 2**19):
        plat = Platform.from_components(MU_IND, n, C=600.0, D=60.0,
                                        R=600.0, M=300.0)
        for pred in (P82, P40):
            for strat in (S.young(plat), S.exact_prediction(plat, pred),
                          S.migration(plat, pred)):
                out.append((S5_WORK, plat, pred, strat))
    return out


def _frac(strat):
    return S.Strategy(strat.name + "Half", strat.T_R, q=0.5, mode=strat.mode,
                      T_P=strat.T_P)


#: name -> (cells as (work, platform, predictor, strategy), runs per cell,
#: fault law(s), false-prediction law(s) or None for the fault law)
CASES = {
    "s5": (_s5_cells(), 8, E.exponential(), None),
    "window": (
        [(WORK, PLAT, PREDW, s) for s in (
            S.instant(PLAT, PREDW), S.nockpt(PLAT, PREDW),
            S.withckpt(PLAT, PREDW), S.young(PLAT))],
        12, E.exponential(), E.weibull(1.5),
    ),
    "frac": (
        [(WORK, PLAT, PRED, _frac(S.exact_prediction(PLAT, PRED))),
         (WORK, PLAT, PRED, _frac(S.migration(PLAT, PRED))),
         (WORK, PLAT, PREDW, _frac(S.instant(PLAT, PREDW))),
         (WORK, PLAT, PRED, S.exact_prediction(PLAT, PRED))],
        12, E.exponential(), None,
    ),
    "two_level_silent": (
        [(WORK, PLAT, PRED, S.two_level(PLAT, PRED)),
         (WORK, PLAT, PRED, S.two_level(PLAT)),
         (WORK, PLAT, PRED, S.silent(PLAT)),
         (WORK, PLAT, PRED, S.exact_prediction(PLAT, PRED))],
        12, E.weibull(0.7), E.exponential(),
    ),
    "mixed_static_fp": (
        [(WORK, PLAT, PRED, S.exact_prediction(PLAT, PRED))] * 2
        + [(WORK, PLAT, PRED, S.migration(PLAT, PRED))] * 2,
        12, MIXED, E.exponential(),
    ),
    "mixed": (
        [(WORK, PLAT, PRED, S.exact_prediction(PLAT, PRED)),
         (WORK, PLAT, PRED, S.migration(PLAT, PRED)),
         (WORK, PLAT, PREDW, S.instant(PLAT, PREDW)),
         (WORK, PLAT, PRED, S.young(PLAT))],
        12, MIXED, MIXED[::-1],
    ),
}


def _run(case, precision, chunk):
    """One engine call of ``case``; its per-lane fields and loop counts."""
    cells, n_runs, fdist, fpdist = CASES[case]
    works, plats, preds, strats = (list(x) for x in zip(*cells))
    n_cells = len(cells)
    cidx = np.repeat(np.arange(n_cells, dtype=np.int32), n_runs)
    spec = E.make_trace_spec(
        n_cells * n_runs,
        horizon=[12 * w for w in works], mtbf=[p.mu for p in plats],
        recall=[p.recall for p in preds],
        precision=[p.precision for p in preds],
        window=[p.window for p in preds], lead=[p.lead for p in preds],
        fault_dist=fdist, false_pred_dist=fpdist, seed=20120924,
        cell_index=cidx,
    )
    res = simulate_batch_jax(works, plats, strats, spec, chunk=chunk,
                             precision=precision, use_pallas=False)
    out = {f: np.asarray(getattr(res, f)) for f in FIELDS}
    out["loop_iters"] = jax_sim.LAST_TIMINGS["loop_iters"]
    return out


def _key(case, precision, chunking, field):
    return f"{case}/{precision}/{chunking}/{field}"


@pytest.fixture(scope="module")
def golden():
    with np.load(FIXTURE) as z:
        return dict(z)


@pytest.mark.parametrize("chunking", list(CHUNKS))
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("case", list(CASES))
def test_lanes_match_the_golden_results(golden, case, precision, chunking):
    got = _run(case, precision, CHUNKS[chunking])
    for field, v in got.items():
        want = golden[_key(case, precision, chunking, field)]
        assert v.dtype == want.dtype and v.shape == want.shape, field
        # bit equality: compare the raw bytes, not the values
        assert v.tobytes() == want.tobytes(), (
            f"{field}: {np.count_nonzero(v != want)} of {v.size} differ"
        )


def test_the_fixture_covers_every_case():
    with np.load(FIXTURE) as z:
        keys = set(z.files)
    want = {
        _key(c, p, k, f)
        for c in CASES for p in PRECISIONS for k in CHUNKS
        for f in FIELDS + ("loop_iters",)
    }
    assert keys == want
    with np.load(FIXTURE) as z:
        # a case that predicts walks its prediction streams; the golden
        # results are no degenerate all-Young grid
        assert z[_key("s5", "x64", "whole", "n_proactive_ckpts")].sum() > 0
        assert z[_key("s5", "x64", "whole", "n_migrations")].sum() > 0
        assert z[_key("two_level_silent", "x64", "whole",
                      "n_disk_recoveries")].sum() > 0
        assert z[_key("two_level_silent", "x64", "whole",
                      "n_detections")].sum() > 0


if __name__ == "__main__":
    arrays = {
        _key(c, p, k, f): v
        for c in CASES for p in PRECISIONS for k, chunk in CHUNKS.items()
        for f, v in _run(c, p, chunk).items()
    }
    os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
    np.savez_compressed(FIXTURE, **arrays)
    print(f"wrote {len(arrays)} arrays to {FIXTURE}")
