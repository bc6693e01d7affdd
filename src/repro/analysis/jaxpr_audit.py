"""jaxpr auditor: abstract-eval contracts of the fused engine dispatch.

The third ``repro-lint`` pass traces the engine's *actual* jitted entry
points — nothing executes, no kernel launches — and checks the
machine-readable contracts the paper-grid validity argument rests on:

- **dtype schema**: every output leaf carries exactly the dtype the
  declared schema (:mod:`repro.analysis.schema`) assigns its role — the
  working float is uniformly ``float64`` in x64 mode, counters ``int64``,
  the phase machine ``int32`` — and no output is weakly typed;
- **no silent promotions**: the trace contains no ``float32`` avals (in
  x64 mode) and no float-to-float ``convert_element_type`` — the
  fingerprints of a literal or intermediate silently widening/narrowing
  the comparison boundary the analytic z-tests depend on;
- **donation**: the per-chunk state buffers declared in
  ``donate_argnums`` really are donated in the lowering (the chunk loop
  would otherwise double its device footprint);
- **O(cells) stats**: a ``collect="stats"`` dispatch returns only the
  ``(n_cells, 11)`` accumulator — no output dimension equals the padded
  lane count, so per-lane state provably never crosses to host;
- **one executable**: a mixed-law grid in device trace mode reuses ONE
  compiled runner across every chunk (the law-indexed sampler fuses the
  families; per-family dispatch would show distinct runners).

Capture works by intercepting ``repro.core.jax_sim._dispatch``: the
engine's own packing code builds the real ``(consts, state)`` chunk,
the spy grabs the jitted runner plus its arguments and aborts (lanes
mode) or passes the untouched accumulator through (stats mode, so the
chunk loop and the mixed-law sweep complete without running XLA).
``audit_callable`` exposes the same checks for arbitrary functions —
the test suite uses it to prove seeded violations (an injected f32
round-trip, a host ``np.asarray`` of a tracer) are caught.
"""

from __future__ import annotations

import contextlib
import warnings
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from .schema import OUT_SCHEMA, STATE_SCHEMA, resolve_role

__all__ = [
    "AuditReport",
    "audit_callable",
    "audit_engine",
    "audit_mixed_law",
    "run_audit",
]


@dataclass
class AuditReport:
    """Outcome of one audit: the entry label, failures, and passed checks."""

    label: str
    errors: List[str] = field(default_factory=list)
    passed: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.errors

    def format(self) -> str:
        lines = [f"[jaxpr-audit] {self.label}: "
                 f"{'OK' if self.ok else 'FAIL'}"]
        lines += [f"  pass: {c}" for c in self.passed]
        lines += [f"  FAIL: {e}" for e in self.errors]
        return "\n".join(lines)


class _AuditDone(Exception):
    """Abort the engine's chunk loop once the dispatch is captured."""


@dataclass
class _Capture:
    runner: object
    devs: tuple
    consts: dict
    state: dict
    acc: tuple


# --------------------------------------------------------------------- #
# jaxpr / lowering checks
# --------------------------------------------------------------------- #
def _iter_eqns(jaxpr):
    """All equations, recursing into call/scan/while sub-jaxprs."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            subs = v if isinstance(v, (tuple, list)) else (v,)
            for sub in subs:
                if isinstance(sub, ClosedJaxpr):
                    yield from _iter_eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _iter_eqns(sub)


def _check_trace_dtypes(jaxpr, fdt: np.dtype) -> Tuple[List[str], List[str]]:
    """No banned-float avals, no float<->float convert_element_type."""
    errors: List[str] = []
    passed: List[str] = []
    banned = np.dtype(np.float32) if fdt == np.float64 else None
    n_bad_avals = 0
    n_bad_convert = 0
    for eqn in _iter_eqns(jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if banned is not None and dt == banned:
                n_bad_avals += 1
                if n_bad_avals <= 3:
                    errors.append(
                        f"float32 aval in an x64 trace: {eqn.primitive.name} "
                        f"operates on {aval}"
                    )
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0].aval.dtype
            dst = eqn.params.get("new_dtype")
            if (
                np.issubdtype(src, np.floating)
                and dst is not None
                and np.issubdtype(np.dtype(dst), np.floating)
                and np.dtype(dst) != src
            ):
                n_bad_convert += 1
                if n_bad_convert <= 3:
                    errors.append(
                        f"float->float convert_element_type {src} -> "
                        f"{np.dtype(dst)} (silent precision change)"
                    )
    if n_bad_avals > 3:
        errors.append(f"... {n_bad_avals - 3} more float32 avals")
    if n_bad_convert > 3:
        errors.append(f"... {n_bad_convert - 3} more float converts")
    if not n_bad_avals:
        passed.append("no float32 avals in the x64 trace")
    if not n_bad_convert:
        passed.append("no float<->float convert_element_type")
    return errors, passed


def _check_out_leaves(
    out_shapes, fdt: np.dtype, idt: np.dtype
) -> Tuple[List[str], List[str]]:
    """Output dtype schema + weak-type check over an eval_shape pytree."""
    import jax

    errors: List[str] = []
    passed: List[str] = []
    allowed = {
        fdt, idt, np.dtype(np.int32), np.dtype(bool),
        np.dtype(np.uint32), np.dtype(np.uint64),
    }
    leaves_with_path = jax.tree_util.tree_flatten_with_path(out_shapes)[0]
    n_weak = n_dtype = n_schema = 0
    for path, leaf in leaves_with_path:
        name = jax.tree_util.keystr(path)
        key = name.strip("[]'\"").split("'")[-1] if name else name
        if getattr(leaf, "weak_type", False):
            n_weak += 1
            errors.append(f"output {name} is weakly typed ({leaf.dtype})")
        if leaf.dtype not in allowed:
            n_dtype += 1
            errors.append(
                f"output {name} dtype {leaf.dtype} outside the engine's "
                f"schema universe {sorted(str(d) for d in allowed)}"
            )
        role = STATE_SCHEMA.get(key) or OUT_SCHEMA.get(key)
        if role is not None:
            want = resolve_role(role, x64=fdt == np.float64)
            if leaf.dtype != want:
                n_schema += 1
                errors.append(
                    f"output {name} is {leaf.dtype}, schema role "
                    f"{role!r} requires {want}"
                )
    if not n_weak:
        passed.append("no weak-typed outputs")
    if not n_dtype:
        passed.append("all output dtypes inside the schema universe")
    if not n_schema:
        passed.append("schema-named outputs match their declared role")
    return errors, passed


def _check_donation(lowered, donated_names: str) -> Tuple[List[str], List[str]]:
    """Donation declared in donate_argnums must survive into the lowering."""
    try:
        text = lowered.as_text()
    except Exception as exc:  # pragma: no cover - lowering always works on CPU
        return [f"could not lower for donation check: {exc}"], []
    if "tf.aliasing_output" in text or "jax.buffer_donor" in text:
        return [], [f"{donated_names} buffers marked donated in the lowering"]
    return [
        f"donate_argnums declared for {donated_names} but the lowering "
        "carries no tf.aliasing_output / jax.buffer_donor marks"
    ], []


def audit_callable(
    fn: Callable,
    *args,
    label: str = "callable",
    fdt=np.float64,
    idt=np.int64,
    expect_donation: Optional[str] = None,
    check_outputs: bool = True,
) -> AuditReport:
    """Trace ``fn`` abstractly (under x64 if ``fdt`` is float64) and run
    the dtype/promotion/donation checks.  ``fn`` may already be jitted;
    plain callables are wrapped.  Nothing executes."""
    import jax

    report = AuditReport(label=label)
    fdt, idt = np.dtype(fdt), np.dtype(idt)
    ctx = contextlib.nullcontext()
    if fdt == np.float64:
        ctx = jax.enable_x64(True)
    jitted = fn if hasattr(fn, "trace") else jax.jit(fn)
    with ctx:
        try:
            traced = jitted.trace(*args)
        except Exception as exc:
            report.errors.append(
                f"abstract trace failed ({type(exc).__name__}): {exc}"
            )
            return report
        report.passed.append("abstract trace succeeded (no host transfer)")
        errs, ok = _check_trace_dtypes(traced.jaxpr.jaxpr, fdt)
        report.errors += errs
        report.passed += ok
        if check_outputs:
            errs, ok = _check_out_leaves(jax.eval_shape(jitted, *args), fdt, idt)
            report.errors += errs
            report.passed += ok
        if expect_donation is not None:
            errs, ok = _check_donation(traced.lower(), expect_donation)
            report.errors += errs
            report.passed += ok
    return report


# --------------------------------------------------------------------- #
# engine entry points
# --------------------------------------------------------------------- #
def _small_problem(trace_mode: str):
    from repro.core import Platform, PredictorModel
    from repro.core import events as E
    from repro.core import simulator as S

    mn = 60.0
    plat = Platform(mu=1000 * mn, C=10 * mn, D=1 * mn, R=10 * mn, M=5 * mn)
    work = 8 * 86400.0
    pred = PredictorModel(recall=0.85, precision=0.82, window=3000.0)
    strat = S.instant(plat, pred)
    kw = {
        "horizon": 12 * work, "mtbf": plat.mu, "recall": pred.recall,
        "precision": pred.precision, "window": pred.window,
        "lead": pred.lead, "fault_dist": E.exponential(),
    }
    if trace_mode == "device":
        traces = E.make_trace_spec(
            8, seed=7, cell_index=np.zeros(8, np.int32), **kw
        )
    else:
        traces = E.make_event_traces_batch(np.random.default_rng(7), 8, **kw)
    return work, plat, strat, traces


@contextlib.contextmanager
def _spy_dispatch(captures: list, passthrough: bool):
    """Swap ``jax_sim._dispatch`` for a capturing spy.

    ``passthrough=False`` raises :class:`_AuditDone` after the first
    capture (lanes mode: nothing fabricates per-lane results);
    ``passthrough=True`` returns the accumulator untouched so the chunk
    loop — and a whole ``run_grid`` sweep — completes without ever
    executing a compiled program."""
    from repro.core import jax_sim

    orig = jax_sim._dispatch

    def spy(runner, devs, consts, state, *acc):
        captures.append(_Capture(runner, devs, consts, state, acc))
        if passthrough and acc:
            return acc[0]
        raise _AuditDone

    jax_sim._dispatch = spy
    try:
        yield
    finally:
        jax_sim._dispatch = orig


def audit_engine(collect: str = "lanes", trace_mode: str = "device") -> AuditReport:
    """Audit one ``simulate_batch_jax`` entry point abstractly."""
    from repro.core.jax_sim import simulate_batch_jax

    label = f"simulate_batch_jax collect={collect} trace_mode={trace_mode}"
    work, plat, strat, traces = _small_problem(trace_mode)
    captures: List[_Capture] = []
    want_stats = collect == "stats"
    with _spy_dispatch(captures, passthrough=want_stats):
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # zeroed stats -> 0/0 noise
                simulate_batch_jax(
                    work, plat, strat, traces, collect=collect, chunk=None,
                )
        except _AuditDone:
            pass
    if not captures:
        return AuditReport(label, errors=["engine never reached _dispatch"])
    cap = captures[0]
    args = (cap.consts, cap.state) + cap.acc
    donated = "state+accumulator" if want_stats else "state"
    report = audit_callable(
        cap.runner, *args, label=label, expect_donation=donated,
    )
    if want_stats:
        import jax

        n_pad = cap.state["t"].shape[0]
        out = jax.eval_shape(cap.runner, *args)
        dims = {
            d
            for leaf in jax.tree_util.tree_leaves(out)
            for d in getattr(leaf, "shape", ())
        }
        if n_pad in dims:
            report.errors.append(
                f"collect='stats' output carries a lane-sized dimension "
                f"({n_pad}): per-lane state would cross to host"
            )
        else:
            report.passed.append(
                f"stats output is O(cells): no dimension equals the "
                f"padded lane count {n_pad}"
            )
    return report


def audit_mixed_law(n_runs: int = 128, chunk_lanes: int = 128) -> AuditReport:
    """A mixed-law paper-grid sweep must compile exactly one executable.

    Runs ``run_grid`` (device trace mode, fused dispatch, stats
    collection) over three cells with three different failure laws, with
    the dispatch spied out — every chunk's runner is recorded and no XLA
    program executes.  Per-family dispatch would surface distinct jitted
    runners; the law-indexed fused grid reuses one."""
    import dataclasses

    from repro.core import events as E
    from repro.core.engine import EngineConfig
    from repro.experiments.grid import GridSpec
    from repro.experiments.paper_grid import paper_grid_cells
    from repro.experiments.runner import run_grid

    label = "run_grid mixed-law device-trace fused dispatch"
    report = AuditReport(label)
    dists = [E.exponential(), E.weibull(0.7), E.lognormal(1.0)]
    # non-migration cells only: the engine legitimately specializes
    # has_migration per chunk, which is orthogonal to law fusion
    base = [c for c in paper_grid_cells("bench") if "Migration" not in c.label]
    cells = [
        dataclasses.replace(c, fault_dist=d) for c, d in zip(base, dists)
    ]
    grid = GridSpec(tuple(cells), n_runs=n_runs, seed=3)
    captures: List[_Capture] = []
    with _spy_dispatch(captures, passthrough=True):
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # zeroed stats -> 0/0 noise
                run_grid(
                    grid,
                    EngineConfig(
                        engine="jax", trace_mode="device",
                        collect="stats", chunk_lanes=chunk_lanes,
                    ),
                )
        except Exception as exc:
            # aggregation of the all-zero spy statistics may trip
            # downstream sanity checks; the dispatch pattern is already
            # recorded by then, which is all this audit needs
            if not captures:
                report.errors.append(f"sweep failed before dispatch: {exc}")
                return report
    if len(captures) < 2:
        report.errors.append(
            f"expected multiple chunks (got {len(captures)} dispatches); "
            "shrink chunk_lanes so the one-executable claim is exercised"
        )
        return report
    runners = {id(c.runner) for c in captures}
    if len(runners) > 1:
        report.errors.append(
            f"mixed-law sweep used {len(runners)} distinct compiled "
            f"runners across {len(captures)} dispatches — the law-indexed "
            "grid must lower to exactly one executable"
        )
    else:
        report.passed.append(
            f"one executable across {len(captures)} mixed-law chunk "
            "dispatches (3 failure-law families)"
        )
    return report


def run_audit() -> List[AuditReport]:
    """The full jaxpr pass: both collects, both trace modes, mixed-law."""
    return [
        audit_engine("lanes", "device"),
        audit_engine("lanes", "host"),
        audit_engine("stats", "device"),
        audit_mixed_law(),
    ]
