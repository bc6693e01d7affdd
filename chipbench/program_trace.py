"""What the program itself puts on the record of a traced run.

* Host spans named ``repro.<layer>.<step>`` (``repro.core.spans``).  The
  profiler trace holds them with their stats (a ``call`` id shared by
  the spans of one ``run_grid`` or ``optimize`` call, and counts), and
  the process keeps those that opened and closed while the profiler ran:
  :func:`recorded` reads the kept ones, :func:`read_spans` reads them
  from a trace file on the trace's own clock, :func:`calls` sums them
  per call.
* The device scope ``step_kernel`` (``jax.named_scope`` around the hot
  step kernel).  A TPU trace names its ops by their HLO text alone, with
  no stats, and a scope names only a custom call there: the kernel is
  ``%step_kernel.<n>``.  :func:`scope_share` reads an op's scope from
  its name, or from its stats where a trace keeps them;
  :func:`nested_loop_share` finds the lane machine's nested loops by
  the nesting of the ``while`` ops, which no scope can mark there.
* Idle gaps named by the innermost span, the benchmark's
  (``chipbench.*``) or the program's: :func:`gap_names`, and the share
  of the idle time inside one benchmark span that a program span holds:
  :func:`held_share`.

A program without spans or the scope (an older checkout) gives None or
nothing here, never an error.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import replace

PREFIX = "repro."
#: the engine's chunk programs: ``jit(run_stats)`` on one chip, the
#: ``shard_map`` body on several (as ``chunk_device_ms.sweep`` reads them)
STEP_PROGRAM = re.compile(r"^jit_(run_stats|body)\b")


def recorded():
    """The program's spans that opened and closed while the profiler ran,
    ``[(name, start_ns, duration_ns, stats)]`` on the host's clock; None
    where the program keeps none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return list(spans.RECORDED)


def calls(records, top: str) -> list:
    """Per call whose top span is named ``top``: ``{span name: total
    nanoseconds}`` over the call's spans, ``top`` included; missing names
    read 0."""
    out = {}
    for name, _, dur, stats in records or ():
        if name == top:
            out[stats["call"]] = defaultdict(float, {top: float(dur)})
    for name, _, dur, stats in records or ():
        c = out.get(stats.get("call"))
        if c is not None and name != top:
            c[name] += dur
    return list(out.values())


def mean_ms(records, top: str, value):
    """Mean over the calls of ``top`` of ``value(call totals)``, in
    milliseconds; None without any such call."""
    cs = calls(records, top)
    return 1e-6 * sum(value(c) for c in cs) / len(cs) if cs else None


def _union(intervals) -> float:
    tot, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            tot += e - max(s, end)
            end = e
    return tot


def _step_runs(tr):
    """Per device: the step program's runs in the window, each with its
    op events ``[(name, start, end, what)]`` clipped to the window."""
    out = []
    for dev, evs in tr.ops.items():
        runs = [
            (s, s + d) for name, s, d, *_ in tr.modules.get(dev, ())
            if STEP_PROGRAM.match(name) and s < tr.end_ns
            and s + d > tr.start_ns
        ]
        ops = {r: [] for r in runs}
        for e in evs:
            s, t = max(e[1], tr.start_ns), min(e[1] + e[2], tr.end_ns)
            mid = e[1] + e[2] / 2.0
            for r in runs:
                if t > s and r[0] <= mid <= r[1]:
                    ops[r].append((e[0], s, t, e[3] if len(e) > 3 else ""))
                    break
        out.extend(ops.values())
    return out


def _share(tr, pick):
    """Percent of the step program's busy time (the union of the op
    intervals inside its runs) in the ops ``pick(run's ops)`` returns;
    None where it picks none."""
    busy = held = 0.0
    found = False
    for ops in _step_runs(tr):
        busy += _union((s, t) for _, s, t, _ in ops)
        chosen = pick(ops)
        found = found or bool(chosen)
        held += _union((s, t) for _, s, t, _ in chosen)
    return 100.0 * held / busy if found and busy > 0.0 else None


def scope_share(tr, scope: str):
    """Percent of the step program's busy time in ops under the named
    scope ``scope``: an op named ``[%]<scope>.<n>`` (the custom call the
    scope names in a TPU trace), or whose stats hold ``/<scope>/``."""
    name = re.compile(rf"^%?{re.escape(scope)}\.\d+(\s|$)")
    path = re.compile(rf"/{re.escape(scope)}/")
    return _share(tr, lambda ops: [
        o for o in ops if name.match(o[0]) or path.search(o[3])
    ])


def nested_loop_share(tr):
    """Percent of the step program's busy time in the lane machine's
    nested loops: every ``while`` op of a run but its longest, the outer
    loop (its cursor priming, lookahead, false-prediction and
    stale-fault loops, including those inside its conditionals)."""
    loop = re.compile(r"^%?while\.\d+(\s|$)")

    def pick(ops):
        whiles = sorted((o for o in ops if loop.match(o[0])),
                        key=lambda o: o[2] - o[1])
        return whiles[:-1]

    return _share(tr, pick)


def read_spans(path: str, prefix: str = PREFIX) -> list:
    """The host spans named ``prefix...`` in a recorded trace file, the
    program's by default, ``[(name, start_ns, duration_ns, stats)]`` on
    the trace's clock, from every thread's line (two threads can share a
    line name)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, float(e.start_ns),
                                float(e.duration_ns), dict(e.stats)))
    return out


def gap_names(tr, program_spans, device: str = None) -> list:
    """``Trace.idle_gaps`` with each gap named by the innermost span of
    either kind, the benchmark's or the program's (``program_spans`` on
    the trace's clock)."""
    spans = list(tr.spans) + [tuple(s[:3]) for s in program_spans]
    return replace(tr, spans=spans).idle_gaps(device)


def held_share(tr, program_spans, within: str, device: str = None):
    """Percent of one device's idle time inside the benchmark's
    ``within`` spans that some program span holds; None where those
    spans hold no idle time."""
    if not tr.ops:
        return None
    device = device or sorted(tr.ops)[0]
    edges = [tr.start_ns]
    for s, e in tr.busy_intervals(device):
        edges += [s, e]
    edges.append(tr.end_ns)
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    outer = [(s, s + d) for name, s, d in tr.spans if name == within]
    prog = [(s, s + d) for _, s, d, *_ in program_spans]

    def cut(ivs, by):
        return [
            (max(a, c), min(b, d)) for a, b in ivs for c, d in by
            if min(b, d) > max(a, c)
        ]

    idle_in = cut(idle, outer)
    total = _union(idle_in)
    if total <= 0.0:
        return None
    return 100.0 * _union(cut(idle_in, prog)) / total
