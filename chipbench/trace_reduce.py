"""The one reduction from a profiler trace to the benchmark's numbers.

Reads the ``.xplane.pb`` file that ``jax.profiler`` writes and returns:

* the traced window: the host span :data:`WINDOW_SPAN` that the tracing
  thread holds open while it traces (without it, from the first to the
  last event of any plane);
* per device plane (``/device:TPU:<n>``): the op events of its
  ``XLA Ops`` line and the program events of its ``XLA Modules`` line,
  and the busy time, the union of its op intervals inside the window;
* the host's ``chipbench.*`` spans (``jax.profiler.TraceAnnotation``
  from the benchmark's own files), which name the idle gaps.

Only :func:`load` touches JAX (its trace reader); the rest is plain
arithmetic over ``(name, start_ns, duration_ns)`` events, so the tests
can check it on a recorded trace or on events made by hand.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: stats of a device op that say what it is beyond its instruction name
OP_STATS = ("long_name", "tf_op", "hlo_category")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.trace_window"


@dataclass
class Trace:
    start_ns: float
    end_ns: float
    #: device plane name -> [(op name, start_ns, duration_ns, what)], with
    #: ``what`` the op's :data:`OP_STATS` joined (its HLO text, its JAX
    #: name, its category), or "" where the trace gives none
    ops: dict = field(default_factory=dict)
    #: device plane name -> [(program name, start_ns, duration_ns)]
    modules: dict = field(default_factory=dict)
    #: host spans [(name, start_ns, duration_ns)]
    spans: list = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def busy_intervals(self, device: str) -> list:
        """Merged op intervals of one device, clipped to the window."""
        iv = sorted(
            (max(s, self.start_ns), min(s + d, self.end_ns))
            for _, s, d, *_ in self.ops.get(device, ())
        )
        out = []
        for s, e in iv:
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the device planes."""
        if not self.ops:
            return 0.0
        tot = sum(
            sum(e - s for s, e in self.busy_intervals(d)) for d in self.ops
        )
        return tot * 1e-9 / len(self.ops)

    def op_seconds(self, match=None) -> dict:
        """Total seconds per op name, averaged over devices."""
        tot = defaultdict(float)
        for evs in self.ops.values():
            for name, _, d, *_ in evs:
                if match is None or match(name):
                    tot[name] += d * 1e-9
        n = max(1, len(self.ops))
        return {k: v / n for k, v in tot.items()}

    def idle_gaps(self, device: str = None) -> list:
        """``(host span, seconds)`` of each gap between busy intervals of
        one device (the first by default), longest first; a gap is named
        by the innermost ``chipbench.*`` span holding its midpoint."""
        if not self.ops:
            return []
        device = device or sorted(self.ops)[0]
        edges = [self.start_ns]
        for s, e in self.busy_intervals(device):
            edges += [s, e]
        edges.append(self.end_ns)
        gaps = []
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((self.span_at((a + b) / 2.0), (b - a) * 1e-9))
        return sorted(gaps, key=lambda g: -g[1])

    def span_at(self, t_ns: float) -> str:
        holding = [
            (d, name) for name, s, d in self.spans if s <= t_ns <= s + d
        ]
        return min(holding)[1] if holding else "no chipbench span"


def reduce_planes(planes) -> Trace:
    """Build a :class:`Trace` from ``(plane name, {line name: events})``
    pairs, events being ``(name, start_ns, duration_ns)``."""
    lo, hi = float("inf"), float("-inf")
    window = None
    tr = Trace(0.0, 0.0)
    for pname, lines in planes:
        for lname, evs in lines.items():
            for _, s, d, *_ in evs:
                lo = min(lo, s)
                hi = max(hi, s + d)
            if DEVICE_PLANE.match(pname):
                if lname == OPS_LINE:
                    tr.ops[pname] = list(evs)
                elif lname == MODULES_LINE:
                    tr.modules[pname] = list(evs)
                continue
            for e in evs:
                if e[0] == WINDOW_SPAN:
                    window = (e[1], e[1] + e[2])
                elif e[0].startswith(SPAN_PREFIX):
                    tr.spans.append(tuple(e[:3]))
    if window is None:
        window = (lo, hi) if lo < hi else (0.0, 0.0)
    tr.start_ns, tr.end_ns = window
    return tr


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(
        os.path.join(directory, "**", "*.xplane.pb"), recursive=True
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _what(event) -> str:
    stats = dict(event.stats)
    return " | ".join(str(stats[k]) for k in OP_STATS if k in stats)


def read_planes(path: str):
    """``(plane name, {line name: events})`` of a recorded trace; device
    ops carry what their stats say (:data:`OP_STATS`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name) and line.name == OPS_LINE:
                lines[line.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns), _what(e))
                    for e in line.events
                ]
            else:
                lines[line.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                ]
        out.append((plane.name, lines))
    return out


def load(path: str) -> Trace:
    return reduce_planes(read_planes(path))
