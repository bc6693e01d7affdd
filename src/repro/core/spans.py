"""Host spans of the program, on the profiler's clock.

One ``with span(name, record, key, **attrs):`` block is, at once:

* a :class:`jax.profiler.TraceAnnotation` named ``name``
  (``repro.<layer>.<step>``) whose keyword attributes reach a profiler
  trace as the event's stats; it shares the trace's clock with the
  device planes, so a device idle gap can be put down to the host work
  that held it;
* a host timer whose seconds add to ``record[key]`` where both are
  given (the engine's ``LAST_TIMINGS`` fields ``pack_s``,
  ``dispatch_s``, ``fetch_s``);
* one call of the program: a span opened while no span is open on its
  thread takes a fresh ``call`` id, and every span opened inside it on
  that thread carries the same id.  Nesting on the thread gives the
  parent.

While a profiler session is on, every span that opened and closed inside
it is also kept in :data:`RECORDED`, for readers that have the process
but not the trace file.  With no session, nothing is kept and no
annotation is made: the cost is the timer and the id.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque

__all__ = ["span", "RECORDED"]

#: spans that opened and closed inside a profiler session, oldest first:
#: ``(name, start_ns, duration_ns, attrs)``, times on the host's
#: ``time.perf_counter_ns`` clock, ``attrs`` with the span's ``call``
RECORDED: deque = deque(maxlen=1 << 16)

_CALLS = itertools.count(1)
_LOCAL = threading.local()
_ANNOTATION = None


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class span:
    """``with span("repro.engine.pack", timings, "pack_s", chunk=k):``"""

    __slots__ = ("name", "record", "key", "attrs", "_ann", "_t0", "_top")

    def __init__(self, name: str, record: dict = None, key: str = None,
                 /, **attrs):
        self.name, self.record, self.key, self.attrs = name, record, key, attrs

    def __enter__(self):
        call = getattr(_LOCAL, "call", None)
        self._top = call is None
        if self._top:
            call = _LOCAL.call = next(_CALLS)
        self.attrs["call"] = call
        ann = _annotation()
        self._ann = None
        if ann.is_enabled():
            self._ann = ann(self.name, **self.attrs)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            if self._ann.is_enabled():
                RECORDED.append((self.name, self._t0, dt, self.attrs))
        if self.record is not None:
            self.record[self.key] = self.record.get(self.key, 0.0) + dt * 1e-9
        if self._top:
            _LOCAL.call = None
        return False
