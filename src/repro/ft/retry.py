"""Failure classification and deterministic retry/backoff.

One classifier serves every recovery loop in the repo — the campaign
runner's chunk-boundary dispatch retries (:mod:`repro.ft.campaign`) and
the executor's restore-tier fallback (:mod:`repro.ft.executor`).  A
dispatch failure is mapped to a :class:`FailureKind` by exception type
and message (the XLA runtime encodes its status codes in the message
text, so string matching is the portable contract across jax versions),
and a :class:`RetryPolicy` prices the retry: jittered exponential
backoff with a bounded attempt budget.

The jitter is drawn from the repo's counter-based SplitMix64 stream
(:func:`repro.core.events.splitmix64`), not wall-clock entropy, so a
resumed campaign replays the *same* backoff schedule as the run it
replaces — retries never perturb reproducibility.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable

import numpy as np

from ..core.events import splitmix64, uniform24

__all__ = ["FailureKind", "classify_failure", "RetryPolicy"]


class FailureKind(Enum):
    #: allocation pressure: shrink the resident-lane footprint and retry
    OOM = "oom"
    #: a device dropped out: rebuild the dispatch on the survivors
    DEVICE_LOSS = "device_loss"
    #: unknown runtime error: retry as-is under the backoff budget
    TRANSIENT = "transient"
    #: programming/config error or a deterministic engine failure (the
    #: kernel does not lower, the lane machine does not converge): never
    #: retried and never degraded to another engine, propagate immediately
    FATAL = "fatal"


#: message fragments the XLA runtime uses for allocation failures
_OOM_PATTERNS = (
    "RESOURCE_EXHAUSTED",
    "RESOURCE EXHAUSTED",
    "Resource exhausted",
    "Out of memory",
    "out of memory",
    "OOM",
)

#: message fragments for device health failures
_DEVICE_LOSS_PATTERNS = (
    "DEVICE_LOST",
    "device lost",
    "Device lost",
    "device is lost",
    "device unavailable",
    "NCCL",
)

#: message fragments of failures that recur on every attempt: Mosaic
#: kernel lowering/compilation, unimplemented XLA operations, and the
#: engine's own non-convergence error
_FATAL_PATTERNS = (
    "Mosaic",
    "UNIMPLEMENTED",
    "did not converge",
)

#: exception types that signal a bug, bad configuration or an operation
#: the backend cannot lower — not a fault
_FATAL_TYPES = (
    TypeError, ValueError, KeyError, AttributeError, IndexError,
    NotImplementedError,
)


def classify_failure(exc: BaseException) -> FailureKind:
    """Map an exception raised by a dispatch (or restore) to a
    :class:`FailureKind`.  Synthetic chaos exceptions carry the same
    message fragments as their real counterparts, so they classify
    through this one function — the recovery paths under test are the
    production paths."""
    kind = getattr(exc, "failure_kind", None)
    if isinstance(kind, FailureKind):
        return kind
    msg = f"{type(exc).__name__}: {exc}"
    if any(p in msg for p in _FATAL_PATTERNS):
        return FailureKind.FATAL
    if any(p in msg for p in _DEVICE_LOSS_PATTERNS):
        return FailureKind.DEVICE_LOSS
    if any(p in msg for p in _OOM_PATTERNS):
        return FailureKind.OOM
    if isinstance(exc, _FATAL_TYPES):
        return FailureKind.FATAL
    return FailureKind.TRANSIENT


@dataclass
class RetryPolicy:
    """Jittered exponential backoff with a bounded per-site budget.

    ``max_attempts`` counts tries of one logical operation (a chunk
    dispatch, a restore tier); attempt ``k`` (0-based) sleeps
    ``base * factor**k * (1 + jitter * u)`` where ``u ~ U(0,1)`` comes
    from the seeded SplitMix64 counter stream — deterministic given
    (seed, counter), so schedules replay bit-exactly across resumes.
    ``sleep`` is injectable for tests (and for the executor's simulated
    clock, which advances virtual time instead of stalling)."""

    max_attempts: int = 4
    base: float = 0.05
    factor: float = 2.0
    jitter: float = 0.5
    seed: int = 0
    sleep: Callable[[float], None] = field(default=time.sleep, repr=False)

    def backoff(self, attempt: int, counter: int) -> float:
        """Backoff duration (seconds) before retry ``attempt``."""
        hi, _lo = splitmix64(np.uint64(self.seed & 0xFFFFFFFFFFFFFFFF),
                             np.uint64(counter & 0xFFFFFFFFFFFFFFFF))
        u = float(uniform24(hi))
        return self.base * (self.factor ** attempt) * (1.0 + self.jitter * u)

    def pause(self, attempt: int, counter: int) -> float:
        """Sleep the backoff for (attempt, counter); returns the
        duration so callers can attribute the stall (e.g. to a
        :class:`~repro.ft.executor.WasteLedger` bucket)."""
        dt = self.backoff(attempt, counter)
        self.sleep(dt)
        return dt
