"""The ``walk_passes.sweep`` reader: the mean over chunks and devices of
the program's ``jax_sim.LAST_TIMINGS["walk_passes"]`` count, and nothing
from a program that keeps no such count."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import bench  # noqa: E402


def _read():
    return bench.load_module("metrics", "walk_passes.sweep", ROOT).read


def test_walk_passes_reads_the_last_call(monkeypatch):
    from repro.core import jax_sim

    monkeypatch.setattr(jax_sim, "LAST_TIMINGS", {
        "loop_iters": np.array([[100, 300], [200, 400]], np.int64),
        "walk_passes": np.array([[400, 600], [1000, 2000]], np.int64),
    })
    assert _read()({}) == pytest.approx(1000.0)


@pytest.mark.parametrize("timings", [
    {},
    {"loop_iters": np.array([[100]], np.int64)},
    {"walk_passes": np.zeros((0, 1), np.int64)},
], ids=["nothing", "no-walk-count", "no-chunk"])
def test_walk_passes_gives_nothing_without_a_count(monkeypatch, timings):
    """A program that keeps no walk count (one older than the count, or a
    call that ran no chunk) leaves the metric out of the line."""
    from repro.core import jax_sim

    monkeypatch.setattr(jax_sim, "LAST_TIMINGS", timings)
    assert _read()({"layer": {}}) is None
