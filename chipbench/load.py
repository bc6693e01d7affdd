"""What every traffic kind shares: its seed streams, its engine settings,
and the lookup of the kind's driver by name.

A traffic mix (``chipbench/traffic/<mix>.json``) is data: it names its
``kind``, the program's ``engine`` settings (the fields of
``repro.core.engine.EngineConfig``) and the kind's own parameters.  The
kind is code, ``chipbench/traffic/<kind>.py``, whose ``Load(cfg,
traffic, seed)`` drives the program (``warm``, ``step``, ``reset``) and
keeps what the window produced for the comparison (``check``), and whose
``control_numbers`` puts the plain reference in the program's place.  A
new mix of a known kind is one data file; a new kind, one code file.
"""

from __future__ import annotations

import numpy as np

from .bench import BenchError, load_module

#: ``--seed`` -> independent streams for the program, reference and control
STREAM_PROGRAM, STREAM_REFERENCE, STREAM_CONTROL = 0, 1, 2


def seed_stream(seed: int, which: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, which]))


def stream_seed(seed: int, which: int) -> int:
    return int(seed_stream(seed, which).integers(0, 2**62))


def engine(traffic: dict):
    """The program's ``EngineConfig``, as the traffic file states it."""
    from repro.core.engine import EngineConfig

    try:
        return EngineConfig(**traffic["engine"]).validate()
    except (KeyError, TypeError, ValueError) as e:
        raise BenchError(f"traffic engine settings: {e}") from None


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def kind(traffic: dict, root: str):
    """The traffic kind's module, ``chipbench/traffic/<kind>.py``."""
    name = traffic.get("kind")
    if not isinstance(name, str) or not name.isidentifier():
        raise BenchError(f"traffic kind {name!r} is not a module name")
    return load_module("traffic", name, root)


def make(cfg: dict, traffic: dict, seed: int, root: str):
    return kind(traffic, root).Load(cfg, traffic, seed)
