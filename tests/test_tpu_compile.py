"""Compile rehearsals for one TPU v5e chip, run on a machine without one.

The TPU compiler compiles for a described, unattached chip, so what
Mosaic or XLA would refuse on the chip fails here first: the simulation
kernels and the fused engine step of the main path, at the default
device chunk (16,384 lanes) in float32, the TPU engine's precision.
Nothing executes, so nothing here says anything about results or times.
"""

import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jax_sim as J
from repro.kernels import sim_step as K

#: the engine's default device chunk (``jax_sim._DEFAULT_CHUNK_DEV``)
LANES = 16384

#: every fault law the device sampler lowers, plus the law-indexed mix
LAWS = [
    ("exponential", 0.0),
    ("weibull", 0.7),
    ("lognormal", 1.0),
    ("uniform", 0.0),
    ("indexed", 0.0),
]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _lanes(sharding, dtype):
    return jax.ShapeDtypeStruct((LANES,), dtype, sharding=sharding)


def _compile(fn, *shapes):
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("kind,param", LAWS, ids=[k for k, _ in LAWS])
def test_stream_advance_compiles(one_chip, kind, param):
    """The sampling kernel lowers through Mosaic for every law (its
    uniform24 draw casts through int32: Mosaic has no uint32 -> float)."""
    indexed = kind == "indexed"

    def advance(mask, ctr, tm, k0, k1, mean, horizon, law, s1, s2):
        return K.masked_stream_advance(
            mask, ctr, tm, (k0, k1), mean, horizon, kind=kind, param=param,
            law=law if indexed else None, lp=(s1, s2) if indexed else None,
            interpret=False,
        )

    f32, i32, u32 = (
        _lanes(one_chip, d) for d in (jnp.float32, jnp.int32, jnp.uint32)
    )
    compiled = _compile(
        advance, i32, i32, f32, u32, u32, f32, f32, i32, f32, f32
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind,param", LAWS, ids=[k for k, _ in LAWS])
def test_primitive_update_compiles(one_chip, kind, param):
    """The hot step lowers as the engine runs it in both trace modes:
    plain (host traces) and with the fused refill draw of ``kind``'s
    fault stream (device traces)."""

    def update(prim, cont, target, ckend, nf, t, saved, unsaved, pw, W, DR,
               k0, k1, sctr, mean, horizon, law, s1, s2, fused):
        kw = {}
        if fused:
            stream = ((k0, k1), sctr, nf, mean, horizon)
            if kind == "indexed":
                stream += (law, s1, s2)
            kw = dict(stream=stream, gap=(kind, param))
        return K.masked_primitive_update(
            prim, cont, target, ckend, nf, t, saved, unsaved, pw, W, DR,
            eps=1e-6, reg_cont=3, interpret=False, **kw,
        )

    f32, i32, u32 = (
        _lanes(one_chip, d) for d in (jnp.float32, jnp.int32, jnp.uint32)
    )
    shapes = (i32, i32) + (f32,) * 9 + (u32, u32, i32, f32, f32, i32, f32, f32)
    for fused in (False, True):
        compiled = _compile(
            lambda *a, fused=fused: update(*a, fused=fused), *shapes
        )
        assert "tpu_custom_call" in compiled.as_text()


def _grid(name):
    from repro.experiments.grid import GridSpec
    from repro.experiments.paper_grid import (
        paper_grid_cells,
        silent_grid_cells,
        two_level_grid_cells,
    )

    if name == "paper":
        cells = paper_grid_cells("full")
    else:
        cells = two_level_grid_cells("validation") + silent_grid_cells(
            "validation"
        )
    # enough runs that the grid fills a whole default device chunk
    n_runs = -(-LANES // len(cells))
    return GridSpec(cells=tuple(cells), n_runs=n_runs, seed=0)


@pytest.mark.parametrize("name", ["paper", "scenario"])
def test_engine_step_compiles(one_chip, monkeypatch, name):
    """The fused stats step of ``run_grid``'s device-trace path — one
    whole while-loop program with both kernels inside — compiles at the
    default device chunk.  The engine's own packing builds the chunk; a
    spy takes the jitted runner before anything runs, and the runner is
    built with compiled (not interpreted) kernels, as on a TPU."""
    from repro.analysis.jaxpr_audit import _spy_dispatch
    from repro.experiments.runner import build_fused_layout

    get_runner = J._get_runner

    def tpu_runner(use_pallas, interpret, *a, **kw):
        return get_runner(use_pallas, False, *a, **kw)

    monkeypatch.setattr(J, "_get_runner", tpu_runner)
    lay = build_fused_layout(_grid(name), "device")
    caps = []
    with _spy_dispatch(caps, passthrough=True), warnings.catch_warnings():
        warnings.simplefilter("ignore")  # zeroed stats -> 0/0 noise
        J.simulate_batch_jax(
            lay.work_c, lay.plats_c, lay.strats_c, lay.concat_spec(),
            precision="x32", collect="stats", chunk=LANES,
        )
    cap = caps[0]  # the first chunk is a full one

    def shape(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    assert cap.state["t"].shape == (LANES,)
    assert cap.state["t"].dtype == np.float32
    args = [jax.tree.map(shape, t) for t in (cap.consts, cap.state, cap.acc[0])]
    with jax.enable_x64(False), warnings.catch_warnings():
        # stats mode returns only the accumulator: the donated lane state
        # has no output to alias, which XLA reports and which is harmless
        warnings.filterwarnings("ignore", "Some donated buffers")
        compiled = cap.runner.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
