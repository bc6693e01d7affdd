"""Runs of the benchmark with the timed path broken underneath must come
out not correct, and a sound run correct.

Each test drives ``chipbench.run.run_cell`` on the CPU in a checkout of
the benchmark's files whose configuration and traffic files are cut to a
size a test run holds (one platform size, one predictor), with the look
for a TPU replaced by the CPU's devices, and with one fault planted in
the program: a lane machine whose chunk sums never reach the state it
returns, half of every batch left out, an answer altered where the
engine produces it, the exchange between chips left out; and for queries
an optimum altered where it is returned.  The controls (the plain
reference with a guarantee broken, in the program's place) must come out
not correct too."""

import json
import os
import shutil
import subprocess
import sys
from dataclasses import replace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

CONFIG = "s5-exact-exp"
#: the cells these tests drive: config, traffic mix, chips
CELLS = {
    "exact-sweep": ("sweep", 1),
    "exact-query": ("query", 1),
    "exact-sweep-4chip": ("sweep-all-chips", 4),
}
#: one platform size and one predictor: 3 cells
SMALL_CONFIG = {"N": [524288], "predictors": [
    {"name": "p82r85", "precision": 0.82, "recall": 0.85}]}
SMALL_TRAFFIC = {"sweep": {"lanes_per_sweep": 768},
                 "query": {"runs_per_strategy": 256}}
REFERENCE_RUNS = 300
SEED = 2**31 + 12345


def _edit_json(path, changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)
    return data


def make_root(path: str) -> str:
    """A checkout of the benchmark with small configuration and traffic
    files, a mix sharding the sweep over every chip, a BENCHMARK.json
    naming every cell of :data:`CELLS`, and the CPU's device kind in the
    peaks table."""
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(path, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    cb = os.path.join(path, "chipbench")
    _edit_json(os.path.join(cb, "configs", f"{CONFIG}.json"), SMALL_CONFIG)
    with open(os.path.join(cb, "traffic", "sweep.json")) as f:
        mix = json.load(f)
    mix["engine"]["devices"] = "all"  # the sharded cell's mix: data only
    with open(os.path.join(cb, "traffic", "sweep-all-chips.json"), "w") as f:
        json.dump(mix, f)
    for mix in {t for t, _ in CELLS.values()}:
        p = os.path.join(cb, "traffic", f"{mix}.json")
        with open(p) as f:
            kind = json.load(f)["kind"]
        _edit_json(p, SMALL_TRAFFIC[kind])
    with open(os.path.join(cb, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    with open(os.path.join(cb, "peaks.json"), "w") as f:
        json.dump(peaks, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)

    def kind(mix):
        with open(os.path.join(cb, "traffic", f"{mix}.json")) as f:
            return json.load(f)["kind"]

    mix_of = {w["name"]: w["traffic"] for w in b["workloads"]}
    b["workloads"] = [
        {"name": k, "config": CONFIG, "traffic": t, "chips": c, "why": "test"}
        for k, (t, c) in CELLS.items()
    ]
    # each cell reports what the benchmark's cells of its traffic kind report
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            kinds = {kind(mix_of[w]) for w in m["workloads"]}
            m["workloads"] = [k for k, (t, _) in CELLS.items() if kind(t) in kinds]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return path


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("checkout")))


@pytest.fixture
def on_cpu(monkeypatch):
    """The run's look for a TPU finds the CPU, the reference runs in one
    process with fewer runs, and planted faults (which act at trace time)
    meet no compiled runner."""
    import jax

    from chipbench import compare
    from chipbench import run as R
    from chipbench.references import s5
    from repro.core import jax_sim

    monkeypatch.setattr(R, "check_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(s5, "WORKERS", 1)
    monkeypatch.setattr(compare, "REFERENCE_RUNS", REFERENCE_RUNS)
    jax_sim._RUN_CACHE.clear()
    jax.clear_caches()
    yield
    jax_sim._RUN_CACHE.clear()
    jax.clear_caches()


def run(root, workload, seed=SEED):
    from chipbench import run as R

    return R.run_cell(workload, seed, 0.5, False, root=root)


def unchanged_state(monkeypatch):
    from repro.core import jax_sim

    monkeypatch.setattr(jax_sim, "_fold", lambda acc, cs: acc)


def half_batch(monkeypatch):
    import repro.experiments as E

    real = E.run_grid

    def half(grid, config=None, **kw):
        return real(replace(grid, n_runs=grid.n_runs // 2), config, **kw)

    monkeypatch.setattr(E, "run_grid", half)


def altered_answer(monkeypatch):
    from repro.core import jax_sim

    real = jax_sim.CellSums.from_matrix.__func__

    def altered(cls, cs):
        cs = np.array(cs, copy=True)
        cs[:, 1] *= 1.1  # makespan sums, as produced
        cs[:, 2] *= 1.21
        return real(cls, cs)

    monkeypatch.setattr(jax_sim.CellSums, "from_matrix", classmethod(altered))


def altered_optimum(monkeypatch):
    import repro.core as core

    real = core.optimize

    def altered(*a, **kw):
        t = real(*a, **kw)
        return replace(t, waste=t.waste * (1.0 + 1e-6))

    monkeypatch.setattr(core, "optimize", altered)


@pytest.mark.parametrize("workload", ["exact-sweep", "exact-query"])
def test_sound_run_is_correct(root, workload, on_cpu):
    out = run(root, workload)
    assert out["correct"], out["checks"]
    assert out["window_compiles"] == 0


@pytest.mark.parametrize("workload,fault,number", [
    ("exact-sweep", unchanged_state, "lanes_missing"),
    ("exact-sweep", half_batch, "lanes_missing"),
    ("exact-sweep", altered_answer, "max_abs_z"),
    ("exact-query", unchanged_state, "lanes_missing"),
    ("exact-query", half_batch, "lanes_missing"),
    ("exact-query", altered_answer, "max_abs_z"),
    ("exact-query", altered_optimum, "policy_rel_err"),
])
def test_planted_fault_is_not_correct(root, workload, fault, number,
                                      monkeypatch, on_cpu):
    fault(monkeypatch)
    out = run(root, workload)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]


SHARDED = r"""
import json, os, sys
sys.path[:0] = [{repo!r}, os.path.join({repo!r}, "src")]
import jax
from chipbench import compare
from chipbench import run as R
from chipbench.references import s5
R.check_devices = lambda chips: jax.devices()[:chips]
s5.WORKERS = 1
compare.REFERENCE_RUNS = {n_ref}
out = {{}}
for broken in (False, True):
    if broken:
        from repro.core import jax_sim
        jax_sim._RUN_CACHE.clear()
        jax.clear_caches()
        jax.lax.psum = lambda x, axis_name: x  # the exchange left out
    r = R.run_cell("exact-sweep-4chip", {seed}, 0.5, False, root={root!r})
    out[str(broken)] = {{"correct": r["correct"], "checks": r["checks"],
                         "count": r["device"]["count"]}}
print(json.dumps(out))
"""


def test_exchange_between_chips_left_out_is_not_correct(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(repo=ROOT, root=root, seed=SEED, n_ref=REFERENCE_RUNS)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["False"]["correct"] and out["False"]["count"] == 4, out
    assert not out["True"]["correct"]
    c = out["True"]["checks"]["lanes_missing"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("workload", ["exact-sweep", "exact-query"])
def test_control_is_not_correct(root, workload, monkeypatch):
    from chipbench import bench, compare, control
    from chipbench.references import s5

    monkeypatch.setattr(s5, "WORKERS", 1)
    # enough runs on both sides that the broken guarantee shows at this size
    monkeypatch.setattr(compare, "REFERENCE_RUNS", 1000)
    cfg = bench.load_config(CONFIG, root)
    traffic = bench.load_traffic(CELLS[workload][0], root)
    out = control.control_checks(cfg, traffic, SEED, root, n_ctrl=1000)
    assert not out["correct"], out["checks"]
    z = out["checks"]["max_abs_z"]
    assert z["value"] > z["limit"]
    if workload == "exact-query":
        p = out["checks"]["policy_rel_err"]
        assert p["value"] > p["limit"]
