"""The benchmark's own plumbing: loading by name, arithmetic, the result.

Everything a cell needs is found by the name ``BENCHMARK.json`` gives it:

* ``chipbench/configs/<config>.json``: one deployment;
* ``chipbench/traffic/<traffic>.json``: one traffic mix, data: its
  ``kind``, the program's engine settings and the kind's sizes;
* ``chipbench/traffic/<kind>.py``: the driver of a traffic kind
  (:mod:`chipbench.load`);
* ``chipbench/references/<reference>.py``: the plain reference a
  configuration names (a module name);
* ``chipbench/metrics/<metric>.py``: one per-layer metric reader, whose
  ``read(ctx)`` returns a number, or None where it finds nothing to read;
  ``ctx`` holds the driver's ``layer()`` numbers, the reduced ``trace``,
  the ``peaks``, the ``cfg``, the ``traffic`` and the cell's ``devices``;
* ``chipbench/peaks.json``: the chip peaks, keyed by ``device_kind``.

Nothing here imports JAX or the program.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(Exception):
    """A cell, file or device the benchmark cannot run."""


def _json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}") from None


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    names = [w["name"] for w in bench["workloads"]]
    raise BenchError(f"unknown workload {name!r} (expected one of {names})")


def load_config(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "chipbench", "configs", f"{name}.json"))


def load_traffic(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "chipbench", "traffic", f"{name}.json"))


def load_module(kind: str, name: str, root: str = ROOT):
    """Import ``chipbench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = os.path.join(root, "chipbench", kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"missing {os.path.relpath(path, root)}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(name: str):
    """The plain reference a configuration names, as a package module so
    that spawned workers can import what it defines."""
    import importlib

    if not name.isidentifier():
        raise BenchError(f"reference name {name!r} is not a module name")
    return importlib.import_module(f"chipbench.references.{name}")


def cell_metrics(bench: dict, workload: str, section: str) -> list:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that the
    cell reports: those with no ``workloads`` key, and those naming it."""
    return [
        m for m in bench[section]
        if "workloads" not in m or workload in m["workloads"]
    ]


def peaks(device_kind: str, root: str = ROOT) -> dict:
    table = _json(os.path.join(root, "chipbench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(
            f"no peaks for device kind {device_kind!r} in chipbench/peaks.json"
        )
    return table["devices"][device_kind]


def rate(work: float, seconds: float) -> float:
    """All the work over all the time of the window."""
    if seconds <= 0.0:
        raise BenchError("empty window")
    return work / seconds


def percentile(values, q: float) -> float:
    """Nearest-rank percentile over every sample: the smallest value with
    at least ``q`` percent of the samples at or below it."""
    v = sorted(values)
    if not v:
        raise BenchError("no samples")
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    """The run's last line: the driver's keys, then ``checks`` (each number
    compared beside its limit) last."""
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def check_lines(checks: dict) -> list:
    return [
        f"check {k}: {c['value']!r} limit {c['limit']!r}"
        for k, c in checks.items()
    ]
