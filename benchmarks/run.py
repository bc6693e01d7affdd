"""Benchmark harness — one module per paper table/figure plus the
system-level checkpoint/step/roofline benches.

Prints ``name,us_per_call,derived`` CSV (assignment format) and writes the
same records as machine-readable JSON (default ``BENCH_sim.json``) so the
perf trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only sim_tables]
                                            [--json BENCH_sim.json]
"""

from __future__ import annotations

import argparse
import sys
import time

from . import common

#: benchmark registry (name -> module), importable lazily so ``--only``
#: validation fails fast instead of paying every module's import cost
MODULE_NAMES = (
    "sim_tables",        # Tables 1-2
    "waste_curves",      # Figures 4-7
    "recall_precision",  # Figures 8-11
    "jax_engine",        # device-engine throughput + scaling curves
    "ckpt_bench",        # C measurement + waste impact
    "step_bench",        # real CPU step timings
    "roofline_report",   # Roofline table from cache
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale run counts")
    ap.add_argument("--only", default=None, metavar="NAME",
                    help=f"run a single benchmark: {', '.join(MODULE_NAMES)}")
    ap.add_argument(
        "--json", default=None,
        help="machine-readable output path ('' disables; default "
        "BENCH_sim.json, or BENCH_sim.<module>.json under --only so "
        "partial runs never clobber the full tracking file)",
    )
    args = ap.parse_args()
    if args.only and args.only not in MODULE_NAMES:
        ap.exit(
            2,
            f"error: unknown benchmark {args.only!r} for --only; "
            f"expected one of: {', '.join(MODULE_NAMES)}\n",
        )
    if args.json is None:
        args.json = (
            f"BENCH_sim.{args.only}.json" if args.only else "BENCH_sim.json"
        )

    import importlib

    modules = {
        name: importlib.import_module(f".{name}", __package__)
        for name in MODULE_NAMES
        if not args.only or name == args.only
    }
    common.reset_records()
    print("name,us_per_call,derived")
    t0 = time.monotonic()
    ran = []
    for name, mod in modules.items():
        print(f"# == {name} ==", file=sys.stderr, flush=True)
        mod.run(quick=not args.full)
        ran.append(name)
    total = time.monotonic() - t0
    print(f"# total {total:.1f}s", file=sys.stderr)
    if args.json:
        meta = {
            "mode": "full" if args.full else "quick",
            "modules": ran,
            "total_s": round(total, 1),
        }
        common.write_records_json(args.json, meta=meta)
        print(f"# wrote {args.json}", file=sys.stderr)
        if "jax_engine" in ran and not args.only:
            # the device-engine throughput curve also lands in its own
            # tracking file, next to the main BENCH_sim.json
            common.write_records_json(
                "BENCH_sim.jax_engine.json",
                meta=meta,
                records=[
                    r for r in common.RECORDS
                    if r["name"].startswith("jax_engine/")
                ],
            )
            print("# wrote BENCH_sim.jax_engine.json", file=sys.stderr)


if __name__ == "__main__":
    main()
