"""The comparison's control: the plain reference put in the program's place
with one of the configuration's guarantees broken, which ``correct`` has to
refuse.  Each traffic kind says what its control breaks
(``control_numbers`` in ``chipbench/traffic/<kind>.py``).

Runs on the host alone (no JAX), at the cell's own grid and traffic:

    python3 chipbench/control.py --workload <cell> --seed 1 2 3 [--runs N]

and prints one JSON line per seed with the numbers compared, their limits
and whether the control came out correct (it must not).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import bench, compare, load  # noqa: E402

#: control runs of each cell, against the reference's REFERENCE_RUNS
CONTROL_RUNS = 2000


def control_checks(cfg: dict, traffic: dict, seed: int, root: str,
                   n_ctrl: int = CONTROL_RUNS) -> dict:
    """The control's numbers on one seed, checked as a run's would be."""
    ref = bench.load_reference(cfg["reference"])
    out = load.kind(traffic, root).control_numbers(cfg, traffic, seed,
                                                    n_ctrl, ref)
    checked = compare.checks(out["numbers"])
    return {"checks": checked, "correct": compare.passed(checked),
            "worst": out["worst"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--runs", type=int, default=CONTROL_RUNS,
                    help="control runs per cell")
    args = ap.parse_args(argv)
    b = bench.load_benchmark()
    wl = bench.find_workload(b, args.workload)
    cfg = bench.load_config(wl["config"])
    traffic = bench.load_traffic(wl["traffic"])
    for s in args.seed:
        out = control_checks(cfg, traffic, s, bench.ROOT, args.runs)
        print(json.dumps({"workload": args.workload, "seed": s, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
