"""Readings that set the limits of ``correct``: the program's and the
control's widest gap over many seeds, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 8

Each seed is a whole run of the cell (its own weights, warm-up and a
short window at the cell's own load) followed by the check; the control
(the reference in float8, the configuration's ``arch/<model_type>.py``
``logits_at``) is scored on the same
requests and judged against the same limit: ``program_correct`` should
read true and ``control_correct`` false.  One JSON line per seed; the
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               args.rehearsal, control=True)
        checks = res["checks"]
        row = {"seed": seed, "attempted": res["attempted"],
               "failed": res["failed"],
               "program_correct": all(c["ok"] for k, c in checks.items()
                                      if k != "control_gap_sd"),
               "control_correct": checks["control_gap_sd"]["ok"],
               **{k: c["value"] for k, c in checks.items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
