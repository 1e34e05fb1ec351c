"""Run one benchmark cell once on the chip this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of a steady sub-window and from the harness's spans and the
engines' counters.  The last line of standard output is the result
object; the numbers that decide ``correct`` are the last lines of
standard error.  Without a TPU the run exits nonzero with no result,
unless ``--rehearsal`` asks for a CPU rehearsal at the smoke size (never
a measurement).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on the CPU at the smoke size (no metrics "
                         "of the device are measured)")
    args = ap.parse_args(argv)
    from chipbench import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), args.rehearsal,
                                  t_process=T_PROCESS)
    except (harness.SetupError, FileNotFoundError) as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    harness.report(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
