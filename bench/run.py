#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up draws the cell's traffic from ``--seed``, builds the simulator
and warms it up (one call, or for a streamed cell one segment and the
drain), which compiles or loads every program the window runs
(``setup_s`` counts all of it, from the start of this process).  The
window then runs whole calls back to back until ``--seconds`` have
passed.  With ``--trace 1`` the first call of the window (a streamed
call: one of its segments) runs under the profiler and the per-layer
metrics are read from its trace.  Once the window has closed,
the calls it made are replayed through the numpy reference and compared
fact by fact.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last the numbers compared with their limits,
which the last lines of standard error repeat.  Without a TPU, with fewer
chips than the cell asks for, or without the simulator beside this
directory, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the simulator (src/repro) is not beside bench/",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
