#!/usr/bin/env python3
"""Readings of the program and of the control, for setting the limits.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] [--calls 3]

For each seed, in one process (the program compiles once): the cell's
program makes ``--calls`` whole calls as a run's window does; the numpy
reference replays them; the numbers compared are printed for the program
(the lower readings) and for the control (the upper readings).  The
control is the reference with one guarantee of the configuration broken:
the NAT probes 1 slot of its flow table instead of the stated 8.  It must
fail the comparison; the program must pass it.  One JSON line per seed.
The benchmark's own runs never run this.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--calls", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from bench.drivers import DRIVERS
    from bench.harness import hold_to_config, load_cell
    from repro.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = load_cell(args.workload)
    for seed in args.seeds:
        driver = DRIVERS[cell.config["engine"]](cell.config, cell.traffic,
                                                seed)
        hold_to_config(cell, driver.program_facts())
        t0 = time.perf_counter()
        for i in range(1, args.calls + 1):
            driver.call(i, keep=True)
        t1 = time.perf_counter()
        program = driver.check()
        t2 = time.perf_counter()
        control = driver.check(control=True)
        t3 = time.perf_counter()
        print(json.dumps(dict(
            workload=args.workload, seed=seed, calls=args.calls,
            compared=program.compared, program_ok=program.ok(),
            control_ok=control.ok(), program=program.values,
            control=control.values, calls_s=t1 - t0, check_s=t2 - t1,
            control_s=t3 - t2)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
