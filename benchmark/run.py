#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration, traffic mix, limits
and per-layer readers by name, and hands it to the driver its traffic
file names (drivers/<kind>.py). The last line of standard output is the
result object; everything else is on earlier lines or under benchmark/out/.
"""
import time

_T_START = time.perf_counter()  # before any other import: set-up starts here

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    driver = importlib.import_module(
        "benchmark.drivers." + cell["traffic_file"]["kind"])
    return driver.run(cell, args, harness.Clock(_T_START))


if __name__ == "__main__":
    sys.exit(main())
