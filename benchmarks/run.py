#!/usr/bin/env python3
"""One run of one benchmark cell against the real server on the chip.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (see README.md). This
process never initialises a JAX backend: the server is the one process
on the chip. With no TPU the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "omero_ms_pixel_buffer_tpu")):
        print("the system under test (omero_ms_pixel_buffer_tpu/) is not in "
              "this checkout: nothing to run", file=sys.stderr)
        return 2
    from benchmarks.harness.cell import benchmark_json, run_cell

    seconds = args.seconds
    if seconds is None:
        seconds = float(benchmark_json()["run_seconds"])
    code = run_cell(args.workload, args.seed, seconds, bool(args.trace),
                    T_START)
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        print("the benchmark's own process initialised a JAX backend",
              file=sys.stderr)
        return 4
    return code


if __name__ == "__main__":
    sys.exit(main())
