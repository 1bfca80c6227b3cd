#!/usr/bin/env python3
"""Drive a cell's own traffic at the control (the plain reference in
the server's place, one guarantee broken) and print what `correct`
says. Not a benchmark run: the line it prints names the platform
`control`, which no check of the driver accepts.

    python benchmarks/tests/control_run.py --workload tile_png512_c32 \
        --seed 7 --seconds 5 --mode lowered
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import cell  # noqa: E402


def control_command(workload_name: str, seed: int, mode: str) -> list:
    bench = cell.benchmark_json()
    entry = next(w for w in bench["workloads"] if w["name"] == workload_name)
    config = cell.load_json("configs", entry["config"])
    image = dict(config["image"])
    rehearse = os.environ.get("BENCH_REHEARSE_SIZE")
    if rehearse and os.environ.get("JAX_PLATFORMS") == "cpu":
        image["size_x"] = image["size_y"] = int(rehearse)
    workload = cell.load_json("workloads", workload_name)
    return [
        sys.executable,
        os.path.join(REPO, "benchmarks", "harness", "control_server.py"),
        "--mode", mode, "--reference", workload["reference"],
        "--seed", str(seed), "--image-json", json.dumps(image), "--",
    ]


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--mode", default="lowered")
    args = parser.parse_args()
    code = cell.run_cell(
        args.workload, args.seed, args.seconds, False, T_START,
        require_chip=False,
        server_command=control_command(args.workload, args.seed, args.mode),
    )
    print(f"control {args.mode} seed {args.seed}: run_cell returned {code} "
          f"({'correct' if code == 0 else 'NOT correct'})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
