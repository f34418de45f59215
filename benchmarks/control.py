#!/usr/bin/env python3
"""The control of each cell's comparison: the reference, put in the
program's place with one guarantee of the configuration broken, at the
cell's own size.  It has to come out as not correct.

    python3 benchmarks/control.py --workload <cell> --seed <n> [--seconds <s>]

prints one JSON line per broken guarantee: the numbers compared beside
their limits and ``"correct"``.  No chip is involved (the system runs no
model and states no precision; its guarantees are what a later change could
trade for speed); ``benchmarks/tests`` runs the same at a small size.

Which guarantees a kind of cell can break is the driver's to say:
``drivers/<driver>.py`` has ``controls(config, params, seed, seconds)`` ->
``{broken guarantee: numbers}`` and ``LIMITS``, found by the name in the
workload file as ``run.py`` finds ``run``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from common import load_json  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    cell = load_json(os.path.join(BENCH, "workloads",
                                  f"{args.workload}.json"))
    config = load_json(os.path.join(BENCH, "configs",
                                    f"{cell['config']}.json"))
    params = dict(cell["parameters"])
    if args.rehearse:
        params.update(cell.get("rehearse", {}))
    seconds = args.seconds if args.seconds is not None else load_json(
        os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))["run_seconds"]
    driver = importlib.import_module(f"drivers.{cell['driver']}")
    results = driver.controls(config, params, args.seed, seconds)
    for name, numbers in results.items():
        checks = {k: {"value": numbers[k], "limit": limit}
                  for k, limit in driver.LIMITS.items() if k in numbers}
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        print(json.dumps({"workload": args.workload, "seed": args.seed,
                          "control": name, "correct": correct,
                          "checks": checks}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
