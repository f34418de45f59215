#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, one configuration or one per-layer
metric is a file found by name (``workloads/<cell>.json``,
``configs/<config>.json``, ``metrics/<metric>.json`` -> ``readers/<reader>.py``,
``drivers/<driver>.py``); ``BENCHMARK.json`` at the root says which exist.
This file only wires them: arguments -> cell -> driver -> the one last line
on stdout.  It never imports JAX: the children the driver starts hold the
chip.  Everything else a reader of a failed run needs goes to stderr, and
the children's own output to ``chiprun_out/benchmarks/<cell>/``.

``--rehearse`` is for the CPU: it pins the children to ``JAX_PLATFORMS=cpu``,
applies the workload file's small ``rehearse`` sizes, and the line's device
block then says ``cpu`` — never a measurement.  Without it a run on anything
but the cell's TPU chips exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import types

T_START = time.monotonic()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):  # BENCH first: its modules are the yardstick
    if path in sys.path:
        sys.path.remove(path)
    sys.path.insert(0, path)

from common import (LOG_ROOT, NO_DEVICE_RC, RunFailed, load_json,  # noqa: E402
                    note)


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; a device that is not in the table is an
    error, not a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise RunFailed(f"device kind {kind!r} is not in benchmarks/peaks.json")
    return table[kind]


def read_layer_metrics(bench: dict, cell_name: str, artefacts: dict) -> dict:
    """Every per-layer metric of the cell that its reader finds something
    to read for."""
    out = {}
    for metric in bench["per_layer"]:
        if not applies(metric, cell_name):
            continue
        spec = load_json(os.path.join(BENCH, "metrics",
                                      f"{metric['name']}.json"))
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(artefacts, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal at the workload's small sizes; "
                             "the device block says cpu")
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "annotatedvdb_tpu")):
        note("no program around the benchmark (annotatedvdb_tpu/ missing)")
        return 2
    try:
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        known = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in known:
            raise RunFailed(f"unknown workload {args.workload!r}; "
                            f"BENCHMARK.json has {sorted(known)}")
        cell = load_json(os.path.join(BENCH, "workloads",
                                      f"{args.workload}.json"))
        cell["name"] = args.workload
        config = load_json(os.path.join(BENCH, "configs",
                                        f"{cell['config']}.json"))
        params = dict(cell["parameters"])
        if args.rehearse:
            params.update(cell.get("rehearse", {}))
        log_dir = os.path.join(LOG_ROOT, args.workload)
        os.makedirs(log_dir, exist_ok=True)
        ctx = types.SimpleNamespace(
            cell=cell, config=config, params=params, seed=args.seed,
            seconds=args.seconds, trace=bool(args.trace),
            rehearse=args.rehearse, log_dir=log_dir, t_start=T_START,
        )
        driver = importlib.import_module(f"drivers.{cell['driver']}")
        result = driver.run(ctx)

        device = dict(result["device"])
        on_chip = device["platform"] == "tpu" and device["count"] == cell["chips"]
        if not on_chip and not args.rehearse:
            raise RunFailed(f"ran on {device}, the cell asks for "
                            f"{cell['chips']} TPU chip(s)", rc=NO_DEVICE_RC)
        if on_chip:
            hbm = peaks_for(device["kind"])["hbm_bytes"]
            note(f"memory_peak_bytes {device['memory_peak_bytes']} = "
                 f"{100 * device['memory_peak_bytes'] / hbm:.2f}% of one "
                 f"chip's memory")
        if args.trace:
            reduced = result["artefacts"].get("xplane")
            if reduced:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
            metrics = read_layer_metrics(bench, args.workload,
                                         result["artefacts"])
        else:
            metrics = {
                m["name"]: {"value": result["end_to_end"][m["name"]],
                            "unit": m["unit"]}
                for m in bench["end_to_end"] if applies(m, args.workload)
            }
    except RunFailed as err:
        note(f"FAILED: {err}")
        return err.rc

    checks = result["checks"]
    correct = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())
    line = {
        "correct": correct,
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if args.trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    line["checks"] = checks
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        note(f"check {name}: {c['value']} (limit {c['limit']}) {verdict}")
    sys.stderr.flush()
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
