"""The process that holds the chip while a store is built.

``python benchmarks/children/load_child.py <job.json>`` runs the job's steps
in order, in ONE process (one import, one device start-up, one set of
compiled programs), each through the program's own entry point:

- ``{"kind": "load", "vcf", "store", "log", "wait_for"?, "timed"?}`` —
  ``annotatedvdb_tpu.cli.load_vcf.main`` with the argv of
  ``python -m annotatedvdb_tpu load-vcf --fileName .. --storeDir .. --commit``
  and every default knob.  A ``timed`` step is the measured window: the
  harness's clock (``time.monotonic``) around the call, and, when the job
  says ``trace``, a ``jax.profiler`` capture of exactly that call (Python
  tracer off: 20 s of a Python-heavy load would be millions of events).
- ``{"kind": "compact", "store"}`` — ``cli.doctor.main(["compact", ..])``.

After the first step the device JAX gave the program is known; anything but
the job's ``chips`` TPU devices ends the process with exit code 3 unless
the job says ``rehearse``.  The result (device, per-step clock readings,
peak device memory) goes to ``job["result"]``; stdout and stderr belong to
the program and go to the log files the parent opened.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import time

from _report import device_report, start_trace, write_json

NO_DEVICE_RC = 3


def _wait_for(path: str, timeout: float = 600.0) -> None:
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise SystemExit(f"load_child: {path} never appeared")
        time.sleep(0.01)


def main(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    t_start = time.monotonic()
    from annotatedvdb_tpu.cli import doctor, load_vcf

    result = {"steps": [], "import_seconds": time.monotonic() - t_start}
    for k, step in enumerate(job["steps"]):
        if step.get("wait_for"):
            _wait_for(step["wait_for"])
        record = {"kind": step["kind"], "name": step.get("name")}
        if step["kind"] == "load":
            argv = ["--fileName", step["vcf"], "--storeDir", step["store"],
                    "--commit", "--logFilePath", step["log"]]
            tracing = bool(step.get("timed") and job.get("trace"))
            if tracing:
                start_trace(job["trace"])
            record["t0"] = time.monotonic()
            rc = load_vcf.main(argv)
            record["t1"] = time.monotonic()
            if tracing:
                import jax

                jax.profiler.stop_trace()
                record["trace_stopped"] = time.monotonic()
        elif step["kind"] == "compact":
            out = io.StringIO()
            record["t0"] = time.monotonic()
            with contextlib.redirect_stdout(out):
                rc = doctor.main(["compact", "--storeDir", step["store"],
                                  "--json"])
            record["t1"] = time.monotonic()
            record["report"] = {
                key: value for key, value in json.loads(out.getvalue()).items()
                if key != "plan"
            }
        else:
            raise SystemExit(f"load_child: unknown step {step['kind']!r}")
        record["rc"] = int(rc or 0)
        result["steps"].append(record)
        if k == 0 or k == len(job["steps"]) - 1:
            result["device"] = device_report()
            write_json(job["result"], result)
            device = result["device"]
            on_chip = (device["platform"] == "tpu"
                       and device["count"] >= job["chips"])
            if not on_chip and not job.get("rehearse"):
                print(f"load_child: JAX gave {device}, the cell asks for "
                      f"{job['chips']} TPU chip(s)", file=sys.stderr)
                return NO_DEVICE_RC
        if record["rc"] != 0:
            write_json(job["result"], result)
            return record["rc"]
    result["done"] = True
    write_json(job["result"], result)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
