"""The load kind of cell: one bulk ``load-vcf --commit`` of a generated VCF
into an empty store, timed around the program's entry point.

The job is sized from ``--seconds``: ``records_per_window_second`` x seconds
records (the workload file), so that at the speed the cell was defined at
the timed load lasts about the window.  ``load_variants_per_s`` is rows
committed / seconds of the timed call — all the rows over all the time.

Process: ``children/load_child.py`` holds the chip and runs the entry point
twice in one process — a warm-up file into a throw-away store (import,
device start, native build, parity probe and every compile: set-up), then
the real file, timed.  This process generates both files while the child
imports, never touches JAX while the child lives, and after the child has
exited reads the committed store back and compares every row with the
reference (``reference/store_check.py``).
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from common import (BENCH, Children, RunFailed, check, child_env, note,
                    note_json, work_dir, write_json_atomic)
from readers import run_record, xplane_idle
from reference import store_check
from traffic.vcf import synth_vcf_rows, write_synth_vcf

#: name -> limit of every number the comparison returns (all exact)
LIMITS = dict(store_check.LIMITS, counter_gap=0, compiled_in_window=0)

#: the faults of ``tests/faults`` that a cell of this kind can have
FAULTS = ("load.state_unchanged", "load.half_left_out",
          "load.answer_altered")


def controls(config: dict, params: dict, seed: int, seconds: float) -> dict:
    """{broken guarantee: the numbers compared}: the reference in the
    program's place at the cell's own size (``control.py``).

    ``last_wins`` — duplicates resolved last-wins where the configuration
    says first-wins; ``bin_from_start`` — the bin index taken from the start
    position alone, skipping the end-location inference; ``tail_unflushed``
    — the commit returns before the last 65,536 rows are flushed."""
    chromosomes = tuple(params["chromosomes"])
    rows = synth_vcf_rows(
        int(params["records_per_window_second"] * seconds), seed, chromosomes)
    width = int(config["shapes"]["store_width"])
    want = store_check.expected_view(rows, chromosomes, width)
    out = {"last_wins": store_check.compare(
        store_check.expected_view(rows, chromosomes, width, keep="last"),
        want)}
    from_start = {label: dict(cols) for label, cols in want.items()}
    for cols in from_start.values():
        cols["bin_level"] = np.full_like(cols["bin_level"], 13)
    out["bin_from_start"] = store_check.compare(from_start, want)
    last = chromosomes[-1]
    unflushed = dict(want)
    unflushed[last] = {k: v[:-65536] for k, v in want[last].items()}
    out["tail_unflushed"] = store_check.compare(unflushed, want)
    return out


def generate(path: str, records: int, seed: int, chromosomes: tuple) -> dict:
    """Write the VCF under a temporary name, then rename: the child waits
    for the final name."""
    rows = write_synth_vcf(path + ".part", records, seed, chromosomes)
    os.replace(path + ".part", path)
    return rows


def record_line(name: str, record: dict) -> dict:
    """What a reader of a failed run needs from a load's run record."""
    ex = record.get("execution", {})
    return {
        "load": name,
        "wall_seconds": record.get("wall_seconds"),
        "counters": {k: v for k, v in record.get("counters", {}).items()
                     if k != "alg_id"},
        "stages": {k: round(v, 3)
                   for k, v in run_record.stage_seconds(record).items()},
        "compile": ex.get("compile"),
        "kernel": ex.get("kernel"),
        "native": (ex.get("native_ingest") or {}).get("loaded"),
        "device_lookup": ex.get("device_lookup"),
    }


def run(ctx) -> dict:
    cell, params = ctx.cell, ctx.params
    chromosomes = tuple(params["chromosomes"])
    records = int(params["records_per_window_second"] * ctx.seconds)
    work = work_dir()
    children = Children(ctx.log_dir)
    trace_dir = os.path.join(work, "trace") if ctx.trace else None
    try:
        warm_vcf = os.path.join(work, "warmup.vcf")
        vcf = os.path.join(work, "load.vcf")
        store = os.path.join(work, "vdb")
        result_path = os.path.join(work, "child.json")
        job = {
            "chips": cell["chips"], "rehearse": ctx.rehearse,
            "trace": trace_dir, "result": result_path,
            "steps": [
                {"kind": "load", "name": "warmup", "vcf": warm_vcf,
                 "wait_for": warm_vcf, "store": os.path.join(work, "warm"),
                 "log": os.path.join(ctx.log_dir, "warmup.log")},
                {"kind": "load", "name": "timed", "vcf": vcf,
                 "wait_for": vcf, "store": store, "timed": True,
                 "log": os.path.join(ctx.log_dir, "timed.log")},
            ],
        }
        job_path = os.path.join(work, "job.json")
        write_json_atomic(job_path, job)
        proc, _out, err_path = children.start(
            "load_child",
            [os.path.join(BENCH, "children", "load_child.py"), job_path],
            child_env(ctx.rehearse),
        )
        t0 = time.monotonic()
        generate(warm_vcf, int(params["warmup_records"]),
                 ctx.seed + 1, chromosomes)
        rows = generate(vcf, records, ctx.seed, chromosomes)
        note_json("generate", records=records, rows=int(rows["pos"].size),
                  vcf_bytes=os.path.getsize(vcf),
                  seconds=round(time.monotonic() - t0, 2))
        child = children.result(proc, "load_child", err_path, result_path)
        warm, timed = child["steps"]
        warm_record = run_record.last_run_record(job["steps"][0]["store"])
        timed_record = run_record.last_run_record(store)
        if warm_record is None or timed_record is None:
            raise RunFailed("no completed run record in a store's ledger")
        note_json("child", import_seconds=round(child["import_seconds"], 2),
                  warmup_seconds=round(warm["t1"] - warm["t0"], 2),
                  timed_seconds=round(timed["t1"] - timed["t0"], 3),
                  logs=ctx.log_dir)
        note_json("record", **record_line("warmup", warm_record))
        note_json("record", **record_line("timed", timed_record))

        def programs(record):
            return record["execution"]["compile"]["programs"]

        compiled_in_window = programs(timed_record) - programs(warm_record)
        rows_counted = int(timed_record["counters"].get("variant", 0))
        window_s = timed["t1"] - timed["t0"]

        t0 = time.monotonic()
        try:
            got, width = store_check.read_store(store, chromosomes)
        except (OSError, ValueError, KeyError) as err:
            # nothing readable was committed: every expected row is missing
            note(f"the committed store cannot be read: {err!r}")
            got, width = {}, int(ctx.config["shapes"]["store_width"])
        want = store_check.expected_view(rows, chromosomes, width)
        numbers = store_check.compare(got, want)
        rows_expected = sum(int(v["pos"].shape[0]) for v in want.values())
        # the rate's numerator is what was read back from disk; the
        # program's own counter is only held to it
        rows_stored = sum(int(v["pos"].shape[0]) for v in got.values())
        numbers["counter_gap"] = abs(rows_counted - rows_stored)
        numbers["compiled_in_window"] = compiled_in_window
        note_json("check", rows_stored=rows_stored, rows_counted=rows_counted,
                  rows_expected=rows_expected, store_width=width,
                  store_bytes=sum(
                      os.path.getsize(os.path.join(d, f))
                      for d, _s, files in os.walk(store) for f in files),
                  seconds=round(time.monotonic() - t0, 2), **numbers)
        checks = {name: check(numbers[name], limit)
                  for name, limit in LIMITS.items()}

        artefacts = {"run_record": timed_record, "warm_record": warm_record,
                     "rows_stored": rows_stored}
        breakdown = None
        if trace_dir:
            t0 = time.monotonic()
            reduced = xplane_idle.reduce_trace(trace_dir, window_s)
            xplane_idle.keep_capture(trace_dir, ctx.log_dir)
            note_json("trace", seconds=round(time.monotonic() - t0, 2),
                      **xplane_idle.summary(reduced))
            artefacts["xplane"] = reduced
            stages = sorted(run_record.stage_seconds(timed_record).items(),
                            key=lambda kv: -kv[1])
            note_json("stages", busy_s=stages)
            breakdown = xplane_idle.breakdown(reduced)
        return {
            "end_to_end": {
                "load_variants_per_s": rows_stored / window_s,
                "setup_s": timed["t0"] - ctx.t_start,
            },
            "attempted": rows_expected,
            "failed": max(rows_expected - rows_stored, 0),
            "checks": checks,
            "device": child["device"],
            "artefacts": artefacts,
            "breakdown": breakdown,
        }
    finally:
        children.stop_all()
        shutil.rmtree(work, ignore_errors=True)
