#!/usr/bin/env python
"""Validate BENCH_*.json records against the documented bench schema.

The bench record schema is documented in README.md ("Bench JSON schema").
This checker is dependency-free (no jsonschema) and runs as a tier-1 test
(``tests/test_bench_schema.py``), so drift between what ``bench.py`` emits
and what the docs/analysis tooling expect fails fast instead of surfacing
rounds later as a KeyError in a comparison script.

Two record shapes are accepted:

- the RAW record ``bench.py`` prints (one JSON object with ``metric`` ...);
- the driver WRAPPER committed as ``BENCH_r*.json``:
  ``{"n", "cmd", "rc", "tail", "parsed"}`` where ``parsed`` is the raw
  record (may be null when ``rc`` != 0 — a failed bench run is a
  legitimate historical record and must stay loadable).

Validation is presence-tolerant across schema generations (r02 records
have no ``end_to_end``; pre-PR1 records no ``stage_wall``; pre-PR2 records
no ``queue_stalls``): required core fields must exist with the right
types, every OPTIONAL section is validated strictly when present.

``MULTICHIP_r*.json`` files are validated too: the historic dryrun
wrappers (``{"n_devices", "rc", "ok", ...}``) stay loadable, and
``--multichip`` records carry the strict ``multichip`` scaling block
(``byte_identical`` REQUIRED true at every device count).

``REPL_r*.json`` files (the committed ``chaos_soak.py --repl`` failover
certifications) validate as raw chaos records with the strict ``repl``
block: ``acked_missing`` REQUIRED 0, ``recovered`` REQUIRED true, zero
violations — the same contract the ``serving.replication`` bench block
carries.

Usage::

    python tools/check_bench_schema.py [FILE ...]   # default:
                          # BENCH_*.json + MULTICHIP_*.json + REPL_*.json
"""

from __future__ import annotations

import glob
import json
import os
import sys

NUM = (int, float)


def _is_num(v) -> bool:
    return isinstance(v, NUM) and not isinstance(v, bool)


def _is_int(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


def _check_fields(obj: dict, spec: dict, where: str, errors: list,
                  required: tuple = ()) -> None:
    """``spec`` maps field -> predicate; fields in ``required`` must exist,
    the rest are validated only when present."""
    for field in required:
        if field not in obj:
            errors.append(f"{where}: missing required field {field!r}")
    for field, pred in spec.items():
        if field in obj and not pred(obj[field]):
            errors.append(
                f"{where}: field {field!r} has invalid value "
                f"{obj[field]!r} ({type(obj[field]).__name__})"
            )


def _check_stages(stages, where: str, errors: list) -> None:
    if not isinstance(stages, dict) or not stages:
        errors.append(f"{where}: stages must be a non-empty object")
        return
    for name, rec in stages.items():
        if not isinstance(rec, dict):
            errors.append(f"{where}.stages.{name}: must be an object")
            continue
        _check_fields(
            rec, {"seconds": _is_num, "items": _is_int},
            f"{where}.stages.{name}", errors, required=("seconds",),
        )


def _check_stage_wall(sw, where: str, errors: list) -> None:
    if not isinstance(sw, dict):
        errors.append(f"{where}: stage_wall must be an object")
        return
    _check_fields(
        sw,
        {"wall_seconds": _is_num, "busy_seconds": _is_num, "overlap": _is_num},
        f"{where}.stage_wall", errors,
        required=("wall_seconds", "busy_seconds"),
    )


def _check_queue_stalls(qs, where: str, errors: list) -> None:
    """The PR-2 backpressure block: one record per stage boundary."""
    if not isinstance(qs, dict):
        errors.append(f"{where}: queue_stalls must be an object")
        return
    for boundary, rec in qs.items():
        w = f"{where}.queue_stalls.{boundary}"
        if not isinstance(rec, dict):
            errors.append(f"{w}: must be an object")
            continue
        _check_fields(
            rec,
            {"items": _is_int, "producer_block_s": _is_num,
             "consumer_wait_s": _is_num, "max_depth": _is_int},
            w, errors,
            required=("items", "producer_block_s", "consumer_wait_s",
                      "max_depth"),
        )
        for key in ("producer_block_s", "consumer_wait_s"):
            if _is_num(rec.get(key)) and rec[key] < 0:
                errors.append(f"{w}.{key}: negative stall seconds")


def _check_end_to_end(e2e, where: str, errors: list) -> None:
    if not isinstance(e2e, dict):
        errors.append(f"{where}: end_to_end must be an object")
        return
    w = f"{where}.end_to_end"
    # spine-v2 records ("ingest_spine": 2, the chunked-prefetch loader)
    # must PROVE the device was not idle-dominant: device_idle_fraction
    # and the per-stage breakdown are required, not optional.  Historic
    # pre-spine records keep validating against the relaxed core schema.
    spine_v2 = e2e.get("ingest_spine") == 2
    required = ["variants_per_sec", "variants", "seconds", "stages"]
    if spine_v2:
        required += ["device_idle_fraction", "stage_wall"]
    _check_fields(
        e2e,
        {
            "variants_per_sec": _is_num, "variants": _is_int,
            "duplicates": _is_int, "seconds": _is_num, "vcf_mb": _is_num,
            "mb_per_sec": _is_num,
            "pipeline": lambda v: isinstance(v, str),
            "device_idle_fraction": _is_num,
            "ingest_spine": _is_int,
            # median_headline sampling: every measured run's rate
            "runs": lambda v: isinstance(v, list)
            and all(_is_num(x) for x in v),
        },
        w, errors,
        required=tuple(required),
    )
    if spine_v2 and _is_num(e2e.get("device_idle_fraction")):
        f = e2e["device_idle_fraction"]
        if not (0.0 <= f <= 1.0):
            errors.append(
                f"{w}.device_idle_fraction: {f} outside [0, 1]"
            )
    if "stages" in e2e:
        _check_stages(e2e["stages"], w, errors)
    if "stage_wall" in e2e:
        _check_stage_wall(e2e["stage_wall"], w, errors)
    if "queue_stalls" in e2e:
        _check_queue_stalls(e2e["queue_stalls"], w, errors)
    if "vep_update" in e2e:
        vu = e2e["vep_update"]
        if not isinstance(vu, dict):
            errors.append(f"{w}.vep_update: must be an object")
        else:
            _check_fields(
                vu,
                {"results_per_sec": _is_num, "updated": _is_int,
                 "seconds": _is_num,
                 "runs": lambda v: isinstance(v, list)
                 and all(_is_num(x) for x in v)},
                f"{w}.vep_update", errors,
                required=("results_per_sec", "updated", "seconds"),
            )


def _check_serving(sv, where: str, errors: list) -> None:
    """The avdb-serve bench block: concurrent-client QPS + latency
    percentiles + batch-fill, with an optional region sub-leg."""
    if not isinstance(sv, dict):
        errors.append(f"{where}: serving must be an object")
        return
    w = f"{where}.serving"
    _check_fields(
        sv,
        {
            "qps": _is_num, "p50_ms": _is_num, "p99_ms": _is_num,
            "requests": _is_int, "clients": _is_int, "errors": _is_int,
            "batch_fill": _is_num, "batches": _is_int, "seconds": _is_num,
            "store_rows": _is_int,
        },
        w, errors,
        required=("qps", "p50_ms", "p99_ms", "requests", "batch_fill",
                  "seconds"),
    )
    if _is_num(sv.get("batch_fill")) and not 0 <= sv["batch_fill"] <= 1:
        errors.append(f"{w}.batch_fill: must be a ratio in [0, 1]")
    if _is_num(sv.get("p50_ms")) and _is_num(sv.get("p99_ms")) \
            and sv["p99_ms"] < sv["p50_ms"]:
        errors.append(f"{w}: p99_ms below p50_ms")
    if "region" in sv:
        if not isinstance(sv["region"], dict):
            errors.append(f"{w}.region: must be an object")
        else:
            _check_fields(
                sv["region"],
                {"qps": _is_num, "requests": _is_int, "seconds": _is_num},
                f"{w}.region", errors, required=("qps", "seconds"),
            )
    if "regions" in sv and isinstance(sv["regions"], dict) \
            and "error" not in sv["regions"]:
        _check_regions(sv["regions"], w, errors)
    if "stats" in sv and isinstance(sv["stats"], dict) \
            and "error" not in sv["stats"]:
        _check_stats(sv["stats"], w, errors)
    if "open_loop" in sv:
        _check_open_loop(sv["open_loop"], w, errors)
    if "observability" in sv and isinstance(sv["observability"], dict) \
            and "error" not in sv["observability"]:
        _check_observability(sv["observability"], w, errors)
    if "slo" in sv and isinstance(sv["slo"], dict) \
            and "error" not in sv["slo"]:
        _check_slo(sv["slo"], w, errors)
    if "mixed_workload" in sv and isinstance(sv["mixed_workload"], dict) \
            and "error" not in sv["mixed_workload"]:
        _check_mixed_workload(sv["mixed_workload"], w, errors)
    if "chaos" in sv and isinstance(sv["chaos"], dict) \
            and "error" not in sv["chaos"]:
        _check_chaos(sv["chaos"], w, errors)
    if "replication" in sv and isinstance(sv["replication"], dict) \
            and "error" not in sv["replication"]:
        _check_replication(sv["replication"], w, errors)


def _check_mixed_workload(mx: dict, where: str, errors: list) -> None:
    """The live-write-path leg: open-loop point reads at a p99 SLO while
    a writer sustains WAL-durable upserts, with every acknowledged
    upsert read back afterwards (``acked_missing`` must be 0 — the zero
    acknowledged-write-loss contract)."""
    w = f"{where}.mixed_workload"
    _check_fields(
        mx,
        {"read_qps_target": _is_num, "upserts_per_sec_target": _is_num,
         "duration_s": _is_num, "slo_p99_ms": _is_num, "conns": _is_int,
         "read_slo_met": lambda v: isinstance(v, bool),
         "acked_verified": _is_int, "acked_missing": _is_int},
        w, errors,
        required=("read_qps_target", "upserts_per_sec_target",
                  "read", "upserts", "acked_missing"),
    )
    if _is_int(mx.get("acked_missing")) and mx["acked_missing"] != 0:
        errors.append(
            f"{w}.acked_missing: {mx['acked_missing']} acknowledged "
            "upsert(s) were lost — the ack contract is broken"
        )
    rd = mx.get("read")
    if rd is not None:
        if not isinstance(rd, dict):
            errors.append(f"{w}.read: must be an object")
        else:
            _check_fields(
                rd,
                {"offered_qps": _is_num, "achieved_qps": _is_num,
                 "p50_ms": _is_num, "p99_ms": _is_num, "errors": _is_int,
                 "transport_errors": _is_int, "requests": _is_int,
                 "seconds": _is_num},
                f"{w}.read", errors,
                required=("offered_qps", "achieved_qps", "p99_ms"),
            )
    up = mx.get("upserts")
    if up is not None:
        if not isinstance(up, dict):
            errors.append(f"{w}.upserts: must be an object")
        else:
            _check_fields(
                up,
                {"acked": _is_int, "errors": _is_int,
                 "achieved_per_sec": _is_num,
                 "ack_p50_ms": _is_num, "ack_p99_ms": _is_num},
                f"{w}.upserts", errors,
                required=("acked", "achieved_per_sec", "ack_p99_ms"),
            )
            if _is_num(up.get("ack_p50_ms")) \
                    and _is_num(up.get("ack_p99_ms")) \
                    and up["ack_p99_ms"] < up["ack_p50_ms"]:
                errors.append(f"{w}.upserts: ack_p99_ms below ack_p50_ms")


def _check_observability(ob: dict, where: str, errors: list) -> None:
    """The tracing-overhead gate: the open-loop headline re-run with the
    request-observability plane armed vs unarmed.  The overhead is
    REQUIRED at/below ``max_overhead`` (3%) on sustained QPS, and on p99
    either at/below the same ratio or under the recorded absolute noise
    floor (``p99_abs_floor_ms`` — on a 10-40ms baseline a 3% relative
    bound measures the container, not the code) — a record whose tracing
    costs more is a broken record, exactly like a lost acknowledged
    upsert."""
    _check_overhead_gate(ob, f"{where}.observability", errors, "tracing")


def _check_slo(ob: dict, where: str, errors: list) -> None:
    """The health-plane overhead gate: same armed/unarmed contract as
    the tracing gate (the metrics history ring + SLO burn evaluation at
    default cadence must also cost <= 3%), PLUS the ``alerts_sample``
    proof — the armed server's live ``/alerts`` body with at least one
    declared SLO row, so the record shows the plane was evaluating, not
    merely enabled."""
    w = f"{where}.slo"
    _check_overhead_gate(ob, w, errors, "health plane")
    sample = ob.get("alerts_sample")
    if sample is None:
        errors.append(f"{w}.alerts_sample: required (the armed /alerts "
                      "body proves the plane was live)")
        return
    if not isinstance(sample, dict):
        errors.append(f"{w}.alerts_sample: must be an object")
        return
    if sample.get("enabled") is not True:
        errors.append(f"{w}.alerts_sample.enabled: must be true — the "
                      "armed server's health plane was off")
    rows = sample.get("alerts")
    if not isinstance(rows, list) or not rows:
        errors.append(f"{w}.alerts_sample.alerts: at least one declared "
                      "SLO row required")
        return
    for i, row in enumerate(rows):
        if not isinstance(row, dict) or not row.get("slo") \
                or row.get("state") not in ("ok", "pending", "firing",
                                            "resolved"):
            errors.append(f"{w}.alerts_sample.alerts[{i}]: needs a slo "
                          "name and a valid state")


def _check_overhead_gate(ob: dict, w: str, errors: list,
                         plane: str) -> None:
    """The shared armed-vs-unarmed overhead record shape (tracing and
    health-plane gates emit the same block from the same bench
    machinery)."""
    _check_fields(
        ob,
        {
            "offered_qps": _is_num, "duration_s": _is_num,
            "conns": _is_int, "rounds": _is_int,
            "probe_achieved_qps": lambda v: v is None or _is_num(v),
            "overhead_qps": _is_num, "overhead_p99": _is_num,
            "overhead_p99_ms": _is_num, "p99_abs_floor_ms": _is_num,
            "max_overhead": _is_num,
            "within_bound": lambda v: isinstance(v, bool),
        },
        w, errors,
        required=("offered_qps", "armed", "unarmed", "overhead_qps",
                  "overhead_p99", "max_overhead", "within_bound"),
    )
    for side in ("armed", "unarmed"):
        sd = ob.get(side)
        if sd is None:
            continue
        if not isinstance(sd, dict):
            errors.append(f"{w}.{side}: must be an object")
            continue
        _check_fields(
            sd,
            {"achieved_qps": _is_num, "p99_ms": _is_num,
             "samples": lambda v: isinstance(v, list)},
            f"{w}.{side}", errors, required=("achieved_qps", "p99_ms"),
        )
    bound = ob.get("max_overhead")
    if _is_num(bound):
        if _is_num(ob.get("overhead_qps")) and ob["overhead_qps"] > bound:
            errors.append(
                f"{w}.overhead_qps: {ob['overhead_qps']} exceeds the "
                f"{bound} overhead bound — {plane} is too expensive"
            )
        floor = ob.get("p99_abs_floor_ms")
        if _is_num(ob.get("overhead_p99")) and ob["overhead_p99"] > bound \
                and not (_is_num(floor)
                         and _is_num(ob.get("overhead_p99_ms"))
                         and ob["overhead_p99_ms"] <= floor):
            errors.append(
                f"{w}.overhead_p99: {ob['overhead_p99']} exceeds the "
                f"{bound} bound and the absolute delta is over the "
                f"noise floor — {plane} is too expensive"
            )
    if ob.get("within_bound") is False:
        errors.append(
            f"{w}.within_bound: the {plane} failed its own overhead gate"
        )


def _check_chaos(ch: dict, where: str, errors: list) -> None:
    """The PR-7 chaos/soak certification block: fault schedule + error
    budgets + recovery evidence from ``tools/chaos_soak.py``."""
    w = f"{where}.chaos"
    _check_fields(
        ch,
        {
            "mode": lambda v: isinstance(v, str),
            "workers": _is_int, "duration_s": _is_num,
            "offered_qps": _is_num, "requests": _is_int, "ok": _is_int,
            "errors": _is_int, "hard_errors": _is_int, "shed": _is_int,
            "transport_errors": _is_int, "wrong_bytes": _is_int,
            "p99_ms": _is_num, "p99_budget_ms": _is_num,
            "error_rate": _is_num, "error_budget": _is_num,
            "transport_rate": _is_num, "transport_budget": _is_num,
            "faults": lambda v: isinstance(v, list)
            and all(isinstance(s, str) for s in v),
            "recovered": lambda v: isinstance(v, bool),
            "recovered_s": _is_num, "recovery_window_s": _is_num,
            "violations": lambda v: isinstance(v, list),
            "status_counts": lambda v: isinstance(v, dict)
            and all(_is_int(n) for n in v.values()),
        },
        w, errors,
        required=("requests", "wrong_bytes", "error_rate", "error_budget",
                  "recovered", "recovered_s", "faults"),
    )
    if _is_num(ch.get("error_rate")) and not 0 <= ch["error_rate"] <= 1:
        errors.append(f"{w}.error_rate: must be a ratio in [0, 1]")
    if _is_int(ch.get("wrong_bytes")) and ch["wrong_bytes"] < 0:
        errors.append(f"{w}.wrong_bytes: negative count")
    if "compact" in ch:
        # the compact-during-serve leg's summary (full schedule only)
        if not isinstance(ch["compact"], dict):
            errors.append(f"{w}.compact: must be an object")
        else:
            _check_fields(
                ch["compact"],
                {"status": lambda v: isinstance(v, str),
                 "files_before": _is_int, "files_after": _is_int,
                 "bytes_reclaimed": _is_int, "seconds": _is_num},
                f"{w}.compact", errors, required=("status",),
            )
    if "upserts" in ch:
        # the durable-writes-under-chaos leg (full schedule only):
        # acknowledged upserts verified readable after propagation
        if not isinstance(ch["upserts"], dict):
            errors.append(f"{w}.upserts: must be an object")
        else:
            _check_fields(
                ch["upserts"],
                {"acked": _is_int, "errors": _is_int, "missing": _is_int,
                 "verify_s": _is_num},
                f"{w}.upserts", errors, required=("acked", "missing"),
            )
            if _is_int(ch["upserts"].get("missing")) \
                    and ch["upserts"]["missing"] != 0:
                errors.append(
                    f"{w}.upserts.missing: acknowledged-write loss"
                )
    if "stats" in ch:
        # the analytics-under-chaos leg (full schedule only): panel
        # envelopes byte-verified — generation-scrubbed — through the
        # device-EIO burst and the worker SIGKILL
        if not isinstance(ch["stats"], dict):
            errors.append(f"{w}.stats: must be an object")
        else:
            _check_fields(
                ch["stats"],
                {"requests": _is_int, "ok": _is_int,
                 "wrong_bytes": _is_int, "transport_errors": _is_int},
                f"{w}.stats", errors, required=("requests", "wrong_bytes"),
            )
            if _is_int(ch["stats"].get("wrong_bytes")) \
                    and ch["stats"]["wrong_bytes"]:
                errors.append(
                    f"{w}.stats.wrong_bytes: analytics envelopes "
                    "diverged under chaos"
                )
    if "flight" in ch:
        # the crash-flight-recorder gates (full + soak schedules): a
        # harvested black box must exist after the kill/wedge legs,
        # parse, and hold the killed worker's final requests
        if not isinstance(ch["flight"], dict):
            errors.append(f"{w}.flight: must be an object")
        else:
            fl = ch["flight"]
            _check_fields(
                fl,
                {"harvested_files": _is_int, "parse_failures": _is_int,
                 "harvested_requests": _is_int, "breaker_events": _is_int,
                 "brownout_events": _is_int},
                f"{w}.flight", errors,
                required=("harvested_files", "harvested_requests"),
            )
            if _is_int(fl.get("harvested_files")) \
                    and fl["harvested_files"] < 1:
                errors.append(
                    f"{w}.flight.harvested_files: no black box was "
                    "harvested after the kill/wedge legs"
                )
            if _is_int(fl.get("parse_failures")) and fl["parse_failures"]:
                errors.append(
                    f"{w}.flight.parse_failures: harvested flight "
                    "file(s) failed to parse"
                )
    if "repl" in ch:
        # the replica-fleet leg (--repl): kill-the-leader failover —
        # acked_missing REQUIRED 0 and write availability REQUIRED
        # restored (the acked_missing precedent: a record showing
        # replication losing acknowledged writes is a broken build)
        if not isinstance(ch["repl"], dict):
            errors.append(f"{w}.repl: must be an object")
        else:
            _check_repl_block(ch["repl"], f"{w}.repl", errors)
    if "maintain" in ch:
        # the long-autonomy soak's daemon observables (--soak only):
        # daemon-driven passes, >= 1 brownout pause, and convergence
        # back to the low watermark are the certification
        if not isinstance(ch["maintain"], dict):
            errors.append(f"{w}.maintain: must be an object")
        else:
            mt = ch["maintain"]
            _check_fields(
                mt,
                {"high": _is_int, "low": _is_int, "passes": _is_int,
                 "paused": _is_int, "preempted": _is_int,
                 "read_amp_end": _is_int,
                 "converged": lambda v: isinstance(v, bool)},
                f"{w}.maintain", errors,
                required=("passes", "converged"),
            )
            if mt.get("converged") is False:
                errors.append(
                    f"{w}.maintain.converged: read-amp never returned "
                    "below the low watermark — autonomy is broken"
                )


def _check_repl_block(rp: dict, w: str, errors: list) -> None:
    """The shared replication-evidence shape: the ``repl`` sub-block of
    a ``--repl`` chaos record AND the ``serving.replication`` bench
    block validate against the same contract — ship throughput, the
    sampled lag distribution, failover-to-ready seconds, and the two
    hard verdicts (``acked_missing`` REQUIRED 0,
    ``post_promote_write_ok`` REQUIRED true when present)."""
    _check_fields(
        rp,
        {
            "max_lag_s": _is_num, "lag_p50_s": _is_num,
            "lag_p99_s": _is_num, "ship_bytes": _is_int,
            "ship_mb_per_s": _is_num, "records_applied": _is_int,
            "resyncs": _is_int,
            "stale_503_s": lambda v: v is None or _is_num(v),
            "failover_s": _is_num, "acked": _is_int,
            "acked_missing": _is_int,
            "promote_epoch": lambda v: v is None or _is_int(v),
            "promote_rows": lambda v: v is None or _is_int(v),
            "post_promote_write_ok": lambda v: isinstance(v, bool),
            "wrong_bytes": _is_int,
            "violations": lambda v: isinstance(v, list),
        },
        w, errors,
        required=("ship_mb_per_s", "lag_p50_s", "lag_p99_s",
                  "failover_s", "acked_missing"),
    )
    if _is_int(rp.get("acked_missing")) and rp["acked_missing"] != 0:
        errors.append(
            f"{w}.acked_missing: {rp['acked_missing']} acknowledged "
            "upsert(s) lost across the failover — the replication ack "
            "contract is broken"
        )
    if rp.get("post_promote_write_ok") is False:
        errors.append(
            f"{w}.post_promote_write_ok: the promoted leader never "
            "restored write availability"
        )
    if _is_num(rp.get("lag_p50_s")) and _is_num(rp.get("lag_p99_s")) \
            and rp["lag_p99_s"] < rp["lag_p50_s"]:
        errors.append(f"{w}: lag_p99_s below lag_p50_s")
    if _is_int(rp.get("wrong_bytes")) and rp["wrong_bytes"]:
        errors.append(
            f"{w}.wrong_bytes: follower reads diverged from the "
            "leader's bytes"
        )


def _check_replication(rp: dict, where: str, errors: list) -> None:
    """The ``serving.replication`` bench block: the ``--repl`` chaos
    leg's evidence reshaped for the bench record (``bench.py --serve``),
    same contract as the committed ``REPL_r*.json`` records."""
    _check_repl_block(rp, f"{where}.replication", errors)


def _check_compaction(cp: dict, where: str, errors: list) -> None:
    """The store-maintenance leg: a fragmented store compacted by a real
    `doctor compact` subprocess under live serve load, with a byte-identity
    verdict and read-amplification before/after."""
    w = f"{where}.compaction"
    _check_fields(
        cp,
        {
            "rows": _is_int, "rows_dropped": _is_int,
            "files_before": _is_int, "files_after": _is_int,
            "bytes_before": _is_int, "bytes_after": _is_int,
            "bytes_reclaimed": _is_int, "seconds": _is_num,
            "segments_per_sec": _is_num,
            "read_amp_before": _is_num, "read_amp_after": _is_num,
            "byte_identical": lambda v: isinstance(v, bool),
            "mismatches": _is_int,
            "serve": lambda v: isinstance(v, dict),
        },
        w, errors,
        required=("files_before", "files_after", "bytes_before",
                  "bytes_after", "seconds", "byte_identical"),
    )
    for key in ("files_before", "files_after", "bytes_before",
                "bytes_after"):
        if _is_int(cp.get(key)) and cp[key] < 0:
            errors.append(f"{w}.{key}: negative count")
    if _is_int(cp.get("files_before")) and _is_int(cp.get("files_after")) \
            and cp["files_after"] > cp["files_before"]:
        errors.append(f"{w}: files_after above files_before")
    if "serve" in cp and isinstance(cp["serve"], dict):
        _check_fields(
            cp["serve"],
            {"offered_qps": _is_num, "achieved_qps": _is_num,
             "p50_ms": _is_num, "p99_ms": _is_num, "errors": _is_int,
             "transport_errors": _is_int, "requests": _is_int},
            f"{w}.serve", errors, required=("p99_ms",),
        )
        if _is_num(cp["serve"].get("p50_ms")) \
                and _is_num(cp["serve"].get("p99_ms")) \
                and cp["serve"]["p99_ms"] < cp["serve"]["p50_ms"]:
            errors.append(f"{w}.serve: p99_ms below p50_ms")


def _check_autonomy(au: dict, where: str, errors: list) -> None:
    """The storage.autonomy leg: a maintenance daemon holds read-amp
    bounded against a live checkpoint writer and converges the store to
    <= the low watermark once the writer stops — ``converged`` is
    REQUIRED to be true (the acked_missing precedent: a record that
    shows autonomy failing is a broken build, not a data point)."""
    w = f"{where}.autonomy"
    _check_fields(
        au,
        {
            "high": _is_int, "low": _is_int,
            "segments_written": _is_int, "passes": _is_int,
            "preemptions": _is_int, "paused": _is_int,
            "read_amp_peak": _is_int, "read_amp_bound": _is_int,
            "read_amp_bounded": lambda v: isinstance(v, bool),
            "read_amp_end": _is_int, "seconds": _is_num,
            "read_amp_samples": lambda v: isinstance(v, list)
            and all(_is_int(x) for x in v),
            "converged": lambda v: isinstance(v, bool),
        },
        w, errors,
        required=("high", "low", "passes", "read_amp_peak",
                  "read_amp_end", "converged"),
    )
    if au.get("converged") is False:
        errors.append(
            f"{w}.converged: the daemon never converged read-amp back "
            "below the low watermark"
        )
    if au.get("read_amp_bounded") is False:
        errors.append(
            f"{w}.read_amp_bounded: read amplification escaped its "
            "declared transient ceiling"
        )
    if _is_int(au.get("passes")) and au["passes"] < 1:
        errors.append(
            f"{w}.passes: no daemon compaction pass ran — the leg "
            "proves nothing"
        )
    if _is_int(au.get("read_amp_end")) and _is_int(au.get("low")) \
            and au["read_amp_end"] > au["low"]:
        errors.append(
            f"{w}.read_amp_end: {au['read_amp_end']} above the low "
            f"watermark {au['low']}"
        )


def _check_storage(st, where: str, errors: list) -> None:
    """The storage-management block (``storage.autonomy``)."""
    if not isinstance(st, dict):
        errors.append(f"{where}: storage must be an object")
        return
    w = f"{where}.storage"
    if "autonomy" in st and isinstance(st["autonomy"], dict) \
            and "error" not in st["autonomy"]:
        _check_autonomy(st["autonomy"], w, errors)


def _check_multichip(mc, where: str, errors: list) -> None:
    """The mesh scaling-curve block (``bench.py --multichip``): per-
    device-count throughput + parallel efficiency for the annotate
    pipeline and the sharded bulk lookup, with ``byte_identical``
    REQUIRED true at EVERY device count — a curve whose sharded answers
    drift from the single-device bytes is a broken build, not a data
    point (the acked_missing precedent)."""
    w = f"{where}.multichip"
    if not isinstance(mc, dict):
        errors.append(f"{w}: must be an object")
        return
    if "skipped" in mc:
        if not isinstance(mc["skipped"], str):
            errors.append(f"{w}.skipped: must be a string reason")
        return
    _check_fields(
        mc,
        {
            "devices": lambda v: isinstance(v, list) and len(v) > 0
            and all(_is_int(d) and d >= 1 for d in v),
            "cores": _is_int,
            "label": lambda v: isinstance(v, str),
        },
        w, errors, required=("devices", "cores", "label", "annotate",
                             "bulk_lookup"),
    )
    for leg, rate_key in (("annotate", "rows_per_sec"),
                          ("bulk_lookup", "lookups_per_sec")):
        sub = mc.get(leg)
        if not isinstance(sub, dict):
            if leg in mc:
                errors.append(f"{w}.{leg}: must be an object")
            continue
        lw = f"{w}.{leg}"
        _check_fields(
            sub,
            {"speedup_at_max": _is_num,
             "per_device": lambda v: isinstance(v, list) and len(v) > 0},
            lw, errors, required=("per_device", "speedup_at_max"),
        )
        for i, entry in enumerate(sub.get("per_device") or []):
            ew = f"{lw}.per_device[{i}]"
            if not isinstance(entry, dict):
                errors.append(f"{ew}: must be an object")
                continue
            _check_fields(
                entry,
                {"devices": _is_int, rate_key: _is_num,
                 "seconds": _is_num, "speedup": _is_num,
                 "efficiency": _is_num,
                 "byte_identical": lambda v: isinstance(v, bool)},
                ew, errors,
                required=("devices", rate_key, "speedup",
                          "byte_identical"),
            )
            if entry.get("byte_identical") is False:
                errors.append(
                    f"{ew}.byte_identical: the mesh path diverged from "
                    "the single-device bytes — wrong answers are never a "
                    "scaling data point"
                )


def _check_multichip_dryrun(obj: dict, name: str) -> list[str]:
    """Historic MULTICHIP_r01–r05 records: the dryrun driver wrapper
    (``{"n_devices", "rc", "ok", "skipped", "tail"}``) stays loadable."""
    errors: list[str] = []
    _check_fields(
        obj,
        {"n_devices": _is_int, "rc": _is_int,
         "ok": lambda v: isinstance(v, bool),
         "skipped": lambda v: isinstance(v, bool),
         "tail": lambda v: isinstance(v, str)},
        name, errors, required=("n_devices", "rc", "ok"),
    )
    return errors


def _check_regions(rg: dict, where: str, errors: list) -> None:
    """The PR-8 batch-region-join leg: a ≥2k-interval panel answered
    device-batched (``POST /regions``) vs the sequential single-region
    baseline, with a byte-identity verdict."""
    w = f"{where}.regions"
    _check_fields(
        rg,
        {
            "intervals": _is_int, "window_bp": _is_int, "limit": _is_int,
            "batch_size": _is_int, "mismatches": _is_int,
            "byte_identical": lambda v: isinstance(v, bool),
            "speedup": _is_num,
            "sequential": lambda v: isinstance(v, dict),
            "batched": lambda v: isinstance(v, dict),
            "count_only": lambda v: isinstance(v, dict),
        },
        w, errors,
        required=("intervals", "sequential", "batched", "speedup",
                  "byte_identical"),
    )
    for leg in ("sequential", "batched", "count_only"):
        sub = rg.get(leg)
        if not isinstance(sub, dict):
            continue
        _check_fields(
            sub,
            {"intervals_per_sec": _is_num, "seconds": _is_num,
             "p50_ms": _is_num, "p99_ms": _is_num, "calls": _is_int,
             "speedup": _is_num},
            f"{w}.{leg}", errors,
            required=("intervals_per_sec", "seconds"),
        )
        if _is_num(sub.get("p50_ms")) and _is_num(sub.get("p99_ms")) \
                and sub["p99_ms"] < sub["p50_ms"]:
            errors.append(f"{w}.{leg}: p99_ms below p50_ms")
    if _is_int(rg.get("intervals")) and rg["intervals"] <= 0:
        errors.append(f"{w}.intervals: must be positive")


def _check_stats(sg: dict, where: str, errors: list) -> None:
    """The on-device analytics leg: a panel summarized batched
    (``POST /stats/region``) vs the sequential per-row host scan, with a
    byte-identity verdict that is REQUIRED true (the summaries are
    deterministic integer aggregations — a mismatch is wrong answers,
    not noise; the ``acked_missing`` precedent) and a point-read p99
    parity probe bracketing the legs."""
    w = f"{where}.stats"
    _check_fields(
        sg,
        {
            "intervals": _is_int, "window_bp": _is_int,
            "batch_size": _is_int, "store_rows": _is_int,
            "mismatches": _is_int,
            "byte_identical": lambda v: isinstance(v, bool),
            "speedup": _is_num,
            "sequential": lambda v: isinstance(v, dict),
            "batched": lambda v: isinstance(v, dict),
            "point_read": lambda v: isinstance(v, dict),
        },
        w, errors,
        required=("intervals", "sequential", "batched", "speedup",
                  "byte_identical"),
    )
    if sg.get("byte_identical") is False:
        errors.append(
            f"{w}.byte_identical: batched stats diverged from the "
            "sequential host-scan reference — wrong answers, not noise"
        )
    for leg in ("sequential", "batched"):
        sub = sg.get(leg)
        if not isinstance(sub, dict):
            continue
        _check_fields(
            sub,
            {"intervals_per_sec": _is_num, "seconds": _is_num,
             "p50_ms": _is_num, "p99_ms": _is_num, "calls": _is_int},
            f"{w}.{leg}", errors,
            required=("intervals_per_sec", "seconds"),
        )
        if _is_num(sub.get("p50_ms")) and _is_num(sub.get("p99_ms")) \
                and sub["p99_ms"] < sub["p50_ms"]:
            errors.append(f"{w}.{leg}: p99_ms below p50_ms")
    if _is_int(sg.get("intervals")) and sg["intervals"] <= 0:
        errors.append(f"{w}.intervals: must be positive")
    pr = sg.get("point_read")
    if isinstance(pr, dict):
        _check_fields(
            pr,
            {"p99_ms_before": _is_num, "p99_ms_after": _is_num,
             "ratio": _is_num,
             "parity_ok": lambda v: isinstance(v, bool)},
            f"{w}.point_read", errors,
            required=("p99_ms_before", "p99_ms_after", "parity_ok"),
        )


def _check_open_loop(ol, where: str, errors: list) -> None:
    """The PR-6 open-loop sweep: per-fleet stepped offered load with a
    p99 SLO and the max sustainable QPS each fleet size delivered."""
    w = f"{where}.open_loop"
    if not isinstance(ol, dict):
        errors.append(f"{w}: must be an object")
        return
    _check_fields(
        ol,
        {"slo_p99_ms": _is_num, "conns": _is_int, "duration_s": _is_num,
         "max_sustainable_qps": _is_num,
         "fleets": lambda v: isinstance(v, list) and len(v) > 0},
        w, errors,
        required=("slo_p99_ms", "max_sustainable_qps", "fleets"),
    )
    if not isinstance(ol.get("fleets"), list):
        return
    for i, fleet in enumerate(ol["fleets"]):
        fw = f"{w}.fleets[{i}]"
        if not isinstance(fleet, dict):
            errors.append(f"{fw}: must be an object")
            continue
        _check_fields(
            fleet,
            {"workers": _is_int, "max_sustainable_qps": _is_num,
             "steps": lambda v: isinstance(v, list)},
            fw, errors, required=("workers", "max_sustainable_qps"),
        )
        for j, step in enumerate(fleet.get("steps") or []):
            sw = f"{fw}.steps[{j}]"
            if not isinstance(step, dict):
                errors.append(f"{sw}: must be an object")
                continue
            _check_fields(
                step,
                {"offered_qps": _is_num, "achieved_qps": _is_num,
                 "p50_ms": _is_num, "p99_ms": _is_num, "errors": _is_int,
                 "transport_errors": _is_int,
                 "status_counts": lambda v: isinstance(v, dict)
                 and all(_is_int(n) for n in v.values()),
                 "requests": _is_int, "seconds": _is_num},
                sw, errors,
                required=("offered_qps", "achieved_qps", "p99_ms"),
            )
            if _is_num(step.get("p50_ms")) and _is_num(step.get("p99_ms")) \
                    and step["p99_ms"] < step["p50_ms"]:
                errors.append(f"{sw}: p99_ms below p50_ms")


def _check_export(ex, where: str, errors: list) -> None:
    """The ``export`` block of a ``mode: "export"`` record: the one-shot
    throughput leg plus the determinism battery — every byte-compare
    flag must be literally ``true`` (an export bench whose corpus is not
    reproducible is a failed record, not a slow one)."""
    ew = f"{where}.export"
    if not isinstance(ex, dict):
        errors.append(f"{ew}: must be an object")
        return
    _check_fields(
        ex,
        {"rows": _is_int, "seed": _is_int, "batch_rows": _is_int,
         "one_shot": lambda v: isinstance(v, dict),
         "replay_identical": lambda v: v is True,
         "host_twin_identical": lambda v: v is True,
         "resume": lambda v: isinstance(v, dict)},
        ew, errors,
        required=("rows", "seed", "batch_rows", "one_shot",
                  "replay_identical", "host_twin_identical", "resume"),
    )
    one = ex.get("one_shot")
    if isinstance(one, dict):
        _check_fields(
            one,
            {"tokens_per_sec": _is_num, "device_idle_frac": _is_num,
             "rows": _is_int, "tokens": _is_int, "parts": _is_int,
             "seconds": _is_num,
             "complete": lambda v: isinstance(v, bool)},
            f"{ew}.one_shot", errors,
            required=("tokens_per_sec", "device_idle_frac", "rows",
                      "tokens", "parts", "seconds", "complete"),
        )
        if _is_num(one.get("device_idle_frac")) \
                and not 0 <= one["device_idle_frac"] <= 1:
            errors.append(f"{ew}.one_shot: device_idle_frac out of [0, 1]")
    res = ex.get("resume")
    if isinstance(res, dict) and "error" not in res:
        _check_fields(
            res,
            {"killed_rc": _is_int, "resume_rc": lambda v: v == 0,
             "identical": lambda v: v is True},
            f"{ew}.resume", errors,
            required=("killed_rc", "resume_rc", "identical"),
        )
        if _is_int(res.get("killed_rc")) and res["killed_rc"] == 0:
            errors.append(f"{ew}.resume: killed_rc is 0 — the injected "
                          "SIGKILL never landed")


def validate_record(rec: dict, where: str = "record") -> list[str]:
    """Validate one RAW bench record; returns a list of error strings."""
    errors: list[str] = []
    if not isinstance(rec, dict):
        return [f"{where}: not a JSON object"]
    if rec.get("mode") == "tpu-only":
        # --tpu-only records: the kernel + end-to-end legs on an
        # accelerator (historic ones may carry probe evidence only)
        _check_fields(
            rec, {"platform_pin": lambda v: isinstance(v, str)},
            where, errors, required=("platform_pin",),
        )
    elif rec.get("mode") == "export":
        # --export corpus records: the EXPORT block is the payload
        _check_fields(
            rec,
            {"metric": lambda v: v == "export_tokens_per_sec",
             "value": _is_num,
             "unit": lambda v: v == "tokens/sec",
             "vs_baseline": _is_num,
             "backend": lambda v: isinstance(v, str)},
            where, errors,
            required=("metric", "value", "unit", "vs_baseline", "backend"),
        )
        if "error" not in rec:
            if "export" not in rec:
                errors.append(f"{where}: export record carries no "
                              "export block")
            else:
                _check_export(rec["export"], where, errors)
        return errors
    elif rec.get("mode") == "multichip":
        # --multichip scaling records: the MULTICHIP block is the payload
        _check_fields(
            rec,
            {"metric": lambda v: isinstance(v, str), "value": _is_num,
             "vs_baseline": _is_num,
             "backend": lambda v: isinstance(v, str)},
            where, errors,
            required=("metric", "value", "vs_baseline", "backend"),
        )
        if "error" not in rec:
            if "multichip" not in rec:
                errors.append(f"{where}: multichip record carries no "
                              "multichip block")
            else:
                _check_multichip(rec["multichip"], where, errors)
        return errors
    else:
        _check_fields(
            rec,
            {
                "metric": lambda v: isinstance(v, str),
                "value": _is_num,
                "unit": lambda v: isinstance(v, str),
                "vs_baseline": _is_num,
                "kernel_variants_per_sec": _is_num,
                "kernel_vs_target": _is_num,
                "kernel": lambda v: isinstance(v, str),
                "backend": lambda v: isinstance(v, str),
            },
            where, errors,
            required=("metric", "value", "unit", "vs_baseline", "backend"),
        )
    if "end_to_end" in rec:
        _check_end_to_end(rec["end_to_end"], where, errors)
    if "cadd_join" in rec and isinstance(rec["cadd_join"], dict) \
            and "error" not in rec["cadd_join"]:
        _check_fields(
            rec["cadd_join"],
            {"table_rows_per_sec": _is_num, "matched": _is_int,
             "seconds": _is_num},
            f"{where}.cadd_join", errors,
            required=("table_rows_per_sec", "seconds"),
        )
    if "qc_update" in rec and isinstance(rec["qc_update"], dict) \
            and "error" not in rec["qc_update"]:
        _check_fields(
            rec["qc_update"],
            {"rows_per_sec": _is_num, "updated": _is_int, "seconds": _is_num},
            f"{where}.qc_update", errors,
            required=("rows_per_sec", "seconds"),
        )
    if "multichip" in rec and isinstance(rec["multichip"], dict) \
            and "error" not in rec["multichip"]:
        _check_multichip(rec["multichip"], where, errors)
    if "serving" in rec and isinstance(rec["serving"], dict) \
            and "error" not in rec["serving"]:
        _check_serving(rec["serving"], where, errors)
    if "compaction" in rec and isinstance(rec["compaction"], dict) \
            and "error" not in rec["compaction"]:
        _check_compaction(rec["compaction"], where, errors)
    if "storage" in rec:
        _check_storage(rec["storage"], where, errors)
    return errors


def validate_file(path: str) -> list[str]:
    """Validate one BENCH file (raw record or driver wrapper)."""
    name = os.path.basename(path)
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as err:
        return [f"{name}: unreadable ({err})"]
    if not isinstance(obj, dict):
        return [f"{name}: not a JSON object"]
    if "n_devices" in obj and "parsed" not in obj:
        # historic MULTICHIP_r01–r05 dryrun wrappers
        return _check_multichip_dryrun(obj, name)
    if obj.get("mode") == "repl" and "repl" in obj:
        # committed REPL_r*.json: the raw --repl chaos record from
        # tools/chaos_soak.py (the kill-the-leader certification)
        errors: list[str] = []
        _check_chaos(obj, name, errors)
        if obj.get("recovered") is not True:
            errors.append(f"{name}: recovered must be true — the "
                          "failover never completed")
        if obj.get("violations"):
            errors.append(f"{name}: committed repl record carries "
                          f"violations: {obj['violations']}")
        return errors
    if "parsed" in obj or "rc" in obj:  # driver wrapper
        errors: list[str] = []
        if obj.get("rc") == 0 and not isinstance(obj.get("parsed"), dict):
            errors.append(
                f"{name}: rc=0 but no parsed record (bench printed no JSON?)"
            )
        if isinstance(obj.get("parsed"), dict):
            errors.extend(validate_record(obj["parsed"], name))
        return errors
    return validate_record(obj, name)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = argv or sorted(
        glob.glob(os.path.join(root, "BENCH_*.json"))
        + glob.glob(os.path.join(root, "MULTICHIP_*.json"))
        + glob.glob(os.path.join(root, "REPL_*.json"))
    )
    if not paths:
        print("no BENCH_*.json files found", file=sys.stderr)
        return 1
    n_errors = 0
    for path in paths:
        errors = validate_file(path)
        if errors:
            n_errors += len(errors)
            for e in errors:
                print(f"FAIL {e}", file=sys.stderr)
        else:
            print(f"ok   {os.path.basename(path)}")
    return 1 if n_errors else 0


if __name__ == "__main__":
    sys.exit(main())
