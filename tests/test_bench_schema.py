"""Bench-record schema gate: every committed ``BENCH_*.json`` must validate
against the documented schema (README "Bench JSON schema"), and the checker
itself must catch the drift classes it exists for."""

import copy
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

from check_bench_schema import validate_file, validate_record  # noqa: E402

GOOD = {
    "metric": "end_to_end_vcf_to_store_variants_per_sec",
    "value": 1000000.0,
    "unit": "variants/sec",
    "vs_baseline": 6.7,
    "kernel_variants_per_sec": 4.5e6,
    "kernel_vs_target": 4.5,
    "kernel": "jnp",
    "backend": "cpu",
    "end_to_end": {
        "variants_per_sec": 1000000.0,
        "variants": 2092068,
        "duplicates": 5084,
        "seconds": 2.1,
        "vcf_mb": 67.3,
        "mb_per_sec": 32.0,
        "pipeline": "overlapped",
        "stages": {
            "ingest": {"seconds": 0.9, "items": 0},
            "annotate": {"seconds": 0.01, "items": 2092068},
        },
        "stage_wall": {
            "wall_seconds": 2.1, "busy_seconds": 3.2, "overlap": 1.52,
        },
        "queue_stalls": {
            "ingest": {"items": 16, "producer_block_s": 0.4,
                       "consumer_wait_s": 0.1, "max_depth": 2},
            "store-writer": {"items": 16, "producer_block_s": 0.0,
                             "consumer_wait_s": 0.0, "max_depth": 1},
        },
        "vep_update": {
            "results_per_sec": 200000.0, "updated": 200000,
            "seconds": 1.0, "runs": [199000.0, 200000.0, 201000.0],
        },
    },
    "cadd_join": {"table_rows_per_sec": 2.0e6, "matched": 49778,
                  "variants": 100000, "seconds": 0.43},
    "qc_update": {"rows_per_sec": 120000.0, "updated": 100000,
                  "seconds": 0.82},
    "serving": {
        "qps": 3200.0, "p50_ms": 4.9, "p99_ms": 6.3, "requests": 4000,
        "clients": 16, "errors": 0, "batch_fill": 0.06, "batches": 250,
        "seconds": 1.2, "store_rows": 50000,
        "region": {"qps": 110.0, "requests": 200, "seconds": 1.8},
        "regions": {
            "intervals": 2048, "window_bp": 30, "limit": 10,
            "batch_size": 256, "byte_identical": True, "mismatches": 0,
            "sequential": {"intervals_per_sec": 850.0, "p50_ms": 1.1,
                           "p99_ms": 3.2, "seconds": 2.41},
            "batched": {"intervals_per_sec": 7400.0, "calls": 8,
                        "p50_ms": 33.0, "p99_ms": 41.0, "seconds": 0.28},
            "speedup": 8.7,
            "count_only": {"intervals_per_sec": 52000.0, "seconds": 0.04,
                           "speedup": 61.2},
        },
        "stats": {
            "intervals": 1024, "window_bp": 4000, "batch_size": 256,
            "store_rows": 60000, "byte_identical": True, "mismatches": 0,
            "sequential": {"intervals_per_sec": 133.1, "p50_ms": 6.4,
                           "p99_ms": 20.5, "seconds": 7.69},
            "batched": {"intervals_per_sec": 2204.3, "calls": 4,
                        "p50_ms": 106.2, "p99_ms": 132.2,
                        "seconds": 0.47},
            "speedup": 16.56,
            "point_read": {"p99_ms_before": 19.8, "p99_ms_after": 16.0,
                           "ratio": 0.81, "parity_ok": True},
        },
        "open_loop": {
            "slo_p99_ms": 25.0, "conns": 8, "duration_s": 2.5,
            "max_sustainable_qps": 11800.0,
            "fleets": [
                {"workers": 1, "max_sustainable_qps": 9900.0,
                 "steps": [
                     {"offered_qps": 8000.0, "achieved_qps": 7950.0,
                      "p50_ms": 12.0, "p99_ms": 21.5, "errors": 0,
                      "transport_errors": 0,
                      "status_counts": {"200": 19875, "429": 125},
                      "requests": 20000, "seconds": 2.5},
                 ]},
                {"workers": 2, "max_sustainable_qps": 11800.0,
                 "steps": [
                     {"offered_qps": 12000.0, "achieved_qps": 11800.0,
                      "p50_ms": 14.0, "p99_ms": 24.0, "errors": 0,
                      "requests": 30000, "seconds": 2.5},
                 ]},
            ],
        },
        "observability": {
            "offered_qps": 3600.0, "probe_achieved_qps": 7980.0,
            "duration_s": 2.5, "conns": 8, "rounds": 5,
            "armed": {"achieved_qps": 3590.0, "p99_ms": 12.4,
                      "samples": [{"achieved_qps": 3591.0,
                                   "p99_ms": 12.9}]},
            "unarmed": {"achieved_qps": 3594.0, "p99_ms": 12.2,
                        "samples": [{"achieved_qps": 3596.0,
                                     "p99_ms": 12.0}]},
            "overhead_qps": 0.0011, "overhead_p99": 0.0164,
            "overhead_p99_ms": 0.2, "p99_abs_floor_ms": 2.0,
            "max_overhead": 0.03, "within_bound": True,
        },
        "slo": {
            "offered_qps": 3600.0, "probe_achieved_qps": 7973.0,
            "duration_s": 2.5, "conns": 8, "rounds": 5,
            "armed": {"achieved_qps": 3582.0, "p99_ms": 9.3,
                      "samples": [{"achieved_qps": 3582.0,
                                   "p99_ms": 9.3}]},
            "unarmed": {"achieved_qps": 3589.0, "p99_ms": 7.9,
                        "samples": [{"achieved_qps": 3589.0,
                                     "p99_ms": 7.9}]},
            "overhead_qps": 0.0019, "overhead_p99": 0.0182,
            "overhead_p99_ms": 1.46, "p99_abs_floor_ms": 2.0,
            "max_overhead": 0.03, "within_bound": True,
            "alerts_sample": {
                "enabled": True, "worker": 0, "state": "ok",
                "firing": 0, "burn_threshold": 2.0,
                "windows": {"fast_s": 60.0, "slow_s": 300.0},
                "alerts": [
                    {"slo": "availability", "kind": "availability",
                     "state": "ok", "burn_fast": 0.0, "burn_slow": 0.0,
                     "threshold": 2.0, "since": None, "fired_total": 0,
                     "target": 0.999},
                    {"slo": "point_read_p99", "kind": "latency",
                     "state": "ok", "burn_fast": 0.0, "burn_slow": 0.0,
                     "threshold": 2.0, "since": None, "fired_total": 0,
                     "target_ms": 250.0, "objective": 0.99},
                ],
            },
        },
        "mixed_workload": {
            "read_qps_target": 2000.0, "upserts_per_sec_target": 150.0,
            "duration_s": 6.0, "slo_p99_ms": 25.0, "conns": 8,
            "read": {"offered_qps": 2000.0, "achieved_qps": 1988.0,
                     "p50_ms": 8.2, "p99_ms": 19.4, "errors": 0,
                     "transport_errors": 0,
                     "status_counts": {"200": 11928},
                     "requests": 11928, "seconds": 6.0},
            "read_slo_met": True,
            "upserts": {"acked": 894, "errors": 0,
                        "achieved_per_sec": 148.8,
                        "ack_p50_ms": 2.4, "ack_p99_ms": 9.7},
            "acked_verified": 894, "acked_missing": 0,
        },
        "chaos": {
            "mode": "full", "workers": 2, "duration_s": 40.0,
            "offered_qps": 600.0, "requests": 24734, "ok": 23359,
            "errors": 0, "hard_errors": 0, "shed": 12,
            "transport_errors": 1375,
            "status_counts": {"200": 23359, "503": 12},
            "wrong_bytes": 0, "p99_ms": 813.5, "p99_budget_ms": 2500.0,
            "error_rate": 0.0, "error_budget": 0.05,
            "transport_rate": 0.056, "transport_budget": 0.25,
            "faults": ["serve.batch:prob:0.2:delay:20",
                       "serve.wedge:1:delay:30000"],
            "breaker_trips": 1,
            "recovered": True, "recovered_s": 19.1,
            "recovery_window_s": 30.0, "violations": [],
            "compact": {"status": "compacted", "files_before": 2,
                        "files_after": 1, "bytes_reclaimed": 120034,
                        "seconds": 0.8},
            "upserts": {"acked": 360, "errors": 2, "missing": 0,
                        "verify_s": 3.1},
            "maintain": {"high": 3, "low": 2, "passes": 20, "paused": 2,
                         "preempted": 1, "read_amp_end": 1,
                         "converged": True},
            "flight": {"harvested_files": 2, "parse_failures": 0,
                       "harvested_requests": 57, "breaker_events": 3,
                       "brownout_events": 4},
        },
        "replication": {
            "max_lag_s": 3.0, "lag_p50_s": 0.0, "lag_p99_s": 0.16,
            "ship_bytes": 104013, "ship_mb_per_s": 0.008,
            "records_applied": 379, "resyncs": 1, "stale_503_s": 3.03,
            "failover_s": 1.64, "acked": 380, "acked_missing": 0,
            "promote_epoch": 1, "promote_rows": 138,
            "post_promote_write_ok": True, "wrong_bytes": 0,
            "violations": [],
        },
    },
    "storage": {
        "autonomy": {
            "high": 3, "low": 2, "segments_written": 12, "passes": 5,
            "preemptions": 0, "paused": 0, "read_amp_peak": 3,
            "read_amp_bound": 6, "read_amp_bounded": True,
            "read_amp_end": 2, "read_amp_samples": [2, 3, 2, 3, 2],
            "converged": True, "seconds": 8.4,
        },
    },
    "compaction": {
        "rows": 40000, "rows_dropped": 0,
        "files_before": 12, "files_after": 2,
        "bytes_before": 2804211, "bytes_after": 1517804,
        "bytes_reclaimed": 2804211, "seconds": 1.92,
        "segments_per_sec": 6.25,
        "read_amp_before": 6.0, "read_amp_after": 1.0,
        "byte_identical": True, "mismatches": 0,
        "serve": {"offered_qps": 400.0, "achieved_qps": 396.0,
                  "p50_ms": 6.1, "p99_ms": 38.0, "errors": 0,
                  "transport_errors": 0, "requests": 3200},
    },
}


def test_committed_bench_records_validate():
    paths = sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json")))
    assert paths, "no committed BENCH records found"
    for path in paths:
        errors = validate_file(path)
        assert not errors, f"{os.path.basename(path)}: {errors}"


def test_good_record_passes_including_new_blocks():
    assert validate_record(GOOD) == []


def test_missing_core_field_fails():
    bad = copy.deepcopy(GOOD)
    del bad["value"]
    errors = validate_record(bad)
    assert any("value" in e for e in errors)


def test_bad_stage_shape_fails():
    bad = copy.deepcopy(GOOD)
    bad["end_to_end"]["stages"]["ingest"] = {"items": 0}  # no seconds
    errors = validate_record(bad)
    assert any("ingest" in e and "seconds" in e for e in errors)


def test_serving_block_is_validated_strictly():
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["p99_ms"]
    assert any("p99_ms" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["batch_fill"] = 1.5  # a ratio, not a count
    assert any("batch_fill" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["p99_ms"] = 1.0  # below p50: impossible percentiles
    assert any("p99_ms below p50_ms" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["region"] = {"requests": 200}  # qps/seconds required
    assert any("region" in e for e in validate_record(bad))


def test_regions_block_is_validated_strictly():
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["regions"]["speedup"]
    assert any("speedup" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    del bad["serving"]["regions"]["batched"]["intervals_per_sec"]
    assert any("intervals_per_sec" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["regions"]["byte_identical"] = "yes"  # bool, not str
    assert any("byte_identical" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["regions"]["sequential"]["p99_ms"] = 0.5  # below p50
    assert any("p99_ms below p50_ms" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["regions"]["intervals"] = 0
    assert any("positive" in e for e in validate_record(bad))

    # a serving block WITHOUT regions stays valid (r05-r07-era records)
    old = copy.deepcopy(GOOD)
    del old["serving"]["regions"]
    assert validate_record(old) == []

    # a failed leg records its error and stays loadable
    failed = copy.deepcopy(GOOD)
    failed["serving"]["regions"] = {"error": "server did not start"}
    assert validate_record(failed) == []


def test_stats_block_is_validated_strictly():
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["stats"]["speedup"]
    assert any("speedup" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    del bad["serving"]["stats"]["batched"]["intervals_per_sec"]
    assert any("intervals_per_sec" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["stats"]["byte_identical"] = "yes"  # bool, not str
    assert any("byte_identical" in e for e in validate_record(bad))

    # byte identity is a correctness contract, REQUIRED true: summaries
    # are deterministic integer aggregations, a divergence is wrong
    # answers (the acked_missing precedent), never measurement noise
    bad = copy.deepcopy(GOOD)
    bad["serving"]["stats"]["byte_identical"] = False
    assert any("wrong answers" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["stats"]["sequential"]["p99_ms"] = 0.5  # below p50
    assert any("p99_ms below p50_ms" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["stats"]["intervals"] = 0
    assert any("positive" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    del bad["serving"]["stats"]["point_read"]["parity_ok"]
    assert any("parity_ok" in e for e in validate_record(bad))

    # a serving block WITHOUT stats stays valid (r01-r10-era records)
    old = copy.deepcopy(GOOD)
    del old["serving"]["stats"]
    assert validate_record(old) == []

    # a failed leg records its error and stays loadable
    failed = copy.deepcopy(GOOD)
    failed["serving"]["stats"] = {"error": "server did not start"}
    assert validate_record(failed) == []


def test_open_loop_block_is_validated_strictly():
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["open_loop"]["max_sustainable_qps"]
    assert any("max_sustainable_qps" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["open_loop"]["fleets"] = []  # at least one fleet size
    assert any("fleets" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["open_loop"]["fleets"][0]["workers"]
    assert any("workers" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    step = bad["serving"]["open_loop"]["fleets"][0]["steps"][0]
    del step["achieved_qps"]
    assert any("achieved_qps" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    step = bad["serving"]["open_loop"]["fleets"][1]["steps"][0]
    step["p99_ms"] = 1.0  # below p50: impossible percentiles
    assert any("p99_ms below p50_ms" in e for e in validate_record(bad))
    # a serving block WITHOUT open_loop stays valid (r05-era records)
    old = copy.deepcopy(GOOD)
    del old["serving"]["open_loop"]
    assert validate_record(old) == []


def test_chaos_block_is_validated_strictly():
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["chaos"]["wrong_bytes"]
    assert any("wrong_bytes" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["chaos"]["recovered"]
    assert any("recovered" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["error_rate"] = 1.7  # a ratio, not a count
    assert any("error_rate" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["faults"] = "serve.wedge"  # a list of specs
    assert any("faults" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["recovered"] = "yes"  # bool, not string
    assert any("recovered" in e for e in validate_record(bad))
    # a serving block WITHOUT chaos stays valid (r05/r06-era records)
    old = copy.deepcopy(GOOD)
    del old["serving"]["chaos"]
    assert validate_record(old) == []
    # a failed chaos leg records {"error": ...} and stays loadable
    failed = copy.deepcopy(GOOD)
    failed["serving"]["chaos"] = {"error": "chaos soak timed out"}
    assert validate_record(failed) == []


def test_compaction_block_is_validated_strictly():
    bad = copy.deepcopy(GOOD)
    del bad["compaction"]["byte_identical"]
    assert any("byte_identical" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    del bad["compaction"]["files_after"]
    assert any("files_after" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["compaction"]["byte_identical"] = "yes"  # bool, not string
    assert any("byte_identical" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["compaction"]["files_after"] = 99  # compaction cannot grow files
    assert any("files_after above files_before" in e
               for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["compaction"]["bytes_before"] = -1
    assert any("bytes_before" in e and "negative" in e
               for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["compaction"]["serve"]["p99_ms"] = 1.0  # below p50: impossible
    assert any("p99_ms below p50_ms" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    del bad["compaction"]["serve"]["p99_ms"]
    assert any("serve" in e and "p99_ms" in e for e in validate_record(bad))
    # a record WITHOUT the block stays valid (pre-r09 records)
    old = copy.deepcopy(GOOD)
    del old["compaction"]
    assert validate_record(old) == []
    # a failed leg records {"error": ...} and stays loadable
    failed = copy.deepcopy(GOOD)
    failed["compaction"] = {"error": "doctor compact rc=2"}
    assert validate_record(failed) == []
    # the chaos sub-block: compact summary validated when present
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["compact"] = {"files_before": 2}  # no status
    assert any("compact" in e and "status" in e
               for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["compact"]["seconds"] = "fast"
    assert any("compact" in e and "seconds" in e
               for e in validate_record(bad))


def test_open_loop_step_transport_errors_validated():
    bad = copy.deepcopy(GOOD)
    step = bad["serving"]["open_loop"]["fleets"][0]["steps"][0]
    step["transport_errors"] = 1.5  # a count, not a ratio
    assert any("transport_errors" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    step = bad["serving"]["open_loop"]["fleets"][0]["steps"][0]
    step["status_counts"] = {"200": "many"}  # counts are integers
    assert any("status_counts" in e for e in validate_record(bad))


def test_queue_stalls_block_is_validated_strictly():
    bad = copy.deepcopy(GOOD)
    del bad["end_to_end"]["queue_stalls"]["ingest"]["consumer_wait_s"]
    errors = validate_record(bad)
    assert any("consumer_wait_s" in e for e in errors)
    neg = copy.deepcopy(GOOD)
    neg["end_to_end"]["queue_stalls"]["ingest"]["producer_block_s"] = -1.0
    errors = validate_record(neg)
    assert any("negative" in e for e in errors)


def test_wrapper_with_failed_rc_is_tolerated(tmp_path):
    # rc != 0 with no parsed record is a legitimate historical record
    path = tmp_path / "BENCH_rX.json"
    path.write_text(json.dumps(
        {"n": 1, "cmd": "python bench.py", "rc": 1, "tail": "boom",
         "parsed": None}
    ))
    assert validate_file(str(path)) == []
    # but rc == 0 with no parsed record is drift
    path.write_text(json.dumps(
        {"n": 1, "cmd": "python bench.py", "rc": 0, "tail": "", "parsed": None}
    ))
    assert validate_file(str(path))


def test_checker_cli_over_committed_records():
    import subprocess

    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_bench_schema.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stderr


def test_mixed_workload_block_is_validated_strictly():
    mx = GOOD["serving"]["mixed_workload"]
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["mixed_workload"]["acked_missing"]
    assert any("acked_missing" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["mixed_workload"]["acked_missing"] = 3
    assert any("acknowledged upsert" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["mixed_workload"]["upserts"]["ack_p99_ms"] = 0.1
    assert any("ack_p99_ms below ack_p50_ms" in e
               for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    del bad["serving"]["mixed_workload"]["upserts"]["achieved_per_sec"]
    assert any("achieved_per_sec" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["mixed_workload"]["read"]["achieved_qps"] = "fast"
    assert any("achieved_qps" in e for e in validate_record(bad))

    # a failed leg records {"error": ...} and must not fail validation
    failed = copy.deepcopy(GOOD)
    failed["serving"]["mixed_workload"] = {"error": "TimeoutError: x"}
    assert validate_record(failed) == []

    # historic records (no mixed_workload at all) keep validating
    old = copy.deepcopy(GOOD)
    del old["serving"]["mixed_workload"]
    assert validate_record(old) == []
    assert isinstance(mx, dict)


def test_chaos_upserts_subblock_is_validated():
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["upserts"]["missing"] = 4
    assert any("acknowledged-write loss" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    del bad["serving"]["chaos"]["upserts"]["acked"]
    assert any("acked" in e for e in validate_record(bad))

    old = copy.deepcopy(GOOD)
    del old["serving"]["chaos"]["upserts"]
    assert validate_record(old) == []


def test_autonomy_block_is_validated_strictly():
    bad = copy.deepcopy(GOOD)
    del bad["storage"]["autonomy"]["converged"]
    assert any("converged" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["storage"]["autonomy"]["converged"] = False
    assert any("never converged" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["storage"]["autonomy"]["passes"] = 0
    assert any("proves nothing" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["storage"]["autonomy"]["read_amp_end"] = 4  # above low=2
    assert any("above the low watermark" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["storage"]["autonomy"]["read_amp_bounded"] = False
    assert any("escaped" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["storage"]["autonomy"]["read_amp_samples"] = [2, "x"]
    assert any("read_amp_samples" in e for e in validate_record(bad))

    # a failed leg records its error without poisoning the file
    failed = copy.deepcopy(GOOD)
    failed["storage"]["autonomy"] = {"error": "OSError: boom"}
    assert validate_record(failed) == []

    # historic records (no storage block at all) keep validating
    old = copy.deepcopy(GOOD)
    del old["storage"]
    assert validate_record(old) == []


def test_chaos_maintain_subblock_is_validated():
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["maintain"]["converged"] = False
    assert any("autonomy is broken" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    del bad["serving"]["chaos"]["maintain"]["passes"]
    assert any("passes" in e for e in validate_record(bad))

    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["maintain"]["paused"] = "two"
    assert any("paused" in e for e in validate_record(bad))

    old = copy.deepcopy(GOOD)
    del old["serving"]["chaos"]["maintain"]
    assert validate_record(old) == []


GOOD_MULTICHIP = {
    "mode": "multichip",
    "metric": "multichip_annotate_speedup_8dev",
    "value": 1.9,
    "unit": "x_vs_1dev",
    "vs_baseline": 0.95,
    "backend": "cpu",
    "platform_pin": "cpu",
    "multichip": {
        "devices": [1, 2, 4, 8],
        "cores": 2,
        "label": "virtual-cpu host mesh (shared cores)",
        "annotate": {
            "rows": 524288, "width": 16, "speedup_at_max": 1.9,
            "per_device": [
                {"devices": d, "rows_per_sec": 1e6 * d, "seconds": 0.5,
                 "speedup": float(d), "efficiency": 1.0,
                 "byte_identical": True}
                for d in (1, 2, 4, 8)
            ],
        },
        "bulk_lookup": {
            "store_rows": 2097152, "queries": 65536,
            "speedup_at_max": 1.4,
            "per_device": [
                {"devices": d, "lookups_per_sec": 1e5 * d,
                 "seconds": 0.4, "speedup": float(d),
                 "efficiency": 1.0, "byte_identical": True}
                for d in (1, 2, 4, 8)
            ],
        },
    },
}


def test_multichip_record_validates():
    assert validate_record(GOOD_MULTICHIP) == []


def test_multichip_block_is_validated_strictly():
    # byte_identical=false is a hard failure at ANY device count
    rec = copy.deepcopy(GOOD_MULTICHIP)
    rec["multichip"]["annotate"]["per_device"][2]["byte_identical"] = False
    assert any("byte_identical" in e for e in validate_record(rec))
    # a missing per-device throughput is a failure
    rec = copy.deepcopy(GOOD_MULTICHIP)
    del rec["multichip"]["bulk_lookup"]["per_device"][0]["lookups_per_sec"]
    assert any("lookups_per_sec" in e for e in validate_record(rec))
    # the honesty fields are required: cores + label + device list
    for field in ("cores", "label", "devices"):
        rec = copy.deepcopy(GOOD_MULTICHIP)
        del rec["multichip"][field]
        assert any(field in e for e in validate_record(rec)), field
    # missing speedup_at_max fails
    rec = copy.deepcopy(GOOD_MULTICHIP)
    del rec["multichip"]["annotate"]["speedup_at_max"]
    assert any("speedup_at_max" in e for e in validate_record(rec))
    # a multichip-mode record with no block (and no error) fails
    rec = copy.deepcopy(GOOD_MULTICHIP)
    del rec["multichip"]
    assert any("no" in e and "multichip" in e for e in validate_record(rec))
    # ... unless it recorded an error (a failed run stays loadable)
    rec["error"] = "RuntimeError: backend died"
    assert validate_record(rec) == []
    # a skipped curve (too few devices) is a legitimate record
    rec = copy.deepcopy(GOOD_MULTICHIP)
    rec["multichip"] = {"skipped": "only 1 CPU device"}
    assert validate_record(rec) == []


def test_multichip_block_inside_full_record_validates():
    rec = copy.deepcopy(GOOD)
    rec["multichip"] = copy.deepcopy(GOOD_MULTICHIP["multichip"])
    assert validate_record(rec) == []
    rec["multichip"]["bulk_lookup"]["per_device"][3]["byte_identical"] = False
    assert any("byte_identical" in e for e in validate_record(rec))


def test_multichip_dryrun_wrappers_validate(tmp_path):
    # the historic MULTICHIP_r02–r05 shape stays loadable
    wrapper = {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
               "tail": "dryrun_multichip(8): ok\n"}
    p = tmp_path / "MULTICHIP_r99.json"
    p.write_text(json.dumps(wrapper))
    assert validate_file(str(p)) == []
    bad = dict(wrapper, ok="yes")
    p.write_text(json.dumps(bad))
    assert any("ok" in e for e in validate_file(str(p)))


def test_checker_cli_covers_committed_multichip_records():
    paths = sorted(glob.glob(os.path.join(ROOT, "MULTICHIP_*.json")))
    assert len(paths) >= 4  # r02–r05 are committed history
    for path in paths:
        assert validate_file(path) == [], path


def test_observability_block_is_validated_strictly():
    """The tracing-overhead gate: overhead over the bound (or a false
    within_bound) is a schema ERROR — the layer's cost is pinned by the
    record, not by hope."""
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["observability"]["overhead_qps"]
    assert any("overhead_qps" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["observability"]["armed"]
    assert any("armed" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["observability"]["overhead_qps"] = 0.08  # > 3%
    assert any("overhead bound" in e for e in validate_record(bad))
    # p99 over the RATIO but under the absolute noise floor: tolerated
    # (on a 10-40ms baseline 3% measures the container, not the code)
    noisy = copy.deepcopy(GOOD)
    noisy["serving"]["observability"]["overhead_p99"] = 0.08
    noisy["serving"]["observability"]["overhead_p99_ms"] = 0.9
    assert validate_record(noisy) == []
    # p99 over the ratio AND over the floor: rejected
    bad = copy.deepcopy(GOOD)
    bad["serving"]["observability"]["overhead_p99"] = 0.31
    bad["serving"]["observability"]["overhead_p99_ms"] = 8.2
    assert any("noise floor" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["observability"]["within_bound"] = False
    assert any("within_bound" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["observability"]["armed"] = {"p99_ms": 1.0}
    assert any("achieved_qps" in e for e in validate_record(bad))
    # historic records carry no observability block: still valid
    old = copy.deepcopy(GOOD)
    del old["serving"]["observability"]
    assert validate_record(old) == []
    # a failed leg records {"error": ...} and stays loadable
    failed = copy.deepcopy(GOOD)
    failed["serving"]["observability"] = {"error": "worker died"}
    assert validate_record(failed) == []


def test_slo_block_is_validated_strictly():
    """The health-plane overhead gate rides the same armed/unarmed
    contract as tracing, PLUS the alerts_sample proof: a record claiming
    the gate ran without showing a live /alerts body is rejected."""
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["slo"]["overhead_qps"]
    assert any("slo" in e and "overhead_qps" in e
               for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["slo"]["overhead_qps"] = 0.08  # > 3%
    assert any("health plane is too expensive" in e
               for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["slo"]["within_bound"] = False
    assert any("failed its own overhead gate" in e
               for e in validate_record(bad))
    # the liveness proof: sample required, must be enabled, must carry
    # well-formed SLO rows
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["slo"]["alerts_sample"]
    assert any("alerts_sample" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["slo"]["alerts_sample"]["enabled"] = False
    assert any("health plane was off" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["slo"]["alerts_sample"]["alerts"] = []
    assert any("at least one declared SLO row" in e
               for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["slo"]["alerts_sample"]["alerts"][0]["state"] = "broken"
    assert any("valid state" in e for e in validate_record(bad))
    # p99 over the ratio but under the absolute floor: tolerated, same
    # container-noise escape the tracing gate carries
    noisy = copy.deepcopy(GOOD)
    noisy["serving"]["slo"]["overhead_p99"] = 0.08
    noisy["serving"]["slo"]["overhead_p99_ms"] = 0.9
    assert validate_record(noisy) == []
    # pre-PR-17 records carry no slo block: still valid; a failed leg
    # records {"error": ...} and stays loadable
    old = copy.deepcopy(GOOD)
    del old["serving"]["slo"]
    assert validate_record(old) == []
    failed = copy.deepcopy(GOOD)
    failed["serving"]["slo"] = {"error": "worker died"}
    assert validate_record(failed) == []


def test_bench_regress_watchdog_verdicts(tmp_path):
    """The regression watchdog: newest-vs-trailing-median on every
    tracked headline, with the thin-history escape and the exit-code
    contract (1 = regression, 0 = clean or insufficient history)."""
    import subprocess

    from check_bench_regress import evaluate_history, load_records

    def rec(n, qps, p99, value=250000.0):
        return {
            "n": n,
            "parsed": {
                "metric": "end_to_end", "unit": "variants/sec",
                "value": value,
                "serving": {"qps": qps, "p99_ms": p99},
            },
        }

    history = [rec(i, 3000.0 + 10 * i, 10.0) for i in range(1, 6)]
    ok = evaluate_history(history + [rec(6, 2900.0, 11.0)])
    assert ok["regressions"] == 0
    by_name = {c["series"]: c for c in ok["checks"]}
    assert by_name["serving.qps"]["verdict"] == "ok"
    assert by_name["serving.p99_ms"]["verdict"] == "ok"
    # a halved qps and a >2x p99 both trip
    regressed = evaluate_history(history + [rec(6, 100.0, 99.0)])
    names = {c["series"]: c["verdict"] for c in regressed["checks"]}
    assert names["serving.qps"] == "regression"
    assert names["serving.p99_ms"] == "regression"
    assert regressed["regressions"] >= 2
    # single-point series: thin, never a regression
    thin = evaluate_history([rec(1, 3000.0, 10.0)])
    assert thin["regressions"] == 0
    assert thin["thin"] == len(thin["checks"])
    # a serving error row carries no benchmark fact
    errored = [rec(1, 3000.0, 10.0)]
    errored[0]["parsed"]["serving"]["error"] = "died"
    assert all(not c["series"].startswith("serving.")
               for c in evaluate_history(errored)["checks"])
    # CLI contract: regression -> 1, thin/empty history -> 0
    bench_dir = tmp_path / "hist"
    bench_dir.mkdir()
    tool = os.path.join(ROOT, "tools", "check_bench_regress.py")
    for i, doc in enumerate(history + [rec(6, 100.0, 10.0)], start=1):
        (bench_dir / f"BENCH_r{i:02d}.json").write_text(json.dumps(doc))
    assert subprocess.run(
        [sys.executable, tool, "--dir", str(bench_dir)],
        capture_output=True,
    ).returncode == 1
    (bench_dir / "BENCH_r06.json").write_text(
        json.dumps(rec(6, 2900.0, 11.0))
    )
    assert subprocess.run(
        [sys.executable, tool, "--dir", str(bench_dir)],
        capture_output=True,
    ).returncode == 0
    # unreadable + parsed-null records are skipped, not fatal
    (bench_dir / "BENCH_r00.json").write_text("{not json")
    (bench_dir / "BENCH_r07.json").write_text(json.dumps(
        {"n": 7, "parsed": None}
    ))
    assert len(load_records(str(bench_dir))) == 6


def test_chaos_flight_subblock_is_validated():
    """The black-box gates ride the chaos record: a missing harvest or a
    parse failure is a schema error, and pre-PR-14 records (no flight
    sub-block) stay valid."""
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["flight"]["harvested_files"] = 0
    assert any("no black box was harvested" in e
               for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["flight"]["parse_failures"] = 1
    assert any("failed to parse" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    del bad["serving"]["chaos"]["flight"]["harvested_requests"]
    assert any("harvested_requests" in e for e in validate_record(bad))
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["flight"] = "yes"
    assert any("flight: must be an object" in e
               for e in validate_record(bad))
    old = copy.deepcopy(GOOD)
    del old["serving"]["chaos"]["flight"]
    assert validate_record(old) == []


def test_replication_block_is_validated_strictly():
    # the hard verdict: acknowledged writes lost across the failover
    bad = copy.deepcopy(GOOD)
    bad["serving"]["replication"]["acked_missing"] = 3
    assert any("acked_missing" in e for e in validate_record(bad))

    # write availability never restored after promote
    bad = copy.deepcopy(GOOD)
    bad["serving"]["replication"]["post_promote_write_ok"] = False
    assert any("post_promote_write_ok" in e for e in validate_record(bad))

    # the lag distribution must be a distribution
    bad = copy.deepcopy(GOOD)
    bad["serving"]["replication"]["lag_p99_s"] = 0.0
    bad["serving"]["replication"]["lag_p50_s"] = 1.0
    assert any("lag_p99_s below lag_p50_s" in e
               for e in validate_record(bad))

    # follower reads that diverged from the leader's bytes
    bad = copy.deepcopy(GOOD)
    bad["serving"]["replication"]["wrong_bytes"] = 2
    assert any("wrong_bytes" in e for e in validate_record(bad))

    # required evidence fields
    for field in ("ship_mb_per_s", "lag_p50_s", "lag_p99_s",
                  "failover_s", "acked_missing"):
        bad = copy.deepcopy(GOOD)
        del bad["serving"]["replication"][field]
        assert any(field in e for e in validate_record(bad)), field

    # historic records (r01-r11) carry no replication block: still valid
    old = copy.deepcopy(GOOD)
    del old["serving"]["replication"]
    assert validate_record(old) == []
    # a failed leg records {"error": ...} and stays loadable
    failed = copy.deepcopy(GOOD)
    failed["serving"]["replication"] = {"error": "replication timed out"}
    assert validate_record(failed) == []


def test_chaos_repl_subblock_and_committed_repl_records():
    # the --repl chaos record's repl sub-block shares the contract
    bad = copy.deepcopy(GOOD)
    bad["serving"]["chaos"]["repl"] = {
        "max_lag_s": 3.0, "lag_p50_s": 0.0, "lag_p99_s": 0.2,
        "ship_mb_per_s": 0.01, "failover_s": 2.0, "acked_missing": 1,
    }
    assert any("acked_missing" in e for e in validate_record(bad))
    bad["serving"]["chaos"]["repl"]["acked_missing"] = 0
    assert validate_record(bad) == []

    # every committed REPL_r*.json must validate (recovered true, zero
    # violations, acked_missing 0)
    paths = sorted(glob.glob(os.path.join(ROOT, "REPL_*.json")))
    assert paths, "no committed REPL_r*.json failover certification"
    for path in paths:
        assert validate_file(path) == [], path


def test_committed_repl_record_rejects_loss(tmp_path):
    # a doctored record with failover loss must NOT validate
    with open(sorted(glob.glob(os.path.join(ROOT, "REPL_*.json")))[0]) as f:
        rec = json.load(f)
    rec["repl"]["acked_missing"] = 5
    rec["violations"] = ["acked-upsert loss across failover"]
    p = tmp_path / "REPL_r99.json"
    p.write_text(json.dumps(rec))
    errors = validate_file(str(p))
    assert any("acked_missing" in e for e in errors)
    assert any("violations" in e for e in errors)


def test_bench_regress_insufficient_history_cases(tmp_path):
    """A 0-, 1-, or 2-record history is 'insufficient history': the
    watchdog says so and exits 0 — a fresh checkout or a young repo must
    never fail the check chain, and a single prior is not a median worth
    judging against (even when that prior would scream regression)."""
    import subprocess

    from check_bench_regress import MIN_HISTORY

    def rec(n, qps, p99):
        return {
            "n": n,
            "parsed": {
                "metric": "end_to_end", "unit": "variants/sec",
                "value": 250000.0,
                "serving": {"qps": qps, "p99_ms": p99},
            },
        }

    assert MIN_HISTORY == 3
    tool = os.path.join(ROOT, "tools", "check_bench_regress.py")
    bench_dir = tmp_path / "hist"
    bench_dir.mkdir()
    # the 2-record case is the sharp edge: the newest point HALVES qps
    # against its single prior, which a premature judge would flag
    docs = [rec(1, 3000.0, 10.0), rec(2, 100.0, 99.0)]
    for count in (0, 1, 2):
        for i in range(count):
            (bench_dir / f"BENCH_r{i + 1:02d}.json").write_text(
                json.dumps(docs[i]))
        p = subprocess.run(
            [sys.executable, tool, "--dir", str(bench_dir), "--json"],
            capture_output=True, text=True,
        )
        assert p.returncode == 0, (count, p.stderr)
        assert "insufficient history" in p.stderr, (count, p.stderr)
        report = json.loads(p.stdout)
        assert report["checks"] == [] and report["regressions"] == 0
        assert report["insufficient_history"] == count
    # unparseable files do not count toward the minimum
    (bench_dir / "BENCH_r03.json").write_text("{not json")
    (bench_dir / "BENCH_r04.json").write_text(json.dumps(
        {"n": 4, "parsed": None}))
    p = subprocess.run(
        [sys.executable, tool, "--dir", str(bench_dir)],
        capture_output=True, text=True,
    )
    assert p.returncode == 0 and "insufficient history" in p.stderr
    # the third parseable record crosses the threshold: judged for real
    (bench_dir / "BENCH_r05.json").write_text(json.dumps(
        rec(5, 90.0, 99.0)))
    p = subprocess.run(
        [sys.executable, tool, "--dir", str(bench_dir)],
        capture_output=True, text=True,
    )
    assert "insufficient history" not in p.stderr
