#!/usr/bin/env bash
# One entry point for every static check this repo carries; tier-1
# (tests/test_static_checks.py) shells this script so the whole suite
# gates every PR without separate CI infrastructure.
#
#   1. avdb_check  — project-native rules (trace-safety, lock-discipline,
#                    registry-drift, env-drift, CLI-contract, hygiene,
#                    async-safety, twin contract, durability protocol)
#   2. ruff        — generic pyflakes-class lint (pyproject.toml subset);
#                    SKIPPED with a notice when ruff is not installed
#                    (the container image does not ship it)
#   3. check_bench_schema — committed BENCH_*.json records stay loadable
#   4. serve_smoke — the HTTP query API answers point/region/metrics
#                    against a tiny store on an ephemeral loopback port;
#                    runs under AVDB_LOCK_TRACE=1, so every serve-stack
#                    lock is order-traced and ANY acquisition-order cycle
#                    (potential deadlock) fails the smoke
#   5. compact_smoke — crash-safe `doctor compact`: kill a pass mid-merge,
#                    doctor --repair the debris, complete the pass, and
#                    byte-verify the store against the pre-compaction
#                    reference; runs under AVDB_IO_TRACE=1 (the crash-
#                    consistency sanitizer: any rename-before-fsync /
#                    live-file unlink / missing dir fsync fails it)
#   6. upsert_smoke — the WAL-durable live write path: upsert -> SIGKILL
#                    the worker -> respawn replays the WAL -> byte-verify
#                    -> memtable flush -> deep fsck clean; io-order
#                    traced under AVDB_IO_TRACE=1 like compact_smoke
#   7. maintain_smoke — autonomous storage management: a fleet with the
#                    maintenance daemon armed sustains upserts until the
#                    segment watermark trips, and daemon-driven
#                    compaction converges read-amp back below the low
#                    watermark with byte-identical reads
#   8. mesh_smoke — the mesh-native path: forced 4-device host mesh,
#                    sharded load (placement block committed), and a
#                    real fleet with AVDB_SERVE_MESH=1 answering every
#                    query shape byte-identical to a mesh-off server
#   9. ingest_smoke — the overlapped ingest spine: synthetic VCF loaded
#                    serial vs shuffled-overlapped vs mesh-placement
#                    write order, all three byte-identical, deep fsck
#                    clean
#  10. chaos_soak --smoke — a 1-worker fleet under open-loop load with
#                    injected drain latency + a device-EIO breaker trip:
#                    zero wrong bytes, bounded errors, clean recovery
#  11. slo_smoke    — the alert plane end to end: induced latency via the
#                    /_chaos delay lever walks the point-read p99 SLO
#                    ok -> pending -> firing, the lever disarms, and the
#                    alert resolves through the clear-tick hysteresis
#                    (plus the replication_lag gauge-ceiling walk)
#  12. repl_smoke   — the replica fleet: a follower bootstraps from the
#                    leader's snapshot cut, tails the WAL ship stream
#                    under injected flakiness, the leader is SIGKILLed,
#                    `doctor promote` fails over, and every acknowledged
#                    upsert answers byte-identical from the new leader;
#                    io-order traced under AVDB_IO_TRACE=1
#  13. export_smoke — the training-corpus export subsystem: multi-part
#                    reference export, the real CLI SIGKILLed mid-part-
#                    commit, fsck attributing the debris (export-tmp,
#                    never foreign-file), --resume byte-identical to the
#                    uninterrupted run, same-seed replay byte-identical;
#                    io-order traced under AVDB_IO_TRACE=1
#  14. check_bench_regress — the newest committed BENCH record's
#                    headlines (serving qps/p99, load variants/sec)
#                    against the trailing median of their own history
#
# Exit: 0 all clean, 1 any check found problems.

set -u
root="$(cd "$(dirname "$0")/.." && pwd)"
rc=0

echo "== avdb_check ==" >&2
python "$root/tools/avdb_check.py" \
    "$root/annotatedvdb_tpu" "$root/tools" "$root/tests" "$root/bench.py" \
    "$root/chip_smoke.py" \
    || rc=1

echo "== ruff ==" >&2
if command -v ruff >/dev/null 2>&1; then
    (cd "$root" && ruff check .) || rc=1
elif python -c "import ruff" >/dev/null 2>&1; then
    (cd "$root" && python -m ruff check .) || rc=1
else
    echo "ruff not installed: skipped (pyproject.toml carries the config)" >&2
fi

echo "== bench schema ==" >&2
python "$root/tools/check_bench_schema.py" || rc=1

echo "== serve smoke (lock-order traced) ==" >&2
AVDB_LOCK_TRACE=1 python "$root/tools/serve_smoke.py" || rc=1

echo "== compact smoke (io-order traced) ==" >&2
AVDB_IO_TRACE=1 python "$root/tools/compact_smoke.py" || rc=1

echo "== upsert smoke (io-order traced) ==" >&2
AVDB_IO_TRACE=1 python "$root/tools/upsert_smoke.py" || rc=1

echo "== maintain smoke ==" >&2
python "$root/tools/maintain_smoke.py" || rc=1

echo "== mesh smoke ==" >&2
python "$root/tools/mesh_smoke.py" || rc=1

echo "== ingest smoke ==" >&2
python "$root/tools/ingest_smoke.py" || rc=1

echo "== chaos smoke ==" >&2
python "$root/tools/chaos_soak.py" --smoke || rc=1

echo "== slo smoke ==" >&2
python "$root/tools/slo_smoke.py" || rc=1

echo "== repl smoke (io-order traced) ==" >&2
AVDB_IO_TRACE=1 python "$root/tools/repl_smoke.py" || rc=1

echo "== export smoke (io-order traced) ==" >&2
AVDB_IO_TRACE=1 python "$root/tools/export_smoke.py" || rc=1

echo "== bench regression watchdog ==" >&2
python "$root/tools/check_bench_regress.py" || rc=1

if [ "$rc" -eq 0 ]; then
    echo "run_checks: all checks clean" >&2
else
    echo "run_checks: FAILURES above" >&2
fi
exit "$rc"
