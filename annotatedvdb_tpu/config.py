"""Unified typed configuration for every entry point.

The reference repeats flag conventions per script with no shared registry
(``load_vcf_file.py:247-286`` et al., SURVEY.md §5.6).  Here the common
surface is three frozen dataclasses plus argparse registrars: the load and
update drivers share the commit/test/log lifecycle flags
(:func:`add_lifecycle_args`), ``load-vcf`` — the primary driver — layers the
full load + runtime registries on top, and loaders receive typed objects
instead of loose ``args`` namespaces:

- :class:`RuntimeConfig` — platform pin, device fan-out, multi-host;
- :class:`StoreConfig`  — store location/shape;
- :class:`LoadConfig`   — the commit/test/resume/cadence contract every
  loader shares (the reference's ``--commit``/``--commitAfter``/
  ``--resumeAfter``-era conventions).

``annotatedvdb_tpu.cli`` (``python -m annotatedvdb_tpu``) is the single
umbrella command dispatching to the per-task entry points.
"""

from __future__ import annotations

import argparse
import os
from dataclasses import dataclass


#: Canonical registry of every ``AVDB_*`` environment variable the tree
#: reads (name -> one-line doc).  The static analyzer enforces the contract
#: both ways: an undeclared read is AVDB401, a declared-but-never-read
#: entry is AVDB403, and a declared-but-undocumented entry (vs README's
#: environment table) is AVDB402 — so this dict, README, and the code can
#: never drift apart silently.
ENV_VARS: dict = {
    # runtime / platform pin
    "AVDB_JAX_PLATFORM": "cpu pins the CPU backend (same as --platform "
                         "cpu); unset/anything else leaves JAX's own "
                         "backend selection alone",
    # load pipeline
    "AVDB_PIPELINE": "overlapped (default) | serial — staged executor vs "
                     "single-thread double-buffered loop",
    "AVDB_ASYNC_STORE": "0 folds the store writer back into the process "
                        "thread (default 1: async writer stage)",
    "AVDB_INGEST_ENGINE": "auto (default) | native | python — VCF tokenizer "
                          "selection (python captures reject content)",
    "AVDB_INGEST_CHUNK_ROWS": "rows per ingest chunk (default: the "
                              "loader's batch_size; a malformed value "
                              "fails the entry point)",
    "AVDB_INGEST_PREFETCH_DEPTH": "chunks the ingest scanner may run "
                                  "ahead of the pipeline (default 2; "
                                  "bounds staging memory to O(depth) "
                                  "chunks)",
    "AVDB_INGEST_SHUFFLE_SEED": "arms shuffled chunk scheduling with this "
                                "seed (unset = strict source order; the "
                                "resequencer keeps the stored bytes "
                                "identical either way)",
    "AVDB_NATIVE_VEP": "0 disables the native VEP JSON transform",
    "AVDB_NATIVE_CADD": "0 disables the native CADD table scanner",
    "AVDB_PACK_TRANSPORT": "0 disables nibble-packed allele upload and "
                           "packed output transport",
    "AVDB_LOAD_GC": "0 keeps the collector enabled during bulk loads "
                    "(default: gc paused, one collect per load)",
    # device mesh (parallel/mesh.py is the single authority)
    "AVDB_MESH_SHAPE": "device count of the global 1-D mesh (unset = all "
                       "visible devices; a malformed value fails the "
                       "entry point; also recorded as the manifest's "
                       "advisory mesh_placement block at save time)",
    "AVDB_SERVE_MESH": "serve-side mesh execution: auto (default — "
                       "engages with >1 device on a non-CPU backend) | "
                       "1 (force, e.g. the tier-1 virtual-CPU mesh "
                       "tests) | 0 (disable)",
    "AVDB_MESH_BULK_MIN": "smallest bulk-lookup batch that pays a mesh "
                          "dispatch (default 64; 0 sends every batch)",
    # multi-host
    "AVDB_COORDINATOR": "host:port of the jax.distributed coordinator",
    "AVDB_NUM_PROCESSES": "world size for multi-host init",
    "AVDB_PROCESS_ID": "this process's rank for multi-host init",
    # store / robustness
    "AVDB_FSYNC": "1 extends durability to power loss (fsync segment data "
                  "and directories, not just manifest renames)",
    "AVDB_VERIFY": "load-time integrity level: size (default) | deep "
                   "(full checksums) | off",
    "AVDB_DEVICE_LOOKUP": "1 keeps membership-probe segments resident in "
                          "HBM (device lookup cache)",
    "AVDB_FAULT": "<point>:<nth|prob:<p>>[:<action>[:<ms>]] deterministic "
                  "fault injection (see utils/faults.py; unknown points "
                  "fail the arm)",
    "AVDB_FAULT_SEED": "integer seed for the prob:<p> fault-arming coin "
                       "(default 0xA5DB) — chaos runs replay exactly",
    "AVDB_STORE_SPILL_BYTES": "segment containers at/above this size load "
                              "as copy-on-write memmaps (out-of-core tier; "
                              "512m / 2g suffixes; unset/0 = materialize "
                              "everything)",
    "AVDB_COMPACT_CHUNK_ROWS": "rows per streamed merge chunk in doctor "
                               "compact (default 262144) — the unit of "
                               "peak row-payload memory during a pass",
    "AVDB_COMPACT_MIN_SEGMENTS": "smallest on-disk segment-file count that "
                                 "makes a chromosome group eligible for "
                                 "doctor compact (default 2)",
    "AVDB_MEMTABLE_BYTES": "approximate memtable size at which the live "
                           "write path flushes to store segments "
                           "(default 64m; 512m / 2g suffixes; 0 disables "
                           "the size trigger)",
    "AVDB_MEMTABLE_FLUSH_S": "oldest-unflushed-upsert age in seconds at "
                             "which the memtable flushes regardless of "
                             "size (default 30; 0 disables the age "
                             "trigger)",
    "AVDB_MAINTAIN": "1 arms the autonomous maintenance daemon in the "
                     "serve fleet supervisor (watermark-driven background "
                     "compaction; the --maintain flag is the CLI "
                     "spelling)",
    "AVDB_MAINTAIN_SEGMENTS_HIGH": "per-group segment-file count at which "
                                   "the maintenance daemon engages a "
                                   "compaction pass (default 8)",
    "AVDB_MAINTAIN_SEGMENTS_LOW": "hysteresis exit: the daemon disengages "
                                  "once every group is at/below this many "
                                  "segment files (default 2; clamped "
                                  "below the high watermark)",
    "AVDB_MAINTAIN_TICK_S": "maintenance daemon poll cadence in seconds, "
                            "jittered +/-25% (default 2)",
    "AVDB_MAINTAIN_COOLDOWN_S": "base cool-down after a paused/preempted/"
                                "failed maintenance pass, doubling per "
                                "consecutive setback up to 60s "
                                "(default 5)",
    "AVDB_STORE_DISK_RESERVE_BYTES": "free-disk reserve under the store "
                                     "below which upserts answer 507 "
                                     "Insufficient Storage on both front "
                                     "ends (512m / 2g suffixes; unset/0 "
                                     "disables) — reads, flushes of "
                                     "acknowledged rows, and compaction "
                                     "keep running",
    # query & serving (serve/)
    "AVDB_SERVE_BATCH_MAX": "max point queries coalesced into one device "
                            "microbatch (default 256)",
    "AVDB_SERVE_BATCH_WAIT_MS": "batcher drain deadline in ms: how long the "
                                "first query of a batch waits for company "
                                "(default 2)",
    "AVDB_SERVE_MAX_QUEUE": "admission bound: pending queries beyond this "
                            "are rejected with HTTP 429 (default 1024)",
    "AVDB_SERVE_REGION_CACHE": "LRU capacity of the rendered hot-region "
                               "cache, keyed by store generation "
                               "(default 64; 0 disables)",
    "AVDB_SERVE_REGIONS_MAX": "max query intervals per POST /regions batch "
                              "(default 4096; over-cap batches are 400)",
    "AVDB_SERVE_REGIONS_DEVICE_MIN": "min intervals per chromosome group "
                                     "before the batched BITS kernel "
                                     "engages (default 32; smaller groups "
                                     "take the byte-identical host path, "
                                     "0 sends every group to the device)",
    "AVDB_SERVE_STATS_MAX": "max query intervals per POST /stats/region "
                            "analytics batch (default 4096; over-cap "
                            "batches are 400)",
    "AVDB_SERVE_STATS_DEVICE_MIN": "min intervals per chromosome group "
                                   "before the fused stats kernel engages "
                                   "(default 16; smaller panels take the "
                                   "byte-identical host twin, 0 sends "
                                   "every group to the device)",
    "AVDB_SERVE_WORKERS": "serve fleet size: N>1 runs N worker processes "
                          "sharing the port and one readonly store "
                          "generation (default 1)",
    "AVDB_SERVE_HBM_BUDGET": "byte budget for HBM-resident probe segment "
                             "caches, e.g. 512m / 2g (unset = unmanaged: "
                             "the store's own ski-rental rule)",
    "AVDB_SERVE_SNAPSHOT_TTL_MS": "coalesced manifest freshness window: "
                                  "one stat per window across all request "
                                  "threads (default 250)",
    "AVDB_SERVE_CLIENT_RATE": "weighted per-client admission: requests/sec "
                              "per weight unit, rejected 429 beyond the "
                              "bucket (default 0 = disabled)",
    "AVDB_SERVE_STREAM_THRESHOLD": "region row count above which responses "
                                   "stream chunked instead of buffering "
                                   "the body (default 2048)",
    "AVDB_SERVE_DEFAULT_DEADLINE_MS": "default per-request deadline budget "
                                      "in ms (X-Deadline-Ms overrides; "
                                      "0 = requests carry no deadline)",
    "AVDB_SERVE_BROWNOUT_P99_MS": "brownout ladder latency target: when "
                                  ">~5% of recent requests exceed it the "
                                  "ladder escalates (default 250; 0 "
                                  "disables the latency trigger)",
    "AVDB_SERVE_WEDGE_TIMEOUT_S": "fleet watchdog: SIGKILL+respawn a live "
                                  "worker whose event-loop heartbeat is "
                                  "staler than this (default 10; 0 "
                                  "disables)",
    "AVDB_SERVE_CHAOS": "1 enables the POST /_chaos runtime fault-arming "
                        "route on the aio front end (chaos harness only; "
                        "never set in production)",
    "AVDB_SERVE_UPSERTS": "1 enables the live write path: POST "
                          "/variants/upsert with a per-worker WAL "
                          "(replayed on worker start) and memtable "
                          "flushes to store segments",
    # replication (store/replication.py; serve --follow / doctor promote)
    "AVDB_REPL_MAX_LAG_S": "declared follower staleness bound in seconds: "
                           "past it /readyz answers 503 and the "
                           "replication_lag SLO burns (default 5; 0 "
                           "disables both planes together)",
    "AVDB_REPL_POLL_S": "follower tail poll interval in seconds "
                        "(default 0.5; clamped to >= 0.02)",
    "AVDB_REPL_CHUNK_BYTES": "snapshot/WAL ship transfer chunk size "
                             "(default 4m; 512k / 8m suffixes; clamped "
                             "to >= 4k)",
    "AVDB_REPL_TIMEOUT_S": "per-request HTTP timeout for ship fetches "
                           "from the leader (default 10; clamped to "
                           ">= 0.1)",
    "AVDB_LOCK_TRACE": "1 arms the lock-order/deadlock detector: serve-"
                       "stack locks record per-thread acquisition order "
                       "(analysis/lockorder), cycles are potential "
                       "deadlocks, held time exports as "
                       "avdb_lock_held_seconds",
    "AVDB_IO_TRACE": "1 arms the crash-consistency sanitizer: store-path "
                     "open/write/fsync/rename/unlink route through "
                     "recording wrappers (utils/io) feeding a happens-"
                     "before recorder (analysis/iotrace) that flags "
                     "rename-before-fsync, unlink of a manifest-"
                     "referenced file, and missing directory fsync "
                     "after a manifest replace under AVDB_FSYNC=1",
    "AVDB_TRACE_SAMPLE": "fraction of requests recording per-stage span "
                         "breakdowns into the span ring + "
                         "avdb_stage_seconds (default 1.0; 0 disarms "
                         "recording — trace ids still mint and echo)",
    "AVDB_TRACE_SLOW_MS": "slow-request log threshold in ms: any request "
                          "over it logs its full span breakdown (default "
                          "0 = off)",
    "AVDB_FLIGHT_EVENTS": "crash flight-recorder ring slots per worker "
                          "(last-N request summaries + lifecycle events "
                          "in an mmap'd file that survives SIGKILL; "
                          "default 512, 0 disables)",
    "AVDB_OBS_TICK_S": "seconds between metrics time-series snapshots in "
                       "the health plane's history ring (default 1.0; 0 "
                       "disables the ring AND the SLO alert plane riding "
                       "it; malformed values fail startup)",
    "AVDB_OBS_HISTORY_S": "time-series history retention per worker in "
                          "seconds (default 300; 0 disables; the ring "
                          "persists to <store>/history/ for supervisor "
                          "harvest and doctor slo)",
    "AVDB_SLO_FAST_S": "fast SLO burn-rate window in seconds (default "
                       "60): proves a breach is happening NOW; both "
                       "windows must burn past AVDB_SLO_BURN to alert",
    "AVDB_SLO_SLOW_S": "slow (confirming) SLO burn-rate window in "
                       "seconds (default 300; must be >= the fast "
                       "window): proves a breach is sustained",
    "AVDB_SLO_BURN": "burn-rate threshold both SLO windows must exceed "
                     "for an alert to breach (default 2.0 = spending "
                     "error budget twice as fast as the objective "
                     "allows)",
    "AVDB_SLO_AVAIL_TARGET": "availability SLO objective as a fraction "
                             "in (0, 1) (default 0.999; the error "
                             "budget is 1 - target)",
    "AVDB_SLO_LOAD_FLOOR": "load-pipeline variants/sec floor SLO "
                           "(default 0 = declared but dormant; alerts "
                           "when the windowed avdb_rows_total rate "
                           "drops below it)",
    # ML corpus export (annotatedvdb_tpu/export)
    "AVDB_EXPORT_BATCH_ROWS": "rows per fixed-shape export batch (default "
                              "4096): every batch of a corpus shares this "
                              "one shape — one traced pack kernel, "
                              "explicit validity mask at the ragged tail",
    "AVDB_EXPORT_SHUFFLE_SEED": "corpus shuffle seed (default 0): same "
                                "seed => byte-identical corpus; the "
                                "export CLI's --seed overrides, --ordered "
                                "disables the shuffle",
    "AVDB_EXPORT_PART_BYTES": "target committed corpus-part size (default "
                              "8m; k/m/g suffixes): parts hold a "
                              "deterministic whole number of batches",
    # bench / test gates
    "AVDB_BENCH_ROWS": "synthetic row count for bench.py runs",
    "AVDB_BENCH_EXPORT_ROWS": "synthetic row count for the bench.py "
                              "--export corpus leg (default 120000)",
    "AVDB_BENCH_E2E_RUNS": "median-of-N run count for the end-to-end load "
                           "bench leg (default 5)",
    "AVDB_BENCH_VEP_RUNS": "median-of-N run count for the VEP bench leg "
                           "(default 3)",
    "AVDB_PROFILE": "directory for a jax.profiler device trace of the "
                    "bench run",
    "AVDB_SCALE_TEST": "1 enables the 10M-row scaling test tier",
    "AVDB_CRASH_TEST": "1 enables the subprocess crash/recovery matrix",
}


@dataclass(frozen=True)
class RuntimeConfig:
    """Execution environment: platform + parallel fan-out."""

    platform: str = "auto"        # auto (JAX's own selection) | cpu
    max_workers: str = "auto"     # auto | off | device count
    multihost: bool = True        # join jax.distributed when env configured

    def validate(self) -> None:
        """Raise ValueError for malformed flag VALUES (callers map this to
        a usage error; environment/runtime failures in :meth:`apply` are
        deliberately not conflated with it)."""
        if self.max_workers not in ("auto", "off"):
            try:
                if int(self.max_workers) < 1:
                    raise ValueError
            except ValueError:
                raise ValueError(
                    f"maxWorkers must be auto, off, or a count >= 1, "
                    f"not {self.max_workers!r}"
                ) from None

    def apply(self):
        """Honor an explicit CPU pin and place the compile cache, join the
        multi-host world (when configured), and return the annotate mesh
        (None = single device)."""
        from annotatedvdb_tpu.utils.runtime import pin_platform

        self.validate()
        pin_platform(self.platform)
        if self.multihost:
            from annotatedvdb_tpu.parallel.multihost import init_multihost

            init_multihost()
        if self.max_workers == "off":
            return None
        import jax

        from annotatedvdb_tpu.utils.profiling import startup_phase

        # the loader's annotate fan-out uses THIS PROCESS's devices: under
        # multi-host each process loads its own inputs share-nothing (the
        # reference's worker model) and numpy batches stay addressable; the
        # global mesh is the device-resident/dryrun path, not the load path
        with startup_phase("device"):  # the backend starts here
            devices = jax.local_devices()
        # resolution goes through the ONE mesh authority: AVDB_MESH_SHAPE
        # bounds the fan-out (and a typo'd shape fails here, loudly),
        # --maxWorkers clamps it further, single device returns None
        from annotatedvdb_tpu.parallel.mesh import global_mesh

        return global_mesh(
            limit=None if self.max_workers == "auto"
            else int(self.max_workers),
            devices=devices,
        )


from annotatedvdb_tpu.types import DEFAULT_ALLELE_WIDTH


@dataclass(frozen=True)
class StoreConfig:
    store_dir: str
    width: int = DEFAULT_ALLELE_WIDTH  # fixed per store at creation

    def open(self, create: bool = True, readonly: bool = False):
        """(store, ledger) — loading the existing store when present.

        ``readonly=True`` is the serving/read-path mode: the store must
        already exist (never created), ``save`` is forbidden, and missing
        shards are never materialized by lookups."""
        from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore

        manifest = os.path.join(self.store_dir, "manifest.json")
        if os.path.exists(manifest):
            store = VariantStore.load(self.store_dir, readonly=readonly)
        elif create and not readonly:
            os.makedirs(self.store_dir, exist_ok=True)
            store = VariantStore(width=self.width)
        else:
            raise FileNotFoundError(f"no store at {self.store_dir}")
        ledger = AlgorithmLedger(os.path.join(self.store_dir, "ledger.jsonl"))
        return store, ledger


@dataclass(frozen=True)
class LoadConfig:
    """The lifecycle contract shared by every load/update driver."""

    commit: bool = False          # default dry run (reference rollback mode)
    test: bool = False            # stop after one batch
    fail_at: str | None = None    # fault injection
    resume: bool = True           # honor ledger checkpoints
    commit_after: int = 1 << 16   # rows per batch/checkpoint
    log_after: int | None = None  # counter-line cadence; None -> commit_after
    datasource: str | None = None
    genome_build: str = "GRCh38"

    @property
    def effective_log_after(self) -> int | None:
        return effective_log_after(self.log_after, self.commit_after)


def add_lifecycle_args(parser: argparse.ArgumentParser) -> None:
    """The commit/test/log trio every load and update driver shares."""
    parser.add_argument("--commit", action="store_true",
                        help="persist the load (default: dry run)")
    parser.add_argument("--test", action="store_true",
                        help="stop after one batch")
    parser.add_argument("--logAfter", type=int, default=None,
                        help="log counters every N input lines "
                             "(default: the batch size; 0 disables)")
    parser.add_argument("--logFilePath", default=None,
                        help="log file (default: beside the input)")
    parser.add_argument("--maxErrors", type=int, default=-1, metavar="N",
                        help="abort once more than N input rows have been "
                             "rejected to the quarantine sink "
                             "(<store>/quarantine/<input>.rejects.jsonl); "
                             "default -1 = tolerate and quarantine all")


def quarantine_from_args(args, store_dir: str, loader_name: str,
                         input_path: str | None = None, log=None):
    """Build the per-load quarantine sink (``utils.quarantine``) shared by
    every loader CLI: rejects land replayably under ``<store>/quarantine/``
    and count against ``--maxErrors``."""
    from annotatedvdb_tpu.utils.quarantine import ErrorBudget, QuarantineSink

    input_path = input_path or getattr(args, "fileName", None)
    if not input_path or not store_dir:
        return None
    return QuarantineSink(
        store_dir, input_path, loader_name,
        budget=ErrorBudget(getattr(args, "maxErrors", -1)), log=log,
    )


def effective_log_after(log_after: int | None, default: int) -> int | None:
    """CLI cadence semantics: unset -> the batch default; 0 -> disabled."""
    if log_after is None:
        return default
    return log_after or None


def add_runtime_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--platform", default="auto",
                        choices=("auto", "cpu"),
                        help="backend pin: auto leaves JAX's own selection "
                             "alone (the TPU where there is one; no "
                             "fallback); cpu pins the CPU backend")
    parser.add_argument("--maxWorkers", default="auto",
                        help="devices to fan out across: auto/off/count")
    parser.add_argument("--noMultihost", action="store_true",
                        help="ignore multi-host environment settings")


def add_load_args(parser: argparse.ArgumentParser,
                  commit_after: int = 1 << 16) -> None:
    add_lifecycle_args(parser)
    parser.add_argument("--failAt", default=None,
                        help="fail at this variant id (fault injection)")
    parser.add_argument("--noResume", action="store_true",
                        help="ignore previous checkpoints for this file")
    parser.add_argument("--commitAfter", type=int, default=commit_after,
                        help="rows per device batch / checkpoint")
    parser.add_argument("--datasource", default=None,
                        help="e.g. dbSNP / ADSP / EVA")
    parser.add_argument("--genomeBuild", default="GRCh38")


def runtime_from_args(args) -> RuntimeConfig:
    return RuntimeConfig(
        platform=getattr(args, "platform", "auto"),
        max_workers=str(getattr(args, "maxWorkers", "auto")),
        multihost=not getattr(args, "noMultihost", False),
    )


def load_from_args(args) -> LoadConfig:
    return LoadConfig(
        commit=getattr(args, "commit", False),
        test=getattr(args, "test", False),
        fail_at=getattr(args, "failAt", None),
        resume=not getattr(args, "noResume", False),
        commit_after=getattr(args, "commitAfter", 1 << 16),
        log_after=getattr(args, "logAfter", None),
        datasource=getattr(args, "datasource", None),
        genome_build=getattr(args, "genomeBuild", "GRCh38"),
    )
