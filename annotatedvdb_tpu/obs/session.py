"""Per-load observability session: CLI flags, export wiring, run ledger.

Every loader CLI builds one :class:`ObsSession` around its load:

- ``attach(loader)`` hands the loader a chunk-granularity
  :class:`~annotatedvdb_tpu.obs.metrics.LoadObserver` and (when
  ``--traceOut`` was passed) points the loader's ``StageTimer`` at a
  :class:`~annotatedvdb_tpu.obs.trace.Tracer`, so every stage span also
  lands on that no-profiler timeline under its pipeline thread's track;
- ``finish``/``abort`` export the metrics textfile + JSON snapshot and the
  Chrome trace, then append ONE ``type: "run"`` record to the store's
  ``ledger.jsonl`` — input path, config hash, per-stage seconds, counters,
  queue stalls, error class if the load died — the machine-readable load
  history ``undo_load``/resume tooling and ops audits read back.

Observability must never kill a load: every export path is wrapped — a full
disk or read-only metrics target degrades to a stderr warning, the load's
own exit status is untouched.
"""

from __future__ import annotations

import hashlib
import json
import sys
import time

from annotatedvdb_tpu.obs.metrics import LoadObserver, MetricsRegistry
from annotatedvdb_tpu.obs.trace import Tracer


def add_obs_args(parser) -> None:
    """The telemetry flag pair every loader CLI shares."""
    parser.add_argument(
        "--metricsOut", default=None, metavar="FILE",
        help="write load metrics on exit: a Prometheus textfile at FILE "
             "plus a JSON snapshot at FILE.json",
    )
    parser.add_argument(
        "--traceOut", default=None, metavar="FILE",
        help="write a Chrome trace-event JSON of host pipeline spans "
             "(one track per pipeline thread, on its own clock; needs no "
             "profiler -- --profile holds the same spans beside the "
             "device's operations)",
    )


def config_hash(params: dict) -> str:
    """Short stable digest of a load's configuration — two runs with the
    same inputs and flags hash identically, so the run ledger groups them."""
    blob = json.dumps(params, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def execution_facts(probe_base: dict | None = None,
                    sidecar_base: dict | None = None,
                    mapping_base: dict | None = None,
                    freq_base: dict | None = None) -> dict:
    """Where and how this process ran its load: the device as JAX reports
    it, the annotate kernel that was selected, the transport verdicts, the
    native tokenizer's state, the device membership probes since
    ``probe_base``, the sidecar writer's rows since ``sidecar_base``, the
    mapping file's rows since ``mapping_base`` and the FREQ values' rows
    since ``freq_base`` —
    the run record's ``execution`` block, so a reader can tell a chip run
    from a CPU run, see which device paths a load reached, and take
    compile seconds apart from the load itself.
    Reports only: nothing here selects, probes or builds."""
    from annotatedvdb_tpu import native
    from annotatedvdb_tpu.io.egress import mapping_state
    from annotatedvdb_tpu.io.vcf import freq_state
    from annotatedvdb_tpu.models.pipeline import selected_kernel
    from annotatedvdb_tpu.ops.pack import transport_state
    from annotatedvdb_tpu.store.variant_store import (
        device_lookup_state,
        sidecar_state,
    )
    from annotatedvdb_tpu.utils.profiling import STARTUP_SECONDS
    from annotatedvdb_tpu.utils.runtime import compile_summary, device_summary

    return {
        "device": device_summary(),
        "compile": compile_summary(),
        # seconds per start-up phase of this PROCESS so far (cumulative: a
        # second load in one process adds only what it paid again):
        # ``device`` the backend's start, ``native`` the tokenizer's
        # build + dlopen, ``transport_probe`` the two parity probes,
        # ``programs`` the loader's warm-up — loading or compiling every
        # program of the load's shape; it CONTAINS the probes
        "startup": {k: round(v, 4) for k, v in STARTUP_SECONDS.items()},
        "kernel": selected_kernel(resolve=False),
        "pack_transport": transport_state(),
        "native_ingest": native.status(),
        "device_lookup": device_lookup_state(probe_base),
        # rows of the segments this load wrote, rows their sidecar walk
        # looked at, lines it wrote (store.variant_store.sidecar_lines)
        "sidecar": sidecar_state(sidecar_base),
        # rows whose mapping line this load wrote, by route: as bytes from
        # the chunk's columns (native/mapping.py), or through the scalar
        # strings (io/egress.py mapping_lines); tallied once a chunk
        "mapping": mapping_state(mapping_base),
        # FREQ-flagged rows whose value the build stage asked for, by
        # route: written by the native pass from the chunk's INFO spans
        # (native/freq.py), or through io/vcf.py freq_sidecar; tallied
        # once a chunk
        "freq": freq_state(freq_base),
    }


def run_record(script: str, input_path: str | None, params: dict,
               counters: dict, wall_seconds: float,
               stages: dict | None = None,
               queue_stalls: dict | None = None,
               error: BaseException | None = None,
               execution: dict | None = None) -> dict:
    """Build one run-ledger record (the ``type: "run"`` JSONL payload)."""
    rec = {
        "script": script,
        "input": input_path,
        "config_hash": config_hash(params),
        "params": {k: v for k, v in params.items()},
        "wall_seconds": round(wall_seconds, 4),
        "counters": {
            k: (int(v) if isinstance(v, (int, bool)) else v)
            for k, v in (counters or {}).items()
        },
        "status": "aborted" if error is not None else "completed",
    }
    if stages:
        rec["stages"] = stages
    if queue_stalls:
        rec["queue_stalls"] = queue_stalls
    if execution:
        rec["execution"] = execution
    if error is not None:
        rec["error_class"] = type(error).__name__
        rec["error"] = str(error)[:500]
    variants = (counters or {}).get("variant") or (counters or {}).get("update")
    if variants and wall_seconds > 0:
        rec["throughput_per_sec"] = round(variants / wall_seconds, 1)
    return rec


def export_counters(reg: MetricsRegistry, counters: dict,
                    loader: str) -> None:
    """Fold a loader's counter dict into the registry as counters (the
    per-load totals a textfile scrape reads)."""
    for key, v in (counters or {}).items():
        if key == "alg_id" or not isinstance(v, (int, float)):
            continue
        reg.counter(
            f"avdb_load_{key}_total", f"loader counter {key!r}",
            {"loader": loader},
        ).inc(v)


def export_stages(reg: MetricsRegistry, stages: dict, wall: float,
                  loader: str) -> None:
    """Per-stage busy seconds + items as labeled counters, wall as gauge."""
    for stage, rec in (stages or {}).items():
        labels = {"loader": loader, "stage": stage}
        reg.counter(
            "avdb_stage_busy_seconds_total",
            "busy seconds per pipeline stage (per-thread, sums past wall "
            "under overlap)", labels,
        ).inc(rec.get("seconds", 0.0))
        if rec.get("items"):
            reg.counter(
                "avdb_stage_items_total", "items per pipeline stage", labels,
            ).inc(rec["items"])
    if wall:
        reg.gauge(
            "avdb_load_wall_seconds", "wall clock of the load",
            {"loader": loader},
        ).set(wall)


def export_queue_stalls(reg: MetricsRegistry, stalls: dict,
                        loader: str) -> None:
    for boundary, rec in (stalls or {}).items():
        labels = {"loader": loader, "boundary": boundary}
        reg.counter(
            "avdb_queue_producer_block_seconds_total",
            "seconds the producer spent blocked on a full stage queue",
            labels,
        ).inc(rec.get("producer_block_s", 0.0))
        reg.counter(
            "avdb_queue_consumer_wait_seconds_total",
            "seconds the consumer spent waiting on an empty stage queue",
            labels,
        ).inc(rec.get("consumer_wait_s", 0.0))
        reg.gauge(
            "avdb_queue_max_depth", "high-water unconsumed items", labels,
        ).set(rec.get("max_depth", 0))


def export_store_stats(reg: MetricsRegistry, store) -> None:
    """Store residency gauges (rows per chromosome shard + total)."""
    try:
        total = 0
        for code, shard in sorted(store.shards.items()):
            from annotatedvdb_tpu.store.variant_store import chromosome_label

            reg.gauge(
                "avdb_store_rows", "resident rows per chromosome shard",
                {"chrom": chromosome_label(code)},
            ).set(shard.n)
            total += shard.n
        reg.gauge(
            "avdb_store_rows_total", "resident rows across all shards"
        ).set(total)
    except Exception as err:  # store introspection must never kill a load
        print(f"obs: store stats skipped ({err})", file=sys.stderr)


class ObsSession:
    """One load's telemetry lifecycle (see module docstring)."""

    def __init__(self, script: str, input_path: str | None, params: dict,
                 metrics_out: str | None = None,
                 trace_out: str | None = None,
                 registry: MetricsRegistry | None = None):
        self.script = script
        self.input_path = input_path
        self.params = dict(params or {})
        self.metrics_out = metrics_out
        self.trace_out = trace_out
        # fresh registry per session by default: the textfile then describes
        # THIS load, not the process's whole history
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = Tracer(process_name=script) if trace_out else None
        self._t0 = time.perf_counter()
        self._loader = None
        self._closed = False
        # baselines for the process-global fault/retry tallies: exports
        # report THIS session's delta, so two loads in one process never
        # double-attribute each other's counts
        from annotatedvdb_tpu.utils import faults as _faults
        from annotatedvdb_tpu.utils import retry as _retry

        self._faults_base = _faults.fired()
        self._retry_base = dict(_retry.stats)
        from annotatedvdb_tpu.store.variant_store import (
            probe_stats,
            sidecar_stats,
        )

        self._probe_base = dict(probe_stats)
        self._sidecar_base = dict(sidecar_stats)
        from annotatedvdb_tpu.io.egress import mapping_stats

        self._mapping_base = dict(mapping_stats)
        from annotatedvdb_tpu.io.vcf import freq_stats

        self._freq_base = dict(freq_stats)

    @classmethod
    def from_args(cls, script: str, args, params: dict) -> "ObsSession":
        return cls(
            script, getattr(args, "fileName", None), params,
            metrics_out=getattr(args, "metricsOut", None),
            trace_out=getattr(args, "traceOut", None),
        )

    def attach(self, loader):
        """Wire a loader into this session (chainable)."""
        self._loader = loader
        loader.obs = LoadObserver(
            self.registry, getattr(loader, "obs_name", type(loader).__name__)
        )
        timer = getattr(loader, "timer", None)
        if timer is not None and self.tracer is not None:
            timer.tracer = self.tracer
        return loader

    # -- closing ------------------------------------------------------------

    def finish(self, ledger, counters: dict, store=None) -> None:
        """Successful load end: export + append the run record."""
        self._close(ledger, counters, None, store)

    def abort(self, ledger, error: BaseException, store=None) -> None:
        """Failed load end: same exports, ``status: "aborted"`` + error
        class in the run record.  Call from the CLI's except path and
        re-raise — the ledger must witness crashes too."""
        counters = dict(getattr(self._loader, "counters", {}) or {})
        self._close(ledger, counters, error, store)

    def _close(self, ledger, counters, error, store) -> None:
        if self._closed:  # abort-then-finish double calls are harmless
            return
        self._closed = True
        wall = time.perf_counter() - self._t0
        loader = self._loader
        name = getattr(loader, "obs_name", self.script)
        timer = getattr(loader, "timer", None)
        stages = timer.as_dict() if timer is not None else None
        if timer is not None and timer.wall_seconds:
            wall = timer.wall_seconds
        stalls = dict(getattr(loader, "queue_stalls", {}) or {})
        try:
            export_counters(self.registry, counters, name)
            export_stages(self.registry, stages or {}, wall, name)
            export_queue_stalls(self.registry, stalls, name)
            # robustness surface: injected-fault fires, bounded-retry
            # attempts, quarantined-row totals (the 'rejected' counter is
            # already folded in via export_counters).  All deltas against
            # the session baseline — the underlying tallies are
            # process-global
            from annotatedvdb_tpu.utils import faults as _faults
            from annotatedvdb_tpu.utils import retry as _retry

            for point, count in _faults.fired().items():
                count -= self._faults_base.get(point, 0)
                if count > 0:
                    self.registry.counter(
                        "avdb_faults_fired_total",
                        "injected faults fired (AVDB_FAULT harness)",
                        {"point": point},
                    ).inc(count)
            retries = _retry.stats["retries"] - self._retry_base["retries"]
            if retries > 0:
                self.registry.counter(
                    "avdb_io_retries_total",
                    "transient-failure retries (I/O + device transfers)",
                    {"loader": name},
                ).inc(retries)
            gave_up = _retry.stats["gave_up"] - self._retry_base["gave_up"]
            if gave_up > 0:
                self.registry.counter(
                    "avdb_io_retries_exhausted_total",
                    "operations that failed after exhausting retries",
                    {"loader": name},
                ).inc(gave_up)
            if store is not None:
                export_store_stats(self.registry, store)
            if self.metrics_out:
                self.registry.write_textfile(self.metrics_out)
                self.registry.write_json(self.metrics_out + ".json")
            if self.tracer is not None and self.trace_out:
                self.tracer.save(self.trace_out)
        except Exception as err:
            print(f"obs: metric/trace export failed ({err})", file=sys.stderr)
        try:
            if ledger is not None:
                ledger.run(run_record(
                    self.script, self.input_path, self.params, counters,
                    wall, stages=stages, queue_stalls=stalls, error=error,
                    # an aborted load may have died OF the backend: its
                    # record must still land, without the block
                    execution=(
                        execution_facts(self._probe_base, self._sidecar_base,
                                        self._mapping_base, self._freq_base)
                        if error is None else None
                    ),
                ))
        except Exception as err:
            print(f"obs: run-ledger append failed ({err})", file=sys.stderr)
