"""Unified telemetry: metrics registry, host trace timeline, run ledger,
request tracing, and the crash flight recorder.

Five layers, one import surface:

- :mod:`~annotatedvdb_tpu.obs.metrics` — thread-safe counters / gauges /
  fixed-bucket histograms with JSON-snapshot and Prometheus-textfile export
  (``--metricsOut``), plus the fleet snapshot merge (``?fleet=1``);
- :mod:`~annotatedvdb_tpu.obs.trace` — Chrome trace-event host spans, one
  track per pipeline thread (``--traceOut``): the export that needs no
  profiler, on its own clock;
- :mod:`~annotatedvdb_tpu.obs.reqtrace` — request-scoped tracing: spans
  with a start, an end and a parent into the lock-free per-worker span
  ring, ``avdb_stage_seconds`` stage histograms, the slow-request log,
  and the background-writer sink;
- :mod:`~annotatedvdb_tpu.obs.flight` — the mmap'd crash flight recorder
  (last-N request summaries + lifecycle events, SIGKILL-durable,
  supervisor-harvested, ``doctor flight``);
- :mod:`~annotatedvdb_tpu.obs.session` — the per-CLI lifecycle gluing
  metrics+trace to a load and appending the ``type: "run"`` ledger
  record.

Every span these layers time — a loader stage
(``utils.profiling.StageTimer``), a queue wait (``utils.pipeline``), a
request stage (``reqtrace.stage``), a lookup sub-stage (``serve.engine``)
— is also a ``jax.profiler.TraceAnnotation`` named ``avdb.*`` on the
thread that does the work, so any ``jax.profiler`` capture (``--profile``)
holds the program's stages and the device's operations in one file on one
clock.  There is no second tracer for that: the annotation is entered where
the span is already opened.

Backpressure gauges live with the queues themselves
(:class:`annotatedvdb_tpu.utils.pipeline.BoundedStage` ``.stats``) and are
exported through the session.
"""

from annotatedvdb_tpu.obs.flight import FlightRecorder
from annotatedvdb_tpu.obs.metrics import (
    CHUNK_ROW_EDGES,
    CHUNK_SECONDS_EDGES,
    Counter,
    Gauge,
    Histogram,
    LoadObserver,
    MetricsRegistry,
)
from annotatedvdb_tpu.obs.reqtrace import RequestTrace, TraceRecorder
from annotatedvdb_tpu.obs.session import (
    ObsSession,
    add_obs_args,
    config_hash,
    run_record,
)
from annotatedvdb_tpu.obs.trace import Tracer

__all__ = [
    "CHUNK_ROW_EDGES",
    "CHUNK_SECONDS_EDGES",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "LoadObserver",
    "MetricsRegistry",
    "ObsSession",
    "RequestTrace",
    "TraceRecorder",
    "Tracer",
    "add_obs_args",
    "config_hash",
    "run_record",
]
