"""Pipeline observability: per-stage timers + jax.profiler trace capture.

The reference's only performance instrumentation is ad-hoc ``datetime.now()``
pairs around buffer-build vs COPY in debug mode
(``Load/bin/load_vcf_file.py:108-111,136-140,165-168``).  Here every loader
carries a :class:`StageTimer` that attributes wall-clock to named pipeline
stages (ingest / annotate / lookup / egress / append / flush) and can emit
rate summaries at a log cadence.

Every stage is also a ``jax.profiler.TraceAnnotation`` (:func:`annotation`)
on the thread that runs it, so under any ``jax.profiler`` capture — the
``--profile <dir>`` flag (:func:`device_trace`) or one started around the
program — the program's stages sit on the host lines of the same
``.xplane.pb`` as the device's operations: one file, one clock.  With no
capture running an annotation is a flag test (under a microsecond), which
is why spans stay at stage granularity — a handful per chunk, never per
row.
"""

from __future__ import annotations

import contextlib
import threading
import time

_TraceAnnotation = None


def trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use — modules
    that only time things (``utils.pipeline``, ``obs.reqtrace``) stay
    importable without jax.  Its ``is_enabled()`` is the profiler's own
    flag for "a capture is recording": what a span too frequent to create
    for nothing asks first."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation


def annotation(name: str, **args):
    """A ``jax.profiler.TraceAnnotation`` named ``name``: a span on the
    calling thread's host line of whatever profiler capture is running,
    ``args`` shown as the event's stats.  The one place the program's
    spans (load stages, queue waits, request stages, start-up phases, the
    serving loop's turns) reach the profiler's clock."""
    return trace_annotation()(name, **args)


#: seconds per start-up phase of this process (device start, native
#: tokenizer, transport probe, compiled programs), cumulative: a second
#: load in one process adds only what it paid again.  The run record's
#: ``execution.startup``
STARTUP_SECONDS: dict[str, float] = {}


@contextlib.contextmanager
def startup_phase(name: str):
    """Time one start-up phase into :data:`STARTUP_SECONDS` and onto the
    profiler's clock as ``avdb.startup.<name>``.  Records only: nothing is
    reordered or skipped."""
    with annotation(f"avdb.startup.{name}"):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            STARTUP_SECONDS[name] = (
                STARTUP_SECONDS.get(name, 0.0) + time.perf_counter() - t0
            )


class StageTimer:
    """Accumulates busy seconds + item counts per named stage, plus the
    wall-clock of the enclosing run.

    Usage::

        with timer.wall():                      # once around the whole load
            with timer.stage("annotate", items=batch.n):
                ...

    One span, three sinks: the busy-seconds table (run record ``stages``,
    ``avdb_stage_busy_seconds_total``), the optional ``--traceOut``
    :class:`~annotatedvdb_tpu.obs.trace.Tracer`, and a profiler annotation
    ``avdb.load.<stage>`` (``avdb.load`` for :meth:`wall`) on the thread
    that does the work.

    Stages may run CONCURRENTLY on pipeline threads (overlapped executor:
    ingest / dispatch / process / store-writer), so accumulation is
    lock-guarded and per-stage seconds are *busy* time, not exclusive
    wall-clock: with real overlap ``total()`` exceeds ``wall_seconds``.
    ``overlap()`` reports that ratio — it is how the stage table stays
    honest once stages stop being serial (a stage can no longer hide
    inside another's measurement, and the sum no longer bounds the wall).

    ``summary()`` reports seconds, share of measured busy time, items/sec,
    and — when a wall window was recorded — the busy/wall overlap factor.
    """

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._lock = threading.Lock()
        self.seconds: dict[str, float] = {}
        self.items: dict[str, int] = {}
        #: wall-clock of the runs wrapped in ``wall()`` (accumulates across
        #: files like the per-stage counters do)
        self.wall_seconds: float = 0.0
        #: optional :class:`annotatedvdb_tpu.obs.trace.Tracer`; when set,
        #: every stage span is mirrored as a B/E trace-event pair on the
        #: thread that ran it — the no-profiler export of the same spans
        self.tracer = None

    @contextlib.contextmanager
    def stage(self, name: str, items: int = 0):
        tracer = self.tracer
        if tracer is not None:
            tracer.begin(name)
        with annotation(f"avdb.load.{name}", items=items):
            t0 = self._clock()
            try:
                yield
            finally:
                dt = self._clock() - t0
                with self._lock:
                    self.seconds[name] = self.seconds.get(name, 0.0) + dt
                    self.items[name] = self.items.get(name, 0) + items
                if tracer is not None:
                    tracer.end(name)

    @contextlib.contextmanager
    def wall(self):
        """Record one run's wall-clock (the overlapped-critical-path
        denominator for ``overlap()``)."""
        tracer = self.tracer
        if tracer is not None:
            tracer.begin("load")
        with annotation("avdb.load"):
            t0 = self._clock()
            try:
                yield
            finally:
                dt = self._clock() - t0
                with self._lock:
                    self.wall_seconds += dt
                if tracer is not None:
                    tracer.end("load")

    def total(self) -> float:
        with self._lock:
            return sum(self.seconds.values())

    def overlap(self) -> float | None:
        """Busy-seconds / wall-seconds across all recorded runs, or None
        when no wall window was recorded.  1.0 = fully serial; >1.0 = the
        pipeline genuinely ran stages concurrently."""
        with self._lock:
            wall = self.wall_seconds
            busy = sum(self.seconds.values())
        if not wall:
            return None
        return busy / wall

    def summary(self) -> str:
        with self._lock:  # one snapshot: total must equal sum(snapshot),
            # and wall is read under the same lock — a wall() exit on
            # another pipeline thread mid-summary must not tear the line
            snapshot = dict(self.seconds)
            items = dict(self.items)
            wall = self.wall_seconds
        total = sum(snapshot.values()) or 1e-12
        parts = []
        for name in sorted(snapshot, key=snapshot.get, reverse=True):
            s = snapshot[name]
            line = f"{name}: {s:.2f}s ({100 * s / total:.0f}%)"
            if items.get(name) and s > 0:
                line += f" {items[name] / s:,.0f}/s"
            parts.append(line)
        if wall:
            parts.append(
                f"wall: {wall:.2f}s "
                f"(busy {total:.2f}s, {total / wall:.2f}x overlap)"
            )
        return " | ".join(parts)

    def as_dict(self) -> dict:
        with self._lock:
            return {
                name: {
                    "seconds": round(self.seconds[name], 4),
                    "items": self.items.get(name, 0),
                }
                for name in self.seconds
            }

    def wall_dict(self) -> dict:
        """Wall vs busy accounting for bench records: per-stage seconds are
        busy time on their pipeline thread; ``overlap`` > 1 proves stages
        actually ran concurrently instead of the sum hiding inside the wall."""
        with self._lock:
            busy = sum(self.seconds.values())
            wall = self.wall_seconds
        out = {
            "wall_seconds": round(wall, 4),
            "busy_seconds": round(busy, 4),
        }
        if wall:
            out["overlap"] = round(busy / wall, 3)
        return out


class DeviceOccupancy:
    """Union coverage of per-chunk device in-flight windows.

    Each dispatched chunk contributes the interval [dispatch-enqueue,
    results-forced] — the window in which that chunk's device programs can
    be executing.  The union of those intervals over the load, divided by
    the load's wall-clock, approximates device occupancy from the host
    side without a profiler attach; ``idle_fraction`` is its complement —
    the headline the bench's ``device_idle_fraction`` reports.  It is an
    in-flight-window approximation (the window includes queue wait, so it
    over-counts busy and the reported idle is a LOWER bound on true device
    idleness); its job is trend-grade proof that the device is no longer
    idle-dominant, not a cycle count.

    ``record`` is called from one thread (the process stage) in
    force-completion order; intervals may still START out of order under
    shuffled scheduling, so starts are clamped to the high-water mark of
    closed coverage (never double-counted)."""

    __slots__ = ("busy_s", "_start", "_end")

    def __init__(self):
        self.busy_s = 0.0
        self._start = None  # currently-open merged interval
        self._end = 0.0

    def record(self, t0: float, t1: float) -> None:
        if t1 <= t0:
            return
        if self._start is None:
            self._start, self._end = t0, t1
            return
        if t0 <= self._end:  # overlaps/extends the open interval
            if t1 > self._end:
                self._end = t1
        else:  # gap: close the open interval, start a new one
            self.busy_s += self._end - self._start
            self._start = max(t0, self._end)
            self._end = t1

    def total(self) -> float:
        """Union busy seconds recorded so far."""
        if self._start is None:
            return self.busy_s
        return self.busy_s + (self._end - self._start)

    def idle_fraction(self, wall_seconds: float) -> float:
        """1 − busy/wall, clamped to [0, 1]; 0.0 when no wall elapsed."""
        if wall_seconds <= 0:
            return 0.0
        return max(0.0, min(1.0, 1.0 - self.total() / wall_seconds))


def stall_summary(queue_stalls: dict, wall_seconds: float | None = None) -> str:
    """Human line for the backpressure accounting
    (:class:`annotatedvdb_tpu.utils.pipeline.StageStats` dicts keyed by
    boundary name): producer-block = the boundary's consumer is the
    bottleneck, consumer-wait = its producer starved it.  With a wall
    window the dominant side is expressed as % of wall — the printed fact
    that turns "overlap 3.1x" into "dispatch starved 40% of wall"."""
    parts = []
    for name, rec in (queue_stalls or {}).items():
        blocked = rec.get("producer_block_s", 0.0)
        waited = rec.get("consumer_wait_s", 0.0)
        bits = []
        if blocked >= 0.005:
            b = f"blocked {blocked:.2f}s"
            if wall_seconds:
                b += f" ({100 * blocked / wall_seconds:.0f}% of wall)"
            bits.append(b)
        if waited >= 0.005:
            w = f"starved {waited:.2f}s"
            if wall_seconds:
                w += f" ({100 * waited / wall_seconds:.0f}% of wall)"
            bits.append(w)
        if not bits:
            bits.append("no stalls")
        parts.append(f"{name}: " + ", ".join(bits))
    return " | ".join(parts) if parts else "no stage queues ran"


@contextlib.contextmanager
def device_trace(trace_dir: str | None):
    """``jax.profiler`` capture into ``trace_dir`` when set; no-op
    otherwise.  The Python tracer is off (a Python-heavy load would be
    millions of call events) and the host tracer is at level 2: the
    capture holds XLA's own host spans, the program's ``avdb.*``
    annotations and the device's operations — the same file the
    benchmark's children capture."""
    if not trace_dir:
        yield
        return
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with jax.profiler.trace(trace_dir, profiler_options=options):
        yield


@contextlib.contextmanager
def bulk_load_gc():
    """Suspend the cyclic GC for the duration of a bulk load.

    Load hot loops allocate millions of objects that mostly SURVIVE (store
    annotation values): generational collection then rescans the growing
    survivor pile every few ten-thousand allocations for zero reclaimed
    garbage — measured ~10-15% of the VEP update leg.  The standard bulk
    discipline applies: disable, run, one collect afterwards.  Re-entrant
    (a nested loader — e.g. an update load's novel-insert path — must not
    re-enable mid-outer-load) and exception-safe.  AVDB_LOAD_GC=1 keeps
    the collector on for debugging."""
    import gc
    import os

    if os.environ.get("AVDB_LOAD_GC") == "1" or not gc.isenabled():
        yield
        return
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.collect()
