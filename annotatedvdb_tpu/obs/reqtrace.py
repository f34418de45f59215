"""Request-scoped tracing: per-stage spans into a lock-free span ring.

The serve stack runs autonomously (live upserts, a maintenance daemon,
mesh-sharded workers); when p99 moves, aggregate counters say THAT it
moved, never WHY.  This module is the Dapper-shaped answer: every request
carries a trace id (minted at admission or adopted from the client's
``traceparent``/``X-Request-Id`` — see ``serve.http.resolve_trace_id``),
and the stages it passes through — its read off the socket, admission
wait, batcher queue wait, device execution, render, the WAL fsync of an
upsert ack, the wake of its coroutine, the write of its reply — each record
one span against that id.  A span is what a span is: name, start, end
(``perf_counter_ns``), the span that caused it (``parent``; None for a
stage of the request itself) and, through the trace it sits on, the
request's id.  Nothing records a bare duration.

A stage that a thread runs — ``device``, ``render``, ``wal_fsync``
(:func:`stage`) and a background writer's unit of work
(:func:`background_span`) — is also a ``jax.profiler.TraceAnnotation``
``avdb.serve.<stage>`` on that thread, with the request's ``trace_id`` and
``kind`` as arguments, so a profiler capture of the serving process holds
the request stages on the host lines of the same file as the device's
operations.  ``admission`` and ``queue`` are waits measured across threads
(arrival -> executor slot, enqueue -> drain), not scopes any one thread
sits in: they stay recorded spans, with a start and an end.  So do
:data:`LOOP_STAGES` — ``read``, ``wake``, ``reply`` — which run across the
event loop's callbacks; what the loop's thread itself was doing meanwhile
is ``avdb.loop.wait`` / ``avdb.loop.run`` (``obs/loopclock.py``).

Four export surfaces, one recording path:

- **the span ring** — a fixed-size per-worker ring of finished-request
  records.  Writes are LOCK-FREE: one shared ``itertools.count`` reserves
  a slot (thread-safe under the GIL), one list-item assignment publishes
  the immutable record tuple — request threads, the batcher drain, and
  the event loop all write without ever queueing behind each other, and
  a reader copying the list tolerates whatever it races (a slot is either
  the old record or the new one, never a hybrid).
- **stage histograms** — ``avdb_stage_seconds{stage=...}`` on the serving
  registry, one fixed-bucket histogram per stage, so dashboards see the
  queue-vs-device split continuously.
- **the slow-request log** — any request whose total exceeds
  ``AVDB_TRACE_SLOW_MS`` logs its full span breakdown (sampled tracing
  never hides the outlier: the threshold check runs on every finished
  trace that recorded).

- **the profiler's capture** — the annotations above, when one runs.

``AVDB_TRACE_SAMPLE`` (default 1.0) is the recording probability; 0
disarms span recording entirely (trace ids still mint and echo — the
header contract is part of the route surface).  ``chrome_events`` renders
the ring in the ``--traceOut`` tracer's Chrome trace-event format — every
span at its recorded start, nested under its parent — so
``GET /debug/trace`` merges request spans, background spans, and the
batcher tracer's drain spans into one timeline without a profiler.

Background writers join the same plane through the module-level sink
(:func:`set_background_sink` / :func:`background_span` /
:func:`lifecycle_event`): the maintenance daemon's passes, memtable
flushes, and compaction groups record spans on the ``background`` track
and lifecycle events into the flight recorder without the store layer
ever importing serve code.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import random
import threading
import time

from annotatedvdb_tpu.utils.profiling import annotation

#: the three stages that close a request's life on the event loop
#: (``serve/aio.py``): read = the request's head is complete -> its item is
#: handed back (head parse, the body's socket read, admission, the executor
#: submit or the batcher enqueue); wake = the work finished (the drain
#: resolved the futures, the executor half returned) -> the request's
#: coroutine resumes; reply = the coroutine resumed -> the bytes are handed
#: to the transport.  The flight summary drops these three first when a
#: slot has no room (``obs/flight.py``)
LOOP_STAGES = ("read", "wake", "reply")

#: the fixed stage vocabulary (`avdb_stage_seconds{stage=...}` series):
#: admission = arrival -> handed to execution (the executor pool's queue;
#: exec kinds only: a point read's is inside ``read``), queue = batcher
#: queue wait, device = engine execution of the (micro)batch, render =
#: response assembly after the engine answered, wal_fsync = the
#: durable-ack barrier of an upsert, background = one background-writer
#: span (flush / compaction group / daemon pass), total = whole request,
#: to the write of its reply
STAGES = ("read", "admission", "queue", "device", "render", "wal_fsync",
          "wake", "reply", "background", "total")

#: an upsert's stage beside ``wal_fsync``: the rest of its work under the
#: memtable lock (the membership check; the segment build, merge and
#: publish) — two spans around the fsync, one stage.  Only an upsert has
#: it, so it is not in the vocabulary every request's flight summary
#: makes room for
UPSERT_STAGES = ("upsert.apply",)

#: per-stage latency histogram edges (seconds): sub-100µs queue waits up
#: to multi-second background passes
STAGE_SECONDS_EDGES = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 10.0,
)

#: the ``device`` stage of a point/bulk lookup, split where the work
#: happens (``serve.engine.lookup_many``): id parsing; grouping, allele
#: encoding, hashing and the residency touch; the membership probe
#: (upload, program, fetch, breaker); rendering the found rows.  Series of
#: the same ``avdb_stage_seconds`` histogram, observed by the ENGINE once
#: per ``lookup_many`` call (a microbatch is one call, however many
#: requests share it) — sub-spans of ``device``, so never in a request's
#: ``stages`` (the flight recorder's slot has no room for more names)
LOOKUP_STAGES = ("lookup.parse", "lookup.hash", "lookup.probe",
                 "lookup.rows")

#: a ``POST /regions`` panel, split where the work happens: the body to
#: parsed specs and their chromosome groups (``serve.aio`` and
#: ``serve.engine.regions_serve``); each group's span search (index fetch,
#: pad, upload, program, fetch); locating every interval's rows and
#: building its page; rendering the rows — on whichever thread renders,
#: the executor's for a buffered body, the event loop's for a streamed
#: one.  Each observed by the ENGINE once a panel, summed over its groups
#: and chunks (the ``LOOKUP_STAGES`` discipline)
REGION_STAGES = ("regions.parse", "regions.spans", "regions.rows",
                 "regions.render")

#: a single ``GET /region`` read, split the same way
#: (``serve.engine.region_serve``): getting the chromosome's base and
#: delta interval indexes (a cache hit, or a build); locating the rows —
#: base span, delta span, their merge, the page; rendering the envelope's
#: text.  Observed by the ENGINE once a read it located (a region-LRU hit
#: locates nothing); the render only where the engine renders, not a
#: body streamed on the event loop
REGION_READ_STAGES = ("region.index", "region.locate", "region.render")


def stage_histograms(registry, stages) -> dict:
    """{stage: its ``avdb_stage_seconds`` series} on ``registry``."""
    return {
        stage: registry.histogram(
            "avdb_stage_seconds", STAGE_SECONDS_EDGES,
            "per-request stage latency from the request tracer",
            {"stage": stage},
        )
        for stage in stages
    }


def slow_ms_from_env() -> float:
    """``AVDB_TRACE_SLOW_MS`` — slow-request log threshold in ms (0 =
    disabled, the default)."""
    return max(float(os.environ.get("AVDB_TRACE_SLOW_MS", "") or 0), 0.0)


def sample_from_env() -> float:
    """``AVDB_TRACE_SAMPLE`` — fraction of requests recording span
    breakdowns (default 1.0; 0 disarms recording, trace ids still echo)."""
    v = float(os.environ.get("AVDB_TRACE_SAMPLE", "") or 1.0)
    return min(max(v, 0.0), 1.0)


class RequestTrace:
    """One request's in-flight span scratchpad.

    Plain data, touched only by the threads serving this one request (the
    front end and the batcher drain hand it off, never share it
    concurrently); it becomes an immutable ring record at
    :meth:`TraceRecorder.finish`."""

    __slots__ = ("trace_id", "kind", "t0_ns", "spans", "_subspans",
                 "mark_ns", "status")

    #: sub-span cap per request: a 4096-interval panel must not grow an
    #: unbounded span list (stages — parent None — are never dropped)
    MAX_SPANS = 64

    def __init__(self, trace_id: str, kind: str):
        self.trace_id = trace_id
        self.kind = kind
        self.t0_ns = time.perf_counter_ns()
        #: (name, start_ns, end_ns, parent) in recording order; parent
        #: None = a stage of the request, else the enclosing stage's name
        self.spans: list = []
        self._subspans = 0
        #: the stamp one wait hands to the next across threads and
        #: callbacks: set where the work finished (``wake`` starts), moved
        #: on where the coroutine resumed (``reply`` starts); 0 = never
        self.mark_ns = 0
        #: the reply's status, noted where its bytes are built; the trace
        #: is sealed with it once they are written
        self.status = 0

    def record(self, name: str, start_ns: int, end_ns: int,
               parent: str | None = None) -> None:
        """THE span call: one finished span on the ``perf_counter_ns``
        clock.  A stage (``parent`` None) feeds the stage histogram, the
        flight summary and the slow log at :meth:`TraceRecorder.finish`;
        a sub-span (per-chromosome-group engine work etc.) is ring/
        trace-dump detail — unbounded name cardinality has no place in a
        Prometheus export."""
        if parent is not None:
            if self._subspans >= self.MAX_SPANS:
                return
            self._subspans += 1
        self.spans.append((name, start_ns, end_ns, parent))

    def since(self, name: str, start_s: float) -> None:
        """A stage that began at ``time.perf_counter()`` reading
        ``start_s`` and ends now — for waits measured across threads
        (``admission``: arrival -> handed to execution), whose start was
        read before any trace existed."""
        self.record(name, int(start_s * 1e9), time.perf_counter_ns())

    def adopt(self, shared: "RequestTrace") -> None:
        """Take over the spans of a stage this request shared with others
        (a microbatch's one engine call serves every co-batched request:
        the continuous-batching reality)."""
        for span in shared.spans:  # finished spans: immutable, shared
            if span[3] is not None:
                if self._subspans >= self.MAX_SPANS:
                    continue
                self._subspans += 1
            self.spans.append(span)

    @property
    def stages(self) -> list:
        """[(stage, seconds)] — the request's own stages in recording
        order, as the histograms, the flight recorder's summary and the
        slow-request log read them."""
        return [(name, (end - start) / 1e9)
                for name, start, end, parent in self.spans if parent is None]


# -- thread-local active stage (engine sub-span attribution) ----------------

_active = threading.local()


@contextlib.contextmanager
def activate(trace: RequestTrace | None, parent: str = "device"):
    """Bind ``trace`` as THIS thread's active trace for the duration —
    the engine runs entirely on the calling thread (request thread,
    executor worker, or batcher drain), so deep layers attribute
    sub-spans (:func:`record_active`, parented to ``parent``) without
    threading a trace argument through every signature."""
    if trace is None:
        yield
        return
    prev = getattr(_active, "scope", None)
    _active.scope = (trace, parent)
    try:
        yield
    finally:
        _active.scope = prev


@contextlib.contextmanager
def stage(trace: RequestTrace | None, name: str):
    """One request stage on the thread that runs it: a recorded span
    (start, end, no parent) on ``trace``, the active scope of the engine's
    sub-spans, and a profiler annotation ``avdb.serve.<name>`` carrying the
    request's id.  ``trace`` None (unsampled) is transparent.  The span is
    recorded however the block ends — a stage that failed still ran."""
    if trace is None:
        yield
        return
    with annotation(f"avdb.serve.{name}", trace_id=trace.trace_id,
                    kind=trace.kind), activate(trace, name):
        start_ns = time.perf_counter_ns()
        try:
            yield
        finally:
            trace.record(name, start_ns, time.perf_counter_ns())


@contextlib.contextmanager
def shared_stage(traces, name: str):
    """A stage several requests share — a microbatch's ONE engine call on
    the drain thread serves every co-batched request.  Runs as one
    :func:`stage` (one annotation, one set of engine sub-spans); afterwards
    every sampled trace of ``traces`` adopts the stage span and its
    sub-spans.  Nothing sampled: transparent."""
    traces = [t for t in traces if t is not None]
    if not traces:
        yield
        return
    shared = RequestTrace(f"batch:{len(traces)}", traces[0].kind)
    try:
        with stage(shared, name):
            yield
    finally:
        for trace in traces:
            trace.adopt(shared)


def record_active(name: str, start_ns: int, end_ns: int) -> None:
    """Attach a sub-span to the calling thread's active stage (no-op
    outside any request — the engine never needs to know)."""
    scope = getattr(_active, "scope", None)
    if scope is not None:
        scope[0].record(name, start_ns, end_ns, parent=scope[1])


# -- background writers (store layer joins the plane without importing it) --

#: (span_sink, event_sink) — set by the serving/supervisor process that
#: owns a recorder; store-layer writers call the module functions and a
#: process without a recorder pays one ``is None`` check
_BACKGROUND: tuple | None = None


def set_background_sink(span_sink, event_sink) -> None:
    """Install the process's background sinks: ``span_sink(name, start_ns,
    end_ns, meta)`` records one background-track span, ``event_sink(name,
    detail)`` one lifecycle event (flight recorder).  Either may be None;
    pass ``(None, None)`` to clear."""
    global _BACKGROUND
    _BACKGROUND = (span_sink, event_sink) \
        if (span_sink is not None or event_sink is not None) else None


@contextlib.contextmanager
def background_span(name: str, **meta):
    """Time one background-writer unit of work (a memtable flush, a
    compaction group, a daemon pass) onto the ``background`` track, and
    onto the profiler's clock as ``avdb.serve.background`` (the unit in its ``span``
    arguments) on the writer's own thread.  The sink must never take the
    writer down: failures are swallowed — losing a span is always better
    than losing a flush."""
    sink = _BACKGROUND
    with annotation("avdb.serve.background", span=name):
        if sink is None or sink[0] is None:
            yield
            return
        start_ns = time.perf_counter_ns()
        try:
            yield
        finally:
            try:
                sink[0](name, start_ns, time.perf_counter_ns(), meta or None)
            except Exception:  # avdb: noqa[AVDB602] -- observability must never take down the background writer it observes
                pass


def lifecycle_event(name: str, detail: str) -> None:
    """Record one lifecycle event (brownout change, breaker trip, daemon
    pass transition, WAL rotation) into the process's flight recorder —
    a no-op without a sink, and a swallowed failure with one."""
    sink = _BACKGROUND
    if sink is None or sink[1] is None:
        return
    try:
        sink[1](name, detail)
    except Exception:  # avdb: noqa[AVDB602] -- observability must never take down the code path it observes
        pass


class TraceRecorder:
    """Per-worker span recording: the ring, the stage histograms, the
    slow-request log, and the flight-recorder feed.

    ``begin`` makes the sampling decision (one RNG draw when sampling is
    fractional; zero work when disarmed) and hands back a
    :class:`RequestTrace` or None; every code path downstream guards on
    None, so a disarmed recorder costs nothing but the guards."""

    SLOTS = 2048

    def __init__(self, registry=None, slots: int | None = None,
                 slow_ms: float | None = None, sample: float | None = None,
                 log=None, flight=None):
        n = self.SLOTS if slots is None else max(int(slots), 1)
        self.slots = n
        self.t0_ns = time.perf_counter_ns()
        self.t0_epoch = time.time()
        self.slow_s = (
            slow_ms_from_env() if slow_ms is None else max(float(slow_ms), 0.0)
        ) / 1000.0
        self.sample = (
            sample_from_env() if sample is None
            else min(max(float(sample), 0.0), 1.0)
        )
        self.log = log if log is not None else (lambda msg: None)
        self.flight = flight
        #: the serving loop's clock (``obs/loopclock.py``; the server sets
        #: it): a slow request's line says how long the loop's longest
        #: turn was and when it ended
        self.loop_clock = None
        #: the lock-free ring: slot reservation through the (GIL-atomic)
        #: counter, publication through one list-item assignment of an
        #: immutable tuple — concurrent writers never wait on each other
        self._ring: list = [None] * n
        self._seq = itertools.count()
        self._rng = random.Random(0xA5DB7)
        self._hist = {}
        self._m_slow = None
        if registry is not None:
            self._hist = stage_histograms(registry,
                                          STAGES + UPSERT_STAGES)
            self._m_slow = registry.counter(
                "avdb_trace_slow_requests_total",
                "requests whose total latency exceeded AVDB_TRACE_SLOW_MS",
            )

    # -- recording ----------------------------------------------------------

    def begin(self, trace_id: str, kind: str) -> RequestTrace | None:
        if self.sample <= 0.0:
            return None
        if self.sample < 1.0 and self._rng.random() >= self.sample:
            return None
        return RequestTrace(trace_id, kind)

    def finish(self, trace: RequestTrace | None, status: int = 200) -> None:
        """Seal one request's trace: publish the ring record, feed the
        stage histograms, log it when slow, and write the flight-recorder
        request summary."""
        if trace is None:
            return
        now_ns = time.perf_counter_ns()
        total = (now_ns - trace.t0_ns) / 1e9
        hist = self._hist
        # a stage's seconds over its spans (a stage split around another,
        # as an upsert's apply is around its fsync, counts once)
        summed: dict = {}
        for name, start_ns, end_ns, parent in trace.spans:
            if parent is None:
                summed[name] = summed.get(name, 0) + end_ns - start_ns
        stages = tuple((name, ns / 1e9) for name, ns in summed.items())
        for name, seconds in stages:
            h = hist.get(name)
            if h is not None:
                h.observe(seconds)
        if hist:
            hist["total"].observe(total)
        self._ring[next(self._seq) % self.slots] = (
            trace.trace_id, trace.kind, int(status),
            trace.t0_ns, total,
            stages, tuple(trace.spans),
        )
        if self.slow_s and total >= self.slow_s:
            if self._m_slow is not None:
                self._m_slow.inc()
            breakdown = " ".join(
                f"{stage}={seconds * 1000:.2f}ms"
                for stage, seconds in stages
            )
            # sub-spans summed by name: which part of a stage was slow
            subs: dict = {}
            for name, start_ns, end_ns, parent in trace.spans:
                if parent is not None:
                    subs[name] = subs.get(name, 0) + end_ns - start_ns
            detail = " ".join(f"{name}={ns / 1e6:.2f}ms"
                              for name, ns in subs.items())
            self.log(
                f"slow request trace={trace.trace_id} kind={trace.kind} "
                f"status={status} total={total * 1000:.2f}ms {breakdown}"
                + (f" spans={len(trace.spans) - len(stages)} [{detail}]"
                   if subs else "")
                + (f" {self.loop_clock.max_turn_note()}"
                   if self.loop_clock is not None else "")
            )
        if self.flight is not None:
            try:
                self.flight.request(
                    trace.trace_id, trace.kind, int(status), total, stages,
                )
            except Exception:  # avdb: noqa[AVDB602] -- the flight recorder must never fail the request it records
                pass

    def background(self, name: str, start_ns: int, end_ns: int,
                   meta=None) -> None:
        """One background-track span (the module sink's target): same
        ring, kind ``background``, plus the background stage histogram."""
        seconds = (end_ns - start_ns) / 1e9
        record = ("-", "background", 0, int(start_ns), seconds,
                  (("background", seconds),),
                  (("background", int(start_ns), int(end_ns), None),
                   (name, int(start_ns), int(end_ns), "background")))
        self._ring[next(self._seq) % self.slots] = record
        h = self._hist.get("background")
        if h is not None:
            h.observe(seconds)
        if self.flight is not None:
            try:
                detail = f"{name} {seconds * 1000:.1f}ms"
                if meta:
                    detail += " " + ",".join(
                        f"{k}={v}" for k, v in sorted(meta.items())
                    )
                self.flight.event("background", detail)
            except Exception:  # avdb: noqa[AVDB602] -- the flight recorder must never fail the writer it records
                pass

    # -- export -------------------------------------------------------------

    def records(self) -> list[tuple]:
        """Finished-request records, oldest-first best effort.  The copy
        races in-flight writers by design: each slot is either one record
        or another, never torn (immutable tuples, atomic item reads)."""
        snap = list(self._ring)
        return sorted(
            (r for r in snap if r is not None), key=lambda r: r[3]
        )

    def chrome_events(self, base_ns: int | None = None) -> list[dict]:
        """The ring as Chrome trace events in the ``--traceOut`` tracer's
        track format: requests on one named track, background spans on
        another, every span a complete (``X``) event at its RECORDED start
        — a span opened 5 ms into a request is drawn 5 ms in — with its
        parent in ``args`` (viewers nest by containment, which recorded
        starts give).  Merge the list with a
        :class:`~annotatedvdb_tpu.obs.trace.Tracer`'s events (same
        ``base_ns`` timebase: both clocks are ``perf_counter_ns``) and the
        whole worker shows on one timeline."""
        base = self.t0_ns if base_ns is None else int(base_ns)
        pid = os.getpid()
        req_tid, bg_tid = 1, 2
        events: list[dict] = [
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": req_tid,
             "ts": 0, "args": {"name": "requests"}},
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": bg_tid,
             "ts": 0, "args": {"name": "background"}},
        ]
        for trace_id, kind, status, t0_ns, total, _stages, spans \
                in self.records():
            tid = bg_tid if kind == "background" else req_tid
            events.append({
                "ph": "X", "name": kind, "cat": "request", "pid": pid,
                "tid": tid, "ts": (t0_ns - base) / 1000.0,
                "dur": total * 1e6,
                "args": {"trace_id": trace_id, "status": status},
            })
            for name, start_ns, end_ns, parent in spans:
                args = {"trace_id": trace_id}
                if parent is not None:
                    args["parent"] = parent
                events.append({
                    "ph": "X", "name": name,
                    "cat": "stage" if parent is None else "span",
                    "pid": pid, "tid": tid,
                    "ts": (start_ns - base) / 1000.0,
                    "dur": (end_ns - start_ns) / 1000.0, "args": args,
                })
        return events
