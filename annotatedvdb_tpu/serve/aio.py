"""The serving front end: an asyncio event-loop server.

The route surface of ``serve/http.py`` on ONE event loop: requests parse
in-line, point lookups submit to the loop-native continuous batcher
(:class:`LoopBatcher` -> an asyncio future), and a connection costs a
coroutine, not a thread — so thousands of in-flight lookups coalesce
into device microbatches without a parked thread each (Endeavor's
serving argument: keep the device batches large, keep the host thin).

**Pipelining.**  Connections are fully pipelined: the read loop keeps
parsing requests while earlier ones execute, and a per-connection writer
task emits responses strictly in request order (HTTP/1.1 semantics), up
to ``PIPELINE_DEPTH`` in flight per connection — which is exactly how
thousands of lookups from a handful of sockets fill 256-query device
microbatches instead of trickling in one per round trip.

Route grammar, payload builders and message constants come from
``serve/http.py``; what this layer adds:

- **weighted per-client admission** — a token bucket per client key
  (``X-Client-Id`` header scoped to the peer address — at most
  ``PEER_KEY_CAP`` distinct id buckets per peer, so rotating the header
  degrades to the peer's aggregate bucket instead of minting a fresh
  burst per request; no header means the peer bucket), refilling at
  ``AVDB_SERVE_CLIENT_RATE`` requests/sec times the client's declared
  ``X-Client-Weight`` (clamped to [1, 16]).  Over-rate clients get the
  same 429 + Retry-After the queue bound produces, so a hog degrades to
  fast rejections while well-behaved clients ride their weighted share;
  ``0`` (default) disables per-client limiting — the global
  queue/inflight bounds still hold.
- **chunked region streaming** — region bodies above
  ``AVDB_SERVE_STREAM_THRESHOLD`` rows (default 2048) stream with
  ``Transfer-Encoding: chunked``, rows rendered lazily off a
  :class:`~annotatedvdb_tpu.serve.engine.RegionPage` generator: a
  gene-panel-sized region no longer buffers its whole body in RSS.
  Paging rides the same machinery (``?cursor=`` starts a walk; the
  envelope's ``next`` token continues it).
- **coalesced snapshot freshness** — one manifest ``stat`` per
  ``AVDB_SERVE_SNAPSHOT_TTL_MS`` window, and the (rare) generation load
  runs on the executor pool so a commit never stalls the loop.

Bulk and region execution (CPU-bound rendering) runs on a small thread
pool; the ``serve.accept`` fault point fires per accepted connection, so
the matrix can pin that an accept-path failure costs exactly one
connection (raise) or one worker (kill — the fleet's restart case).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import mmap
import os
import selectors
import struct
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from urllib.parse import unquote, urlparse

from annotatedvdb_tpu.export.stream import (
    STREAM_ROUTE as EXPORT_STREAM_ROUTE,
    parse_stream_query,
    stream_payload,
)
from annotatedvdb_tpu.obs import reqtrace as reqtrace_mod
from annotatedvdb_tpu.obs.loopclock import PARTS, LoopClock, TimedSelector
from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.serve.batcher import QueueFull, batch_annotation
from annotatedvdb_tpu.serve.engine import (
    QueryEngine,
    QueryError,
    parse_variant_id,
)
from annotatedvdb_tpu.serve.http import (
    _RETURNED_RE,
    BULK_BODY_ERROR,
    MSG_BROWNOUT_BULK,
    MSG_BROWNOUT_EXPORT,
    MSG_BROWNOUT_REGION,
    MSG_BROWNOUT_STATS,
    MSG_BROWNOUT_UPSERT,
    MSG_CAPACITY_BULK,
    MSG_CAPACITY_EXPORT,
    MSG_CAPACITY_REGION,
    MSG_CAPACITY_STATS,
    HISTORY_ROUTE,
    MSG_CAPACITY_UPSERT,
    MSG_DEADLINE_ADMISSION,
    MSG_DEADLINE_EXECUTE,
    REGIONS_BODY_ERROR,
    REPL_MANIFEST_ROUTE,
    REPL_SEGMENT_ROUTE,
    REPL_WAL_ROUTE,
    STATS_BODY_ERROR,
    STATS_ROUTE,
    TRACE_HEADER,
    UPSERT_BODY_ERROR,
    UPSERT_ROUTE,
    ServeContext,
    alerts_payload,
    chaos_enabled_from_env,
    debug_trace_payload,
    healthz_payload,
    metrics_history_payload,
    metrics_payload,
    parse_region_params,
    parse_regions_body,
    parse_stats_body,
    parse_upsert_body,
    readyz_payload,
    repl_file_response,
    repl_manifest_payload,
    resolve_trace_id,
    stats_payload,
)
from annotatedvdb_tpu.serve.fleet import HB_SLOT
from annotatedvdb_tpu.serve.resilience import DeadlineExceeded, DeviceBreaker
from annotatedvdb_tpu.serve.snapshot import SnapshotManager
from annotatedvdb_tpu.utils import faults

#: request body cap (bulk id lists); larger bodies are 413, never buffered
MAX_BODY = 1 << 26

#: max responses in flight per connection before the read loop stops
#: parsing (TCP backpressure to the client) — bounds per-connection memory
PIPELINE_DEPTH = 512

#: client-weight clamp: a header is a claim, not a blank check
MAX_CLIENT_WEIGHT = 16

#: response head templates (status line); bodies are JSON
_STATUS = {
    200: b"HTTP/1.1 200 OK\r\n",
    400: b"HTTP/1.1 400 Bad Request\r\n",
    403: b"HTTP/1.1 403 Forbidden\r\n",
    404: b"HTTP/1.1 404 Not Found\r\n",
    413: b"HTTP/1.1 413 Payload Too Large\r\n",
    429: b"HTTP/1.1 429 Too Many Requests\r\n",
    431: b"HTTP/1.1 431 Request Header Fields Too Large\r\n",
    500: b"HTTP/1.1 500 Internal Server Error\r\n",
    501: b"HTTP/1.1 501 Not Implemented\r\n",
    503: b"HTTP/1.1 503 Service Unavailable\r\n",
    504: b"HTTP/1.1 504 Gateway Timeout\r\n",
    507: b"HTTP/1.1 507 Insufficient Storage\r\n",
}

_CT_JSON = b"Content-Type: application/json\r\nContent-Length: "
_CT_TEXT = b"Content-Type: text/plain; version=0.0.4\r\nContent-Length: "
_CT_BIN = b"Content-Type: application/octet-stream\r\nContent-Length: "

#: rows rendered between flow-control drains while streaming a region
_STREAM_ROWS_PER_CHUNK = 256

#: coalescing-buffer bound for the per-connection writer: responses
#: batch into one transport write up to this many bytes, then flush —
#: a pipelined batch of large bulk responses must never accumulate
#: batch-count x response-size bytes before the first write
_WRITE_HIGH_WATER = 1 << 18


def _client_rate_from_env() -> float:
    """``AVDB_SERVE_CLIENT_RATE`` — admitted requests/sec per weight unit
    (0 disables per-client limiting)."""
    return max(float(os.environ.get("AVDB_SERVE_CLIENT_RATE", "") or 0), 0.0)


def _stream_threshold_from_env() -> int:
    """``AVDB_SERVE_STREAM_THRESHOLD`` — region row count above which the
    response streams chunked instead of buffering (default 2048)."""
    return max(
        int(os.environ.get("AVDB_SERVE_STREAM_THRESHOLD", "") or 2048), 0
    )


def _resp(status: int, body: str, retry_after: int | None = None,
          content_type: bytes = _CT_JSON) -> bytes:
    """One fully-formed HTTP/1.1 response."""
    payload = body.encode()
    head = _STATUS[status] + content_type + str(len(payload)).encode()
    if retry_after is not None:
        head += b"\r\nRetry-After: " + str(retry_after).encode()
    elif status in (429, 503):
        head += b"\r\nRetry-After: 1"
    return head + b"\r\n\r\n" + payload


def _error(status: int, message: str,
           retry_after: int | None = None) -> bytes:
    return _resp(status, json.dumps({"error": message}), retry_after)


_TRACE_HEADER_B = TRACE_HEADER.encode() + b": "


def _add_trace(resp: bytes, trace_id: str | None) -> bytes:
    """Splice the trace-id echo header into a fully-formed response —
    one insertion after the status line, so every route's prebuilt bytes
    gain the header without threading the id through ``_resp``'s thirty
    call sites."""
    if not trace_id:
        return resp
    i = resp.find(b"\r\n")
    if i < 0:
        return resp
    return (resp[:i + 2] + _TRACE_HEADER_B + trace_id.encode("latin-1")
            + b"\r\n" + resp[i + 2:])


def _status_of(resp: bytes) -> int:
    """The status code of a prebuilt response (``HTTP/1.1 NNN ...``) —
    the writer finishes exec traces centrally, and the bytes already
    know their status."""
    try:
        return int(resp[9:12])
    except ValueError:
        return 0


class LoopBatcher:
    """Loop-native continuous batching of concurrent point lookups.

    The drain runs ON the event loop: submissions append to a list, a
    ``call_later(max_wait_s)`` timer (or a full batch) triggers the
    drain, and the engine executes the microbatch inline.  A drain on a
    thread of its own would cost every request two cross-thread handoffs
    (submit -> drain thread -> loop wakeup), each a scheduler timeslice
    boundary on a host with as many hot threads as cores; a few
    milliseconds of loop occupancy buys zero handoffs, zero extra hot
    threads, and the same coalescing.

    Queries are grammar-validated at submission so a malformed id fails
    ONLY its own caller — co-batched strangers never share a client's
    parse error.  A real engine failure mid-drain fails that one batch
    (every waiter gets the root cause) and the loop keeps serving; the
    ``serve.batch`` fault point fires before each drain so the matrix
    pins exactly that behavior."""

    def __init__(self, engine, max_batch: int | None = None,
                 max_wait_s: float | None = None,
                 max_queue: int | None = None,
                 tracer=None, registry=None, timeout_s: float = 30.0):
        from annotatedvdb_tpu.serve.batcher import resolve_batch_knobs

        self.engine = engine
        self.max_batch, self.max_wait_s, self.max_queue = \
            resolve_batch_knobs(max_batch, max_wait_s, max_queue)
        self.timeout_s = timeout_s
        self.tracer = tracer
        self._pending: list = []  # (future, qid, parsed), loop-only state
        self._timer = None
        self._drain_soon = False  # a call_soon(_drain) is already queued
        self._loop: asyncio.AbstractEventLoop | None = None
        self._closed = False
        self._batches = 0
        self._queries = 0
        self._max_depth = 0
        #: ns the loop's thread spent in ``_drain`` (the loop clock's
        #: ``drain_s``): two clock reads a drain
        self.drain_ns = 0
        if registry is not None:
            from annotatedvdb_tpu.serve.batcher import BATCH_FILL_EDGES

            self._m_batches = registry.counter(
                "avdb_serve_batches_total", "batcher drains executed"
            )
            self._m_fill = registry.histogram(
                "avdb_serve_batch_fill", BATCH_FILL_EDGES,
                "fraction of max_batch used per drain",
            )
            self._m_depth = registry.gauge(
                "avdb_serve_queue_depth", "pending queries awaiting a drain"
            )
            self._m_deadline_shed = registry.counter(
                "avdb_deadline_shed_total",
                "requests shed because their deadline budget ran out",
                {"stage": "batcher"},
            )
        else:
            self._m_batches = self._m_fill = self._m_depth = None
            self._m_deadline_shed = None

    # -- caller side (event loop only) --------------------------------------

    def depth(self) -> int:
        return len(self._pending)

    def submit_future(self, variant_id: str,
                      deadline_t: float | None = None,
                      trace=None) -> asyncio.Future:
        """Enqueue one point query; returns the future of its JSON text
        (or None).  ``QueueFull`` (the admission bound) and
        ``QueryError`` (bad grammar, validated HERE, before the queue)
        raise synchronously, in the caller.  A pending whose
        ``deadline_t`` (absolute monotonic) lapses before its drain
        fails with ``DeadlineExceeded`` instead of occupying device
        work — its admission slot releases."""
        if self._closed:
            raise RuntimeError("batcher is closed")
        parsed = parse_variant_id(variant_id)
        if len(self._pending) >= self.max_queue:
            raise QueueFull(
                f"serve queue full ({self.max_queue} pending queries)"
            )
        if self._loop is None:
            self._loop = asyncio.get_running_loop()
        fut = self._loop.create_future()
        self._pending.append((
            fut, variant_id, parsed, deadline_t, trace,
            time.perf_counter_ns() if trace is not None else 0,
        ))
        depth = len(self._pending)
        if depth > self._max_depth:
            self._max_depth = depth
        if depth >= self.max_batch:
            # one queued drain serves the whole burst: a second call_soon
            # here would leave an orphan handle behind that later fires
            # into a fresh single-item queue and defeats its max_wait
            # coalescing window
            if not self._drain_soon:
                if self._timer is not None:
                    self._timer.cancel()
                    self._timer = None
                self._drain_soon = True
                self._loop.call_soon(self._drain)
        elif self._timer is None and not self._drain_soon:
            self._timer = self._loop.call_later(self.max_wait_s, self._drain)
        return fut

    def _drain(self) -> None:
        t0 = time.perf_counter_ns()
        try:
            self._drain_pending()
        finally:
            self.drain_ns += time.perf_counter_ns() - t0

    def _drain_pending(self) -> None:
        self._drain_soon = False
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if not self._pending:
            return
        batch, self._pending = (
            self._pending[: self.max_batch],
            self._pending[self.max_batch:],
        )
        if self._pending:  # backlog: keep draining without a fresh wait
            self._drain_soon = True
            self._loop.call_soon(self._drain)
        # shed already-dead pendings BEFORE device work: their clients
        # stopped waiting, so probing for them only delays live requests
        now = time.monotonic()
        live = []
        shed = 0
        for item in batch:
            fut, qid, _p, deadline_t, _t, _e = item
            if deadline_t is not None and now >= deadline_t:
                if not fut.done():
                    fut.set_exception(DeadlineExceeded(
                        f"query {qid!r} exceeded its deadline in the "
                        "serve queue"
                    ))
                shed += 1
            else:
                live.append(item)
        if shed and self._m_deadline_shed is not None:
            self._m_deadline_shed.inc(shed)
        if not live:
            return
        with batch_annotation([p for _f, _q, p, _d, _t, _e in live]):
            self._execute(live)

    def _execute(self, batch: list) -> None:
        t_exec = time.perf_counter_ns()
        for _f, _q, _p, _d, trace, t_enq in batch:
            if trace is not None:
                # queue-wait = enqueue -> drain (a wait, not a scope)
                trace.record("queue", t_enq, t_exec)
        try:
            # crash point: the microbatch is assembled, nothing executed —
            # a failure here must fail exactly this batch's callers and
            # leave the loop serving
            faults.fire("serve.batch")
            span = (
                self.tracer.span("serve.batch", n=len(batch))
                if self.tracer is not None else contextlib.nullcontext()
            )
            # device = the microbatch's engine time, shared (with its
            # lookup.* sub-spans) by every co-batched request
            with span, reqtrace_mod.shared_stage(
                    [t for _f, _q, _p, _d, t, _e in batch], "device"):
                results = self.engine.lookup_many(
                    [q for _f, q, _p, _d, _t, _e in batch],
                    parsed=[p for _f, _q, p, _d, _t, _e in batch],
                )
        except Exception as exc:
            for fut, _q, _p, _d, _t, _e in batch:
                if not fut.done():
                    fut.set_exception(exc)
            return
        # the work finished: one clock read a drain, shared by its requests
        # as the start of each one's ``wake``
        t_done = time.perf_counter_ns()
        for (fut, _q, _p, _d, trace, _e), result in zip(batch, results):
            if trace is not None:
                trace.mark_ns = t_done
            if not fut.done():
                fut.set_result(result)
        self._batches += 1
        self._queries += len(batch)
        if self._m_batches is not None:
            self._m_batches.inc()
            self._m_fill.observe(len(batch) / self.max_batch)
            self._m_depth.set(len(self._pending))

    def drain_stats(self) -> dict:
        return {
            "batches": self._batches,
            "queries": self._queries,
            "batch_fill": round(
                self._queries / (self._batches * self.max_batch), 4
            ) if self._batches else 0.0,
            "queue": {"items": self._queries, "max_depth": self._max_depth},
        }

    def close(self, timeout: float = 5.0) -> None:
        """Fail whatever is still queued; safe to call off-loop after the
        loop has stopped (the futures' waiters are gone with it)."""
        self._closed = True
        pending, self._pending = self._pending, []
        for fut, _q, _p, _d, _t, _e in pending:
            try:
                if not fut.done():
                    fut.cancel()
            except RuntimeError:
                pass  # loop already closed: the waiters died with it
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        self._drain_soon = False


#: refillable-debt horizon: an admitted bulk may indebt its bucket by at
#: most this many seconds of refill.  Bulks whose per-id cost exceeds it
#: are REJECTED at parse time (429) rather than served-then-forgiven —
#: a capped debt on work already done would let one oversized /variants
#: body bypass the per-client rate.  The clamp in ``charge`` is only a
#: backstop for direct API users.
MAX_DEBT_S = 30.0


class _TokenBucket:
    """One client's admission budget: ``rate`` tokens/sec, capped at
    ``burst``; a take below one whole token reports the wait instead."""

    __slots__ = ("rate", "burst", "tokens", "t")

    def __init__(self, rate: float, burst: float, now: float):
        self.rate = rate
        self.burst = burst
        self.tokens = burst
        self.t = now

    def take(self, now: float) -> float:
        """0.0 = admitted (one token spent); else seconds until a token."""
        self.tokens = min(
            self.burst, self.tokens + (now - self.t) * self.rate
        )
        self.t = now
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return 0.0
        return (1.0 - self.tokens) / self.rate

    def charge(self, cost: float) -> None:
        """Debit ``cost`` tokens, allowing bounded debt: the bucket must
        refill back above one whole token before the next admit."""
        self.tokens = max(self.tokens - cost, -self.rate * MAX_DEBT_S)


class ClientGovernor:
    """Weighted per-client fairness: each client key owns a token bucket
    refilling at ``base_rate * weight``.  Single-threaded by construction
    (all calls happen on the event loop).  The key population is
    LRU-bounded so an address-spraying client cannot balloon memory."""

    MAX_KEYS = 4096

    #: distinct client-id buckets one peer address may hold.  The id
    #: header is client-supplied — without a cap a hog rotating
    #: ``X-Client-Id`` per request would mint a fresh burst every time
    #: (never throttled) while its spray evicts other clients' buckets
    #: (and their accumulated bulk debt) from the LRU.  Beyond the cap
    #: an UNSEEN id degrades to the peer's aggregate bucket.
    PEER_KEY_CAP = 32

    def __init__(self, base_rate: float):
        self.base_rate = float(base_rate)
        self._buckets: OrderedDict = OrderedDict()
        self._peer_keys: dict[str, int] = {}  # peer -> live id-bucket count

    def resolve_key(self, peer: str, client_id: str | None) -> str:
        """The bucket key for this request.  Ids are scoped to the peer
        address (an id is a claim, not an identity) and capped per peer;
        no header means the peer's aggregate bucket."""
        if not client_id:
            return peer
        key = f"{peer}|{client_id}"
        if key in self._buckets:
            return key
        if self._peer_keys.get(peer, 0) >= self.PEER_KEY_CAP:
            return peer
        return key

    def _evict_oldest(self) -> None:
        key, _bucket = self._buckets.popitem(last=False)
        peer, sep, _cid = key.partition("|")
        if sep:
            n = self._peer_keys.get(peer, 0) - 1
            if n > 0:
                self._peer_keys[peer] = n
            else:
                self._peer_keys.pop(peer, None)

    def admit(self, key: str, weight: int) -> float:
        """0.0 = admitted; else retry-after seconds (the 429 header)."""
        now = time.monotonic()
        weight = min(max(int(weight), 1), MAX_CLIENT_WEIGHT)
        rate = self.base_rate * weight
        bucket = self._buckets.get(key)
        if bucket is None:
            bucket = _TokenBucket(rate, max(rate * 0.25, 4.0), now)
            self._buckets[key] = bucket
            peer, sep, _cid = key.partition("|")
            if sep:
                self._peer_keys[peer] = self._peer_keys.get(peer, 0) + 1
            while len(self._buckets) > self.MAX_KEYS:
                self._evict_oldest()
        else:
            self._buckets.move_to_end(key)
            if bucket.rate != rate:
                # the declared weight binds per REQUEST, not per bucket
                # lifetime: a client that first arrived without the header
                # (weight 1) must not stay throttled at 1/16th of the
                # share it declares later (take() re-clamps tokens to the
                # new burst)
                bucket.rate = rate
                bucket.burst = max(rate * 0.25, 4.0)
        return bucket.take(now)

    def charge(self, key: str, cost: float) -> None:
        """Debit extra work (bulk ids beyond the admit token) against the
        client's bucket — batching must not bypass the per-client rate.
        Callers must keep ``cost`` within :meth:`bulk_budget` (the front
        end rejects bigger bulks before executing them); the debt clamp
        in ``_TokenBucket.charge`` is a backstop, not a forgiveness
        policy.  A key evicted from the LRU between admit and charge
        forfeits the debt (self-correcting; only possible past MAX_KEYS
        clients)."""
        bucket = self._buckets.get(key)
        if bucket is not None:
            bucket.charge(cost)

    def bulk_budget(self, weight: int) -> int:
        """Max ids one admitted bulk may carry for a client of this
        weight: the per-id debt must be repayable within ``MAX_DEBT_S``
        of refill.  Anything larger is rejected outright — served work
        whose debt the clamp would cap is rate-limit bypass."""
        weight = min(max(int(weight), 1), MAX_CLIENT_WEIGHT)
        return max(int(self.base_rate * weight * MAX_DEBT_S), 1)


class AioServer:
    """The event-loop server.  Build with :func:`build_aio_server`; run
    blocking via :meth:`serve_forever` (installs SIGTERM/SIGINT graceful
    drain when on the main thread) or on a helper thread via
    :meth:`start_background` / :meth:`shutdown` (tests, smoke, bench).

    Shutdown order: stop the server, then ``ctx.batcher.close()`` (the
    caller owns the batcher)."""

    #: loop maintenance-tick cadence: heartbeat write + brownout-ladder
    #: evaluation + the serve.wedge fault point, all on the LOOP — a
    #: parked loop stops ticking, which is exactly what the fleet
    #: watchdog detects
    TICK_S = 0.25

    def __init__(self, ctx: ServeContext, host: str = "127.0.0.1",
                 port: int = 0, sock=None,
                 client_rate: float | None = None,
                 stream_threshold: int | None = None,
                 drain_s: float = 5.0,
                 heartbeat_file: str | None = None,
                 heartbeat_index: int = 0):
        self.ctx = ctx
        self.host = host
        self.port = port
        self.sock = sock  # pre-bound listening socket (fleet workers)
        #: fleet watchdog handshake: this worker's slot in the shared
        #: mmap'd heartbeat file (None outside a fleet).  Opened + mmap'd
        #: HERE, at worker start — the maintenance tick runs ON the event
        #: loop and must never touch the filesystem (AVDB701; the tick
        #: only ``struct.pack_into``s the established mapping)
        self.heartbeat_file = heartbeat_file
        self.heartbeat_index = int(heartbeat_index)
        self._hb_mm = None
        if heartbeat_file is not None:
            try:
                with open(heartbeat_file, "r+b") as f:
                    self._hb_mm = mmap.mmap(f.fileno(), 0)
            except (OSError, ValueError) as err:
                ctx.log(f"heartbeat file unusable ({err}); "
                        "watchdog will not see this worker")
                self._hb_mm = None
        #: runtime fault arming (POST /_chaos) for the chaos harness —
        #: gated hard on the environment so the route does not exist on
        #: a production server (404, byte-identical to any unknown
        #: route); resolved through the ONE shared reader (/debug/trace
        #: shares the same gate)
        self._chaos_enabled = chaos_enabled_from_env()
        #: fleet telemetry publishing: the maintenance tick schedules a
        #: snapshot-file write (on the POOL — the loop never does file
        #: I/O) so any sibling's /metrics?fleet=1 can sum this worker in
        self._telemetry_last = 0.0
        self._telemetry_inflight = False
        self._telemetry_error_logged = False
        #: flight flushes run from the tick on the POOL, never inline on
        #: the loop (the whole point of buffering the request summaries)
        self._flight_flush_inflight = False
        #: health-plane ticks likewise run from the tick on the POOL
        #: (the persist half is file I/O, banned on the loop)
        self._health_tick_inflight = False
        #: arming generation: each /_chaos arm bumps it so a stale ttl
        #: timer can never disarm a NEWER arming's fault
        self._chaos_seq = 0
        if client_rate is None:
            client_rate = _client_rate_from_env()
        self.governor = (
            ClientGovernor(client_rate) if client_rate > 0 else None
        )
        self.stream_threshold = (
            _stream_threshold_from_env()
            if stream_threshold is None else max(int(stream_threshold), 0)
        )
        self.drain_s = drain_s
        self.server_address = (host, port)
        self._pool = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="avdb-serve-exec"
        )
        self._loop: asyncio.AbstractEventLoop | None = None
        self._stop: asyncio.Event | None = None
        self._startup_error: BaseException | None = None
        self._started = threading.Event()
        self._thread: threading.Thread | None = None
        self._conns: set = set()
        # bound once: per-request getattr on the manager is hot-path waste
        self._refresh_due = getattr(ctx.manager, "refresh_due", None)
        self._refresh_inflight = False
        #: the loop's own account (``obs/loopclock.py``): every turn split
        #: into waiting and running, always on; ``/stats`` ``loop``, the
        #: ``avdb_loop_*`` series and a slow request's line read it
        self.loop_clock = ctx.loop_clock = ctx.reqtrace.loop_clock = \
            LoopClock()
        reg = ctx.registry
        self._m_loop = {
            "turns": reg.counter(
                "avdb_loop_turns_total",
                "turns of the serving event loop (select, then callbacks)"),
            "max_turn": reg.gauge(
                "avdb_loop_max_turn_seconds",
                "longest busy stretch of the serving event loop since start"),
            **{f"{state}_s": reg.counter(
                "avdb_loop_seconds_total",
                "serving event loop time: wait = inside select, busy = "
                "running callbacks", {"state": state})
               for state in ("wait", "busy")},
            **{f"{part}_s": reg.counter(
                "avdb_loop_busy_seconds_total",
                "serving event loop busy time by what ran (other = "
                "asyncio's machinery and the socket callbacks)",
                {"part": part})
               for part in (*PARTS, "other")},
        }

    # -- lifecycle ----------------------------------------------------------

    def serve_forever(self) -> None:
        """Run the loop on THIS thread until :meth:`shutdown` (or, on the
        main thread, SIGTERM/SIGINT) — then drain gracefully.  A bind
        failure raises here (``OSError``, e.g. EADDRINUSE) rather than
        leaving a zombie loop."""
        asyncio.run(self._main(), loop_factory=self._new_loop)
        if self._startup_error is not None:
            raise self._startup_error

    def _new_loop(self) -> asyncio.AbstractEventLoop:
        """The serving loop: a selector loop whose ``select`` is timed
        into :attr:`loop_clock` (the constructor's own seam; nothing of
        the loop is patched)."""
        return asyncio.SelectorEventLoop(
            TimedSelector(selectors.DefaultSelector(), self.loop_clock)
        )

    def _publish_loop_metrics(self) -> None:
        """Set the ``avdb_loop_*`` series from the clock — at a scrape and
        on the maintenance tick (a fleet sibling's snapshot), on the
        loop's thread; nothing is incremented per turn."""
        stats = self.loop_clock.stats(self.ctx.batcher.drain_ns)
        for key, metric in self._m_loop.items():
            if key == "max_turn":
                metric.set(stats["max_turn_ms_since_start"] / 1000.0)
            else:
                metric.inc(max(stats[key] - metric.value, 0.0))

    def start_background(self, timeout: float = 30.0) -> None:
        """Run the loop on a daemon thread; returns once the socket is
        bound (``server_address`` is then concrete).  Re-raises a bind
        failure from the loop thread (the caller gets the real
        ``OSError``, not a timeout)."""
        self._thread = threading.Thread(
            target=self._serve_quietly, name="avdb-serve-aio", daemon=True
        )
        self._thread.start()
        if not self._started.wait(timeout):
            raise RuntimeError("aio server did not start in time")
        if self._startup_error is not None:
            raise self._startup_error

    def _serve_quietly(self) -> None:
        """Background-thread target: a startup failure is re-raised to
        the foreground by :meth:`start_background`, not the thread
        excepthook."""
        try:
            self.serve_forever()
        except BaseException:
            if self._startup_error is None:
                raise

    def shutdown(self) -> None:
        """Threadsafe stop; joins the background thread when one exists."""
        loop, stop = self._loop, self._stop
        if loop is not None and stop is not None:
            with contextlib.suppress(RuntimeError):
                loop.call_soon_threadsafe(stop.set)
        if self._thread is not None \
                and self._thread is not threading.current_thread():
            self._thread.join(timeout=self.drain_s + 10)
        self._pool.shutdown(wait=False)

    async def _main(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._stop = asyncio.Event()
        if threading.current_thread() is threading.main_thread():
            import signal as _signal

            for signame in ("SIGTERM", "SIGINT"):
                with contextlib.suppress(
                    NotImplementedError, RuntimeError, ValueError
                ):
                    self._loop.add_signal_handler(
                        getattr(_signal, signame), self._stop.set
                    )
        try:
            if self.sock is not None:
                server = await asyncio.start_server(
                    self._handle, sock=self.sock
                )
            else:
                server = await asyncio.start_server(
                    self._handle, self.host, self.port
                )
        except OSError as err:
            # bind failure (EADDRINUSE, EACCES...): record and wake the
            # starter — serve_forever/start_background re-raise it as the
            # clean startup error instead of a 30s hang
            self._startup_error = err
            self._started.set()
            if self._hb_mm is not None:
                with contextlib.suppress(OSError, ValueError):
                    self._hb_mm.close()
                self._hb_mm = None
            return
        self.server_address = server.sockets[0].getsockname()[:2]
        self._started.set()
        self._start_tick()
        try:
            await self._stop.wait()
        finally:
            if self._hb_mm is not None:
                with contextlib.suppress(OSError, ValueError):
                    self._hb_mm.close()
                self._hb_mm = None
            server.close()
            await server.wait_closed()
            # graceful drain: in-flight connections finish their current
            # responses within the drain budget; stragglers are cancelled
            pending = [t for t in self._conns if not t.done()]
            if pending:
                _done, still = await asyncio.wait(
                    pending, timeout=self.drain_s
                )
                for t in still:
                    t.cancel()

    # -- loop maintenance tick ----------------------------------------------

    def _start_tick(self) -> None:
        # the heartbeat mapping was established in __init__ (worker
        # start): this runs on the event loop, where file I/O is banned
        self._loop.call_soon(self._tick)

    def _tick(self) -> None:
        """One maintenance pass ON the event loop: the wedge fault point
        first (a long ``delay`` here parks the loop — requests stall AND
        heartbeats stop, the alive-but-stuck worker), then one heartbeat
        slot write and a brownout-ladder evaluation.  Everything that
        proves this loop is making progress runs here, so a wedged loop
        cannot keep looking healthy from a helper thread."""
        if self._stop is not None and self._stop.is_set():
            return
        t_tick = time.perf_counter_ns()
        try:
            try:
                # crash point: fires per maintenance tick; delay = a
                # wedged loop the fleet watchdog must SIGKILL, kill = a
                # worker death
                faults.fire("serve.wedge")
            except Exception as err:
                self.ctx.log(f"wedge fault injected: {err}")
            if self._hb_mm is not None:
                # struct.error on a mis-sized/mis-indexed slot file
                # included: losing one beat is survivable, losing the
                # TICK CHAIN gets a healthy worker watchdog-killed in a
                # loop.  Beside the beat, the slot publishes this
                # worker's health (brownout level, p99-exceedance EWMA,
                # queue depth) so the supervisor's maintenance daemon can
                # yield to live traffic without an HTTP poll.
                with contextlib.suppress(OSError, ValueError, struct.error):
                    gov = self.ctx.governor
                    HB_SLOT.pack_into(
                        self._hb_mm,
                        self.heartbeat_index * HB_SLOT.size,
                        time.time(), gov.exceedance, gov.level,
                        self.ctx.batcher.depth(),
                    )
            with contextlib.suppress(Exception):
                self.ctx.governor.maybe_step()
            with contextlib.suppress(Exception):
                # memtable age/size flush triggers (the flush itself runs
                # on its own thread; this is one lock + compare)
                self.ctx.maybe_flush_memtable()
            with contextlib.suppress(Exception):
                self._maybe_publish_telemetry()
            with contextlib.suppress(Exception):
                self._maybe_flush_flight()
            with contextlib.suppress(Exception):
                self._maybe_tick_health()
            with contextlib.suppress(Exception):
                self._publish_loop_metrics()
        finally:
            # the next tick is unconditional: whatever one pass hit, the
            # heartbeat/brownout machinery must keep running
            self._loop.call_later(self.TICK_S, self._tick)
            self.loop_clock.tick_ns += time.perf_counter_ns() - t_tick

    #: seconds between fleet-telemetry snapshot publishes
    TELEMETRY_S = 1.0

    def _maybe_publish_telemetry(self) -> None:
        """Time-gated, one in flight: schedule this worker's metric
        snapshot write onto the executor pool (the tick runs ON the
        loop, where file I/O is banned)."""
        tdir = self.ctx.telemetry_dir
        if tdir is None or self._telemetry_inflight:
            return
        now = time.monotonic()
        if now - self._telemetry_last < self.TELEMETRY_S:
            return
        self._telemetry_last = now
        self._telemetry_inflight = True
        fut = self._pool.submit(self._publish_telemetry)
        fut.add_done_callback(
            lambda _f: setattr(self, "_telemetry_inflight", False)
        )

    def _maybe_flush_flight(self) -> None:
        """Drain the flight recorder's buffered request summaries on the
        executor pool (one in flight at a time; the tick itself only
        schedules)."""
        flight = self.ctx.flight
        if flight is None or self._flight_flush_inflight:
            return
        self._flight_flush_inflight = True

        def run():
            try:
                flight.flush(limit=flight.FLUSH_BATCH)
            finally:
                self._flight_flush_inflight = False

        self._pool.submit(run)

    def _maybe_tick_health(self) -> None:
        """Health-plane tick (time-series sample + SLO evaluation +
        history persist) on the executor pool — the persist half is file
        I/O, banned on the loop.  One in flight; the plane's own
        ``due()`` gates the cadence, and ``tick()`` absorbs its own
        failures."""
        health = self.ctx.health
        if health is None or self._health_tick_inflight \
                or not health.due():
            return
        self._health_tick_inflight = True

        def run():
            try:
                health.tick()
            finally:
                self._health_tick_inflight = False

        self._pool.submit(run)

    def _publish_telemetry(self) -> None:
        """Pool half: atomically replace this worker's snapshot file —
        a sibling scraping ``?fleet=1`` must never read a torn JSON."""
        try:
            path = os.path.join(
                self.ctx.telemetry_dir,
                f"worker-{self.ctx.worker_index}.json",
            )
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({
                    "index": self.ctx.worker_index,
                    "pid": os.getpid(),
                    "t": time.time(),
                    "metrics": self.ctx.registry.snapshot(),
                }, f)
            os.replace(tmp, path)
        except (OSError, ValueError, TypeError) as err:
            if not self._telemetry_error_logged:
                self._telemetry_error_logged = True
                self.ctx.log(f"telemetry publish failed ({err}); "
                             "fleet view will miss this worker")

    # -- connection handling ------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        task = asyncio.current_task()
        if task is not None:
            self._conns.add(task)
            task.add_done_callback(self._conns.discard)
        try:
            # crash point: the connection is accepted, nothing parsed —
            # a raise here must cost exactly this connection; kill is the
            # fleet's dead-worker case (supervisor restarts)
            faults.fire("serve.accept")
        except Exception as err:
            self.ctx.log(f"accept failed: {err}")
            writer.close()
            return
        out_q: asyncio.Queue = asyncio.Queue(maxsize=PIPELINE_DEPTH)
        wtask = self._loop.create_task(self._write_responses(writer, out_q))
        try:
            while True:
                try:
                    head = await reader.readuntil(b"\r\n\r\n")
                except (asyncio.IncompleteReadError, ConnectionResetError,
                        BrokenPipeError):
                    break  # client closed between requests
                except asyncio.LimitOverrunError:
                    await out_q.put(_error(431, "request head too large"))
                    break
                # the head is complete: the request's ``read`` starts, and
                # with it the loop clock's ``read`` section
                item, keep = await self._route(
                    reader, writer, head, time.perf_counter_ns()
                )
                if item is not None:
                    await out_q.put(item)
                if not keep:
                    break
        except asyncio.CancelledError:
            wtask.cancel()
            raise  # shutdown drain: let the cancellation propagate
        except Exception as err:
            self.ctx.log(f"connection handler error: {err}")
        finally:
            try:
                out_q.put_nowait(None)  # sentinel: emit the tail, then stop
            except asyncio.QueueFull:
                # a full pipeline at teardown: wait for the writer to make
                # room rather than dropping the sentinel (a dropped
                # sentinel stalls teardown until the watchdog cancel)
                with contextlib.suppress(Exception):
                    await asyncio.wait_for(out_q.put(None), timeout=10)
            if not wtask.done():
                try:
                    await asyncio.wait_for(wtask, timeout=self.drain_s + 25)
                except (asyncio.TimeoutError, asyncio.CancelledError):
                    wtask.cancel()
            # a cancelled writer abandons whatever is still queued —
            # settle those items or their admission slots leak for the
            # life of the (otherwise healthy) server
            while True:
                try:
                    item = out_q.get_nowait()
                except asyncio.QueueEmpty:
                    break
                if item is not None:
                    with contextlib.suppress(Exception):
                        await self._settle(item)
            with contextlib.suppress(Exception):
                writer.close()
                await writer.wait_closed()

    async def _write_responses(self, writer, q: asyncio.Queue) -> None:
        """Emit responses strictly in request order, COALESCING ready
        responses into one transport write — per-response ``send`` calls
        dominate the profile at serving QPS (a batcher drain completes
        ~hundreds of futures at once; their bytes should leave in one
        syscall, not hundreds).  A dead client stops the writes but NOT
        the accounting: remaining items are still awaited (admission
        slots release, executor work completes).

        A request's trace is sealed HERE, after the write that hands its
        bytes to the transport, so that ``reply`` (and ``total``) end
        there: ``_emit`` collects the traces of one coalesced write in
        ``sealing`` and :meth:`_write_out` finishes them together — also
        when the client is gone, with whatever status their bytes had."""
        dead = False
        out = bytearray()
        sealing: list = []
        stop = False
        while not stop:
            item = await q.get()
            batch = [item]
            # opportunistically take everything already queued — their
            # futures resolved with the same microbatch drain
            while True:
                try:
                    batch.append(q.get_nowait())
                except asyncio.QueueEmpty:
                    break
            for idx, it in enumerate(batch):
                if it is None:
                    stop = True
                    break
                try:
                    if dead:
                        await self._settle(it)
                        continue
                    await self._emit(writer, it, out, sealing)
                    if len(out) > _WRITE_HIGH_WATER:
                        self._write_out(writer, out, sealing)
                        await writer.drain()
                except (ConnectionResetError, BrokenPipeError, OSError):
                    # the item whose _emit raised has already settled its
                    # own accounting (the stream path releases in its
                    # finally) — only LATER items go the settle path
                    dead = True
                    out.clear()
                    self._seal(sealing)
                except asyncio.CancelledError:
                    self._seal(sealing)
                    # cancelled (watchdog/shutdown) with items in hand:
                    # they left the queue, so the handler's teardown
                    # drain cannot see them — settle the LATER ones here
                    # without awaiting (the current item settles itself
                    # in _emit/_settle)
                    for later in batch[idx + 1:]:
                        if isinstance(later, tuple) and later[0] == "exec":
                            self._settle_when_done(later[1])
                    raise
            if (out or sealing) and not dead:
                try:
                    self._write_out(writer, out, sealing)
                    if (writer.transport.get_write_buffer_size()
                            > _WRITE_HIGH_WATER):
                        await writer.drain()
                except (ConnectionResetError, BrokenPipeError, OSError):
                    dead = True
                    out.clear()
                    self._seal(sealing)
        if not dead:
            with contextlib.suppress(Exception):
                await writer.drain()

    def _write_out(self, writer, out: bytearray, sealing: list) -> None:
        """One coalesced write: hand ``out`` to the transport, then seal
        the traces whose bytes it held — ``reply`` ends when
        ``writer.write`` has returned (a write that raises seals them all
        the same: the attempt is where the reply ended)."""
        t0 = time.perf_counter_ns()
        try:
            if out:
                writer.write(bytes(out))
                out.clear()
        finally:
            t_written = time.perf_counter_ns()
            self.loop_clock.reply_ns += t_written - t0
            self.loop_clock.write_ns += t_written - t0
            self._seal(sealing, t_written)

    def _seal(self, sealing: list, t_written: int | None = None) -> None:
        """Finish the collected traces (and empty the list): ``reply`` =
        the coroutine resumed -> ``t_written``, the status the one noted
        where the bytes were built."""
        if not sealing:
            return
        if t_written is None:
            t_written = time.perf_counter_ns()
        finish = self.ctx.reqtrace.finish
        for trace in sealing:
            if trace.mark_ns:
                trace.record("reply", trace.mark_ns, t_written)
            finish(trace, trace.status)
        sealing.clear()

    async def _emit(self, writer, item, out: bytearray,
                    sealing: list) -> None:
        """Append one response's bytes to the coalescing buffer (or, for
        a streamed region, flush the buffer and stream directly).  A
        request's trace goes to ``sealing``: the caller finishes it once
        the buffer is written."""
        if isinstance(item, bytes):
            out += item
            return
        kind = item[0]
        if kind == "point":
            _k, fut, t0, vid, generation, tid, trace = item
            out += await self._finish_point(fut, t0, vid, generation,
                                            tid, trace)
            if trace is not None:
                sealing.append(trace)
            return
        # ("exec", future, kind, t0, tid, trace): buffered bytes or a
        # stream marker
        _k, fut, qkind, t0, tid, trace = item
        try:
            result = await fut
        except asyncio.CancelledError:
            # the writer was cancelled mid-wait (watchdog/shutdown); the
            # executor half still finishes, and a streamed region would
            # hold its admission slot forever — settle it when it lands
            self._settle_when_done(fut)
            raise
        t_wake = time.perf_counter_ns()
        if trace is not None:
            self._woke(trace, t_wake)
        if isinstance(result, bytes):
            # the bytes already know their status, so the work functions
            # never fork on it; the trace seals with it after the write
            out += _add_trace(result, tid)
            if trace is not None:
                trace.status = _status_of(result)
                sealing.append(trace)
            self.loop_clock.reply_ns += time.perf_counter_ns() - t_wake
            return
        page = result[1]  # RegionPage or RegionsResult: same stream surface
        try:
            self.loop_clock.reply_ns += time.perf_counter_ns() - t_wake
            # ordering: everything before the stream goes first
            self._write_out(writer, out, sealing)
            await self._stream_region(writer, page, tid)
            self.ctx.observe(qkind, time.perf_counter() - t0,
                             rows=page.returned)
            if trace is not None:
                # a streamed body's reply ends with its last chunk written
                # and drained
                trace.status = 200
                self._seal([trace])
        finally:
            self.ctx.release()

    @staticmethod
    def _woke(trace, t_wake: int) -> None:
        """The request's coroutine resumed after ``await fut``: ``wake``
        = the work finished (``mark_ns``: the drain's one clock read, or
        the executor half's return) -> now; ``reply`` starts here."""
        if trace.mark_ns:
            trace.record("wake", trace.mark_ns, t_wake)
        trace.mark_ns = t_wake

    async def _settle(self, item) -> None:
        """Account for an item that will never reach the wire (the client
        connection died first): release whatever it holds, and make the
        abandonment visible — a chaos run's killed connections should
        show up in a counter, not vanish."""
        self.ctx.abandoned()
        if isinstance(item, bytes):
            return
        fut = item[1]
        try:
            result = await fut
        except asyncio.CancelledError:
            if item[0] == "exec":
                self._settle_when_done(fut)
            raise
        except Exception:
            return
        # seal the abandoned request's trace (status 0 = undelivered)
        self.ctx.reqtrace.finish(item[-1], 0)
        if not isinstance(result, bytes) and item[0] == "exec":
            self.ctx.release()  # undelivered stream: free its slot

    def _settle_when_done(self, fut) -> None:
        """Non-awaiting twin of :meth:`_settle` for an exec future the
        cancelled writer abandoned mid-await."""
        def settle(f):
            with contextlib.suppress(Exception):
                if not isinstance(f.result(), bytes):
                    self.ctx.release()
        fut.add_done_callback(settle)

    async def _finish_point(self, fut, t0, vid: str, generation: int,
                            tid: str | None = None, trace=None) -> bytes:
        """The reply's bytes of one point read, once its drain answered.
        The trace is not sealed here: its ``status`` is noted, and the
        writer finishes it after the write (``_write_out``)."""
        ctx = self.ctx
        try:
            # no wait_for wrapper (it costs a Task + timer per request):
            # every submitted pending is GUARANTEED to finish — the drain
            # completes it, fails it, sheds it past its deadline, or
            # close() fails the queue
            record = await fut
        except DeadlineExceeded as err:
            # the batcher shed it (and counted stage="batcher")
            if trace is not None:
                trace.status = 504
            return _add_trace(_error(504, str(err)), tid)
        except Exception as err:
            ctx.errored("point")
            if trace is not None:
                trace.status = 500
            return _add_trace(
                _error(500, f"{type(err).__name__}: {err}"), tid
            )
        # the coroutine resumed: ``wake`` ends, ``render`` and ``reply``
        # start, and so does the loop clock's ``reply`` section
        t_wake = time.perf_counter_ns()
        ctx.remember_point(generation, vid, record)
        if record is None:
            ctx.observe("point", time.perf_counter() - t0)
            status = 404
            resp = _error(404, f"variant {vid!r} not in store")
        else:
            status = 200
            resp = _resp(200, record)
            ctx.observe("point", time.perf_counter() - t0, rows=1)
        resp = _add_trace(resp, tid)
        t_built = time.perf_counter_ns()
        if trace is not None:
            self._woke(trace, t_wake)
            if status == 200:
                trace.record("render", t_wake, t_built)
            trace.status = status
        self.loop_clock.reply_ns += t_built - t_wake
        return resp

    # -- routing ------------------------------------------------------------

    @staticmethod
    def _parse_head(head: bytes):
        """(method, target, keep_alive, http11, headers) from one request
        head.  ``http11`` gates chunked streaming: RFC 9112 forbids
        ``Transfer-Encoding`` toward a 1.0 peer."""
        lines = head.split(b"\r\n")
        parts = lines[0].split(b" ")
        if len(parts) != 3:
            raise ValueError(f"malformed request line {lines[0][:80]!r}")
        method = parts[0].decode("latin-1")
        target = parts[1].decode("latin-1")
        version = parts[2].decode("latin-1")
        headers: dict[str, str] = {}
        for line in lines[1:]:
            if not line:
                continue
            name, sep, value = line.partition(b":")
            if sep:
                headers[name.decode("latin-1").strip().lower()] = \
                    value.decode("latin-1").strip()
        conn = headers.get("connection", "").lower()
        http11 = version == "HTTP/1.1"
        keep = (http11 and conn != "close") or conn == "keep-alive"
        return method, target, keep, http11, headers

    async def _route(self, reader, writer, head: bytes, t_head: int):
        """One parsed request -> (queue item | None, keep_alive).  The
        trace-id echo header splices into prebuilt byte responses HERE
        (one insertion point); deferred items (point/exec tuples) carry
        the id and the writer splices when their bytes materialize.

        ``t_head`` (``perf_counter_ns``) is when the head was complete.
        From there to the item handed back is the request's ``read`` span
        — recorded here with a start stamped before any trace existed, as
        ``admission`` is — and, less the body's ``await`` (subtracted where
        it returns), the loop clock's ``read`` section."""
        try:
            item, keep, tid = await self._route_inner(reader, writer, head)
            if isinstance(item, bytes):
                item = _add_trace(item, tid)
        finally:
            t_item = time.perf_counter_ns()
            self.loop_clock.read_ns += t_item - t_head
        if item.__class__ is tuple and item[-1] is not None:
            item[-1].record("read", t_head, t_item)
        return item, keep

    async def _route_inner(self, reader, writer, head: bytes):
        """The routing body: returns ``(item, keep_alive, trace_id)``."""
        ctx = self.ctx
        # fast path: the dominant serving request is a plain point GET on
        # a keep-alive connection; skip the full head parse for it (the
        # governor, when on, needs headers — it takes the slow path; so
        # does a client-sent trace id, which must echo byte-identically)
        if self.governor is None and head.startswith(b"GET /variant/"):
            eol = head.find(b"\r\n")
            line = head[:eol]
            hlow = head.lower()
            # any Connection header (rare on this hot path; the token is
            # case-insensitive per RFC 9112) routes to the full parser —
            # a substring guess here would misread "Connection: Close";
            # a client-sent deadline header likewise needs the real parse
            if line.endswith(b" HTTP/1.1") and b"?" not in line \
                    and b"connection:" not in hlow \
                    and b"x-deadline-ms:" not in hlow \
                    and b"x-request-id:" not in hlow \
                    and b"traceparent:" not in hlow:
                vid = line[13:-9].decode("latin-1")
                if "%" in vid:
                    vid = unquote(vid)
                self._maybe_refresh_snapshot()
                tid = resolve_trace_id(None, None)
                return self._point_item(
                    vid, self._default_deadline(), tid
                ), True, tid
        try:
            method, target, keep, http11, headers = self._parse_head(head)
        except ValueError as err:
            return _error(400, str(err)), False, None
        tid = resolve_trace_id(
            headers.get("traceparent"), headers.get("x-request-id")
        )
        url = urlparse(target)
        path = unquote(url.path)
        self._maybe_refresh_snapshot()
        deadline_t = ctx.request_deadline(headers.get("x-deadline-ms"))
        if method == "GET":
            if path.startswith("/variant/"):
                retry = self._admit_client(headers, writer)
                if retry:
                    ctx.rejected("point")
                    return _error(
                        429, "client over rate (point admission)",
                        retry_after=max(int(retry + 0.999), 1),
                    ), keep, tid
                return self._point_item(
                    path[len("/variant/"):], deadline_t, tid
                ), keep, tid
            if path.startswith("/region/"):
                if ctx.governor.shed_bulk():
                    ctx.brownout_shed()
                    return _error(503, MSG_BROWNOUT_REGION), keep, tid
                retry = self._admit_client(headers, writer)
                if retry:
                    ctx.rejected("region")
                    return _error(
                        429, "client over rate (region admission)",
                        retry_after=max(int(retry + 0.999), 1),
                    ), keep, tid
                return self._region_item(
                    path[len("/region/"):], url.query, http11,
                    deadline_t, tid,
                ), keep, tid
            if path == "/healthz":
                return _resp(200, healthz_payload(ctx)), keep, tid
            if path == "/readyz":
                status, body = readyz_payload(ctx)
                return _resp(status, body), keep, tid
            if path == "/metrics":
                self._publish_loop_metrics()
                if "fleet" in (url.query or ""):
                    # the fleet view reads sibling snapshot FILES — that
                    # is executor work, never event-loop work
                    fut = self._loop.run_in_executor(
                        self._pool,
                        lambda: _resp(200, metrics_payload(ctx, url.query),
                                      content_type=_CT_TEXT),
                    )
                    return ("exec", fut, "metrics", time.perf_counter(),
                            tid, None), keep, tid
                return _resp(200, metrics_payload(ctx, url.query),
                             content_type=_CT_TEXT), keep, tid
            if path == "/stats":
                return _resp(200, stats_payload(ctx)), keep, tid
            if path == "/alerts":
                if "fleet" in (url.query or ""):
                    # the fleet view reads sibling history FILES — that
                    # is executor work, never event-loop work
                    fut = self._loop.run_in_executor(
                        self._pool,
                        lambda: _resp(200, alerts_payload(ctx, url.query)),
                    )
                    return ("exec", fut, "alerts", time.perf_counter(),
                            tid, None), keep, tid
                return _resp(200, alerts_payload(ctx, url.query)), keep, tid
            if path == HISTORY_ROUTE:
                # even the solo view walks the whole ring deriving
                # rates/quantiles per sample — executor work like the
                # fleet file reads, never event-loop work
                fut = self._loop.run_in_executor(
                    self._pool,
                    lambda: _resp(
                        200, metrics_history_payload(ctx, url.query)
                    ),
                )
                return ("exec", fut, "history", time.perf_counter(),
                        tid, None), keep, tid
            if path == "/debug/trace" and ctx.debug_trace_enabled:
                # chaos-gated like /_chaos: a production server 404s this
                # byte-identically to any unknown route
                return _resp(200, debug_trace_payload(ctx)), keep, tid
            if path == REPL_MANIFEST_ROUTE:
                # the ship document stats the manifest and scans WAL
                # stable prefixes — file I/O, executor work (AVDB701)
                fut = self._loop.run_in_executor(
                    self._pool,
                    lambda: _resp(*repl_manifest_payload(ctx)),
                )
                return ("exec", fut, "repl", time.perf_counter(),
                        tid, None), keep, tid
            if path in (REPL_SEGMENT_ROUTE, REPL_WAL_ROUTE):
                fut = self._loop.run_in_executor(
                    self._pool, self._repl_file_work, url.query
                )
                return ("exec", fut, "repl", time.perf_counter(),
                        tid, None), keep, tid
            if path == EXPORT_STREAM_ROUTE:
                if ctx.governor.shed_bulk():
                    ctx.brownout_shed()
                    return _error(503, MSG_BROWNOUT_EXPORT), keep, tid
                retry = self._admit_client(headers, writer)
                if retry:
                    ctx.rejected("export")
                    return _error(
                        429, "client over rate (export admission)",
                        retry_after=max(int(retry + 0.999), 1),
                    ), keep, tid
                return self._export_item(
                    url.query, deadline_t, tid
                ), keep, tid
            return _error(404, f"no such route: {path}"), keep, tid
        if method == "POST":
            try:
                length = int(headers.get("content-length", 0))
            except ValueError:
                # a malformed Content-Length is a bad body-carrying
                # request (400),
                # not a too-large one; the body length is unknowable, so
                # the connection cannot be reused
                if path == "/variants":
                    ctx.errored("bulk")
                    return _error(400, BULK_BODY_ERROR), False, tid
                if path == UPSERT_ROUTE:
                    ctx.errored("upsert")
                    return _error(400, UPSERT_BODY_ERROR), False, tid
                if path == "/regions":
                    ctx.errored("regions")
                    return _error(400, REGIONS_BODY_ERROR), False, tid
                if path == STATS_ROUTE:
                    ctx.errored("stats")
                    return _error(400, STATS_BODY_ERROR), False, tid
                return _error(404, f"no such route: {path}"), False, tid
            if length < 0 or length > MAX_BODY:
                return _error(
                    413, f"body too large (cap {MAX_BODY} bytes)"
                ), False, tid
            t_body = time.perf_counter_ns()
            try:
                body = await reader.readexactly(length) if length else b""
            except asyncio.IncompleteReadError:
                return None, False, None
            finally:
                # a wait across callbacks, not the loop's own work: out of
                # the ``read`` section ``_route`` adds when this returns
                self.loop_clock.read_ns -= time.perf_counter_ns() - t_body
            if path == "/variants":
                if ctx.governor.shed_bulk():
                    ctx.brownout_shed()
                    return _error(503, MSG_BROWNOUT_BULK), keep, tid
                retry = self._admit_client(headers, writer)
                if retry:
                    ctx.rejected("bulk")
                    return _error(
                        429, "client over rate (bulk admission)",
                        retry_after=max(int(retry + 0.999), 1),
                    ), keep, tid
                client = max_ids = None
                if self.governor is not None:
                    client, weight = self._client_key(headers, writer)
                    max_ids = self.governor.bulk_budget(weight)
                return self._bulk_item(
                    body, client, max_ids, deadline_t, tid
                ), keep, tid
            if path == UPSERT_ROUTE:
                if ctx.governor.shed_bulk():
                    ctx.brownout_shed()
                    return _error(503, MSG_BROWNOUT_UPSERT), keep, tid
                retry = self._admit_client(headers, writer)
                if retry:
                    ctx.rejected("upsert")
                    return _error(
                        429, "client over rate (upsert admission)",
                        retry_after=max(int(retry + 0.999), 1),
                    ), keep, tid
                client = max_ids = None
                if self.governor is not None:
                    client, weight = self._client_key(headers, writer)
                    max_ids = self.governor.bulk_budget(weight)
                return self._upsert_item(
                    body, client, max_ids, deadline_t, tid
                ), keep, tid
            if path == "/regions":
                if ctx.governor.shed_bulk():
                    ctx.brownout_shed()
                    return _error(503, MSG_BROWNOUT_REGION), keep, tid
                retry = self._admit_client(headers, writer)
                if retry:
                    ctx.rejected("regions")
                    return _error(
                        429, "client over rate (region admission)",
                        retry_after=max(int(retry + 0.999), 1),
                    ), keep, tid
                client = max_ids = None
                if self.governor is not None:
                    client, weight = self._client_key(headers, writer)
                    max_ids = self.governor.bulk_budget(weight)
                return self._regions_item(
                    body, http11, client, max_ids, deadline_t, tid
                ), keep, tid
            if path == STATS_ROUTE:
                if ctx.governor.shed_bulk():
                    ctx.brownout_shed()
                    return _error(503, MSG_BROWNOUT_STATS), keep, tid
                retry = self._admit_client(headers, writer)
                if retry:
                    ctx.rejected("stats")
                    return _error(
                        429, "client over rate (stats admission)",
                        retry_after=max(int(retry + 0.999), 1),
                    ), keep, tid
                client = max_ids = None
                if self.governor is not None:
                    client, weight = self._client_key(headers, writer)
                    max_ids = self.governor.bulk_budget(weight)
                return self._stats_item(
                    body, client, max_ids, deadline_t, tid
                ), keep, tid
            if path == "/_chaos" and self._chaos_enabled:
                return self._chaos_item(body), keep, tid
            return _error(404, f"no such route: {path}"), keep, tid
        return _error(501, f"method {method} not supported"), False, tid

    def _default_deadline(self) -> float | None:
        """Absolute deadline from the configured default budget alone
        (the fast path's case: no headers were parsed, and the fast path
        already guaranteed no X-Deadline-Ms header is present)."""
        d = self.ctx.default_deadline_s
        return time.monotonic() + d if d > 0 else None

    def _point_item(self, variant_id: str, deadline_t: float | None = None,
                    tid: str | None = None):
        ctx = self.ctx
        t0 = time.perf_counter()
        trace = ctx.reqtrace.begin(tid, "point") if tid is not None else None
        action, payload = ctx.point_preflight(variant_id, deadline_t)
        if action == "shed":
            ctx.reqtrace.finish(trace, 504)
            return _error(504, MSG_DEADLINE_ADMISSION)
        if action == "cached":
            if payload is None:
                ctx.observe("point", time.perf_counter() - t0)
                ctx.reqtrace.finish(trace, 404)
                return _error(404, f"variant {variant_id!r} not in store")
            ctx.observe("point", time.perf_counter() - t0, rows=1)
            ctx.reqtrace.finish(trace, 200)
            return _resp(200, payload)
        generation = payload
        try:
            fut = ctx.batcher.submit_future(variant_id, deadline_t,
                                            trace=trace)
        except QueueFull as err:
            ctx.rejected("point")
            ctx.reqtrace.finish(trace, 429)
            return _error(429, str(err), retry_after=1)
        except QueryError as err:
            ctx.errored("point")
            ctx.reqtrace.finish(trace, 400)
            return _error(400, str(err))
        except Exception as err:
            ctx.errored("point")
            ctx.reqtrace.finish(trace, 500)
            return _error(500, f"{type(err).__name__}: {err}")
        return ("point", fut, t0, variant_id, generation, tid, trace)

    def _chaos_item(self, body: bytes) -> bytes:
        """Runtime fault arming (``AVDB_SERVE_CHAOS=1`` only): the chaos
        harness's worker-side lever — environment arming cannot reach a
        running fleet, and respawned workers naturally come up clean
        because this is in-process state.  ``ttl_s`` schedules an
        automatic disarm so a probabilistic fault cannot outlive its
        scheduled chaos window when the disarm request would land on a
        different worker."""
        try:
            obj = json.loads(body or b"{}")
            if not isinstance(obj, dict):
                raise TypeError("chaos body must be a JSON object")
            spec = obj.get("spec", "") or ""
            ttl = obj.get("ttl_s")
            # validate EVERYTHING before arming: a bad ttl must not leave
            # the fault armed with the auto-disarm it promised missing
            ttl_s = max(float(ttl), 0.0) if ttl is not None else None
            faults.reset(spec)
        except (ValueError, TypeError) as err:
            return _error(400, f"bad chaos spec: {err}")
        self._chaos_seq += 1
        if ttl_s is not None and spec:
            seq = self._chaos_seq

            def expire():
                # only disarm the arming this timer belongs to: a newer
                # arm owns the (single) fault slot and its own ttl
                if self._chaos_seq == seq:
                    faults.reset("")

            self._loop.call_later(ttl_s, expire)
        return _resp(200, json.dumps(
            {"armed": spec or None, "pid": os.getpid()}
        ))

    def _repl_file_work(self, query: str) -> bytes:
        """Executor half of ``GET /repl/{segment,wal}``: raw range bytes
        (the shared builder clamps WAL/ledger reads to their stable
        prefixes, so a torn frame can never leave this worker)."""
        status, body = repl_file_response(self.ctx, query)
        if isinstance(body, bytes):
            head = _STATUS[status] + _CT_BIN + str(len(body)).encode()
            return head + b"\r\n\r\n" + body
        return _resp(status, body)

    def _submit(self, work, trace, *args):
        """Run an executor half, ``work(*args, trace)``, on the pool."""
        return self._loop.run_in_executor(
            self._pool, self._timed_work, work, trace, args
        )

    @staticmethod
    def _timed_work(work, trace, args):
        """On the executor's thread: the work, then the one clock read its
        request's ``wake`` starts at (the wait for the event loop to
        resume the coroutine: the executor hop back)."""
        try:
            return work(*args, trace)
        finally:
            if trace is not None:
                trace.mark_ns = time.perf_counter_ns()

    def _bulk_item(self, body: bytes, client: str | None = None,
                   max_ids: int | None = None,
                   deadline_t: float | None = None,
                   tid: str | None = None):
        ctx = self.ctx
        t0 = time.perf_counter()
        if deadline_t is not None and time.monotonic() >= deadline_t:
            ctx.deadline_shed("admission")
            return _error(504, MSG_DEADLINE_ADMISSION)
        if not ctx.admit():
            ctx.rejected("bulk")
            return _error(429, MSG_CAPACITY_BULK, retry_after=1)
        trace = ctx.reqtrace.begin(tid, "bulk") if tid is not None else None
        fut = self._submit(
            self._bulk_work, trace, body, t0, client, max_ids, deadline_t
        )
        return ("exec", fut, "bulk", t0, tid, trace)

    def _bulk_work(self, body: bytes, t0: float,
                   client: str | None = None,
                   max_ids: int | None = None,
                   deadline_t: float | None = None, trace=None) -> bytes:
        """Executor half of a bulk request (parse, probe, render, account);
        never raises — errors become response bytes."""
        ctx = self.ctx
        try:
            if deadline_t is not None and time.monotonic() >= deadline_t:
                # executor-queue lag ate the budget: shed BEFORE the probe
                ctx.deadline_shed("execute")
                return _error(504, MSG_DEADLINE_EXECUTE)
            if trace is not None:
                # admission = arrival -> this executor slot (pool wait
                # included: that IS where an overloaded worker queues)
                trace.since("admission", t0)
            try:
                parsed = json.loads(body or b"{}")
                ids = parsed["ids"]
                if not isinstance(ids, list) \
                        or not all(isinstance(i, str) for i in ids):
                    raise KeyError("ids")
            except (ValueError, KeyError, TypeError):
                ctx.errored("bulk")
                return _error(400, BULK_BODY_ERROR)
            if max_ids is not None and len(ids) > max_ids:
                # a bulk the bucket could never repay within MAX_DEBT_S:
                # executing it and capping the debt would be rate-limit
                # bypass — reject before any lookup runs
                ctx.rejected("bulk")
                return _error(429, (
                    f"bulk of {len(ids)} ids exceeds client rate budget "
                    f"({max_ids} ids); split the request"
                ), retry_after=1)
            if client is not None and len(ids) > 1:
                # admission spent ONE token; the other len-1 lookups debit
                # the bucket too (on the loop thread — the governor is
                # single-threaded by construction), or a hog would bypass
                # the per-client rate entirely by batching
                self._loop.call_soon_threadsafe(
                    self.governor.charge, client, float(len(ids) - 1)
                )
            try:
                with reqtrace_mod.stage(trace, "device"):
                    results = ctx.engine.lookup_many(ids)
            except QueryError as err:
                ctx.errored("bulk")
                return _error(400, str(err))
            except Exception as err:
                ctx.errored("bulk")
                return _error(500, f"{type(err).__name__}: {err}")
            with reqtrace_mod.stage(trace, "render"):
                found = sum(1 for r in results if r is not None)
                resp = _resp(200, (
                    f'{{"n":{len(results)},"found":{found},"results":['
                    + ",".join(r if r is not None else "null" for r in results)
                    + "]}"
                ))
                ctx.observe("bulk", time.perf_counter() - t0, rows=found)
            return resp
        finally:
            ctx.release()

    def _upsert_item(self, body: bytes, client: str | None = None,
                     max_rows: int | None = None,
                     deadline_t: float | None = None,
                     tid: str | None = None):
        """Live write path: the bulk admission shape (slot + per-client
        budget); the WAL fsync runs on the executor pool — the ack
        barrier is blocking I/O and must never touch the event loop."""
        ctx = self.ctx
        t0 = time.perf_counter()
        if deadline_t is not None and time.monotonic() >= deadline_t:
            ctx.deadline_shed("admission")
            return _error(504, MSG_DEADLINE_ADMISSION)
        if not ctx.admit():
            ctx.rejected("upsert")
            return _error(429, MSG_CAPACITY_UPSERT, retry_after=1)
        trace = ctx.reqtrace.begin(tid, "upsert") if tid is not None \
            else None
        fut = self._submit(
            self._upsert_work, trace, body, t0, client, max_rows, deadline_t
        )
        return ("exec", fut, "upsert", t0, tid, trace)

    def _upsert_work(self, body: bytes, t0: float,
                     client: str | None = None,
                     max_rows: int | None = None,
                     deadline_t: float | None = None, trace=None) -> bytes:
        """Executor half of an upsert (parse, WAL append+fsync, memtable
        insert, ack) — the shared :meth:`ServeContext.upsert_execute`
        does the work; never raises — errors become response bytes."""
        ctx = self.ctx
        try:
            if deadline_t is not None and time.monotonic() >= deadline_t:
                # executor-queue lag ate the budget: shed BEFORE the WAL
                # write (nothing durable happened, nothing acknowledged)
                ctx.deadline_shed("execute")
                return _error(504, MSG_DEADLINE_EXECUTE)
            if trace is not None:
                trace.since("admission", t0)
            status, text, rows = ctx.upsert_execute(body, max_rows=max_rows,
                                                    trace=trace)
            if client is not None and rows > 1 and status == 200:
                # admission spent ONE token; the other rows debit the
                # bucket too (on the loop thread — the governor is
                # single-threaded by construction), the bulk contract.
                # ONLY acknowledged work charges: an over-budget 429 was
                # rejected before any WAL/memtable work ran, and debiting
                # it anyway would let one oversized request starve the
                # client's legitimate follow-ups (the bulk path's
                # reject-before-charge precedent)
                self._loop.call_soon_threadsafe(
                    self.governor.charge, client, float(rows - 1)
                )
            if status == 200:
                ctx.maybe_flush_memtable()
            retry = 1 if status in (429, 503) else None
            return _resp(status, text, retry_after=retry)
        finally:
            ctx.release()

    def _regions_item(self, body: bytes, http11: bool = True,
                      client: str | None = None, max_ids: int | None = None,
                      deadline_t: float | None = None,
                      tid: str | None = None):
        """Batch region join: the bulk admission shape (slot + per-client
        budget) with the region streaming shape (a panel whose total row
        count exceeds the threshold streams chunked)."""
        ctx = self.ctx
        t0 = time.perf_counter()
        if deadline_t is not None and time.monotonic() >= deadline_t:
            ctx.deadline_shed("admission")
            return _error(504, MSG_DEADLINE_ADMISSION)
        if not ctx.admit():
            ctx.rejected("regions")
            return _error(429, MSG_CAPACITY_REGION, retry_after=1)
        trace = ctx.reqtrace.begin(tid, "regions") if tid is not None \
            else None
        fut = self._submit(
            self._regions_work, trace, body, t0, http11, client, max_ids,
            deadline_t
        )
        return ("exec", fut, "regions", t0, tid, trace)

    def _regions_work(self, body: bytes, t0: float, http11: bool = True,
                      client: str | None = None,
                      max_ids: int | None = None,
                      deadline_t: float | None = None, trace=None):
        """Executor half of a batch-region request.  Returns response
        bytes, or ``("stream", RegionsResult)`` for a panel whose total
        rendered rows exceed the stream threshold — the writer streams
        per-interval envelopes chunked and releases the admission slot
        when the body is done (exactly the single-region stream
        contract)."""
        ctx = self.ctx
        stream_holds_slot = False
        try:
            if deadline_t is not None and time.monotonic() >= deadline_t:
                ctx.deadline_shed("execute")
                return _error(504, MSG_DEADLINE_EXECUTE)
            if trace is not None:
                trace.since("admission", t0)
            clock = ctx.engine.panel_clock()
            try:
                with clock.span("regions.parse"):
                    specs, min_cadd, max_rank, limit, tokenize = \
                        parse_regions_body(body)
            except QueryError as err:
                ctx.errored("regions")
                return _error(400, str(err))
            if max_ids is not None and len(specs) > max_ids:
                # same bounded-debt contract as bulk /variants: a panel
                # the bucket could never repay within MAX_DEBT_S is
                # rejected before any scan runs
                ctx.rejected("regions")
                return _error(429, (
                    f"regions batch of {len(specs)} exceeds client rate "
                    f"budget ({max_ids} intervals); split the request"
                ), retry_after=1)
            if client is not None and len(specs) > 1:
                # admission spent ONE token; the other intervals debit
                # the bucket too (on the loop thread — the governor is
                # single-threaded by construction)
                self._loop.call_soon_threadsafe(
                    self.governor.charge, client, float(len(specs) - 1)
                )
            try:
                cap = ctx.governor.region_limit_cap()
                if cap is not None:
                    # brownout level >= 1: bound per-interval render work
                    limit = min(limit, cap)
                with reqtrace_mod.stage(trace, "device"):
                    result = ctx.engine.regions_serve(
                        specs,
                        min_cadd=min_cadd,
                        max_conseq_rank=max_rank,
                        limit=limit,
                        tokenize=tokenize,
                        clock=clock,
                    )
            except QueryError as err:
                ctx.errored("regions")
                return _error(400, str(err))
            except Exception as err:
                ctx.errored("regions")
                return _error(500, f"{type(err).__name__}: {err}")
            if http11 and result.returned > self.stream_threshold:
                stream_holds_slot = True
                return ("stream", result)  # the writer releases that slot
            with reqtrace_mod.stage(trace, "render"):
                with clock.span("regions.render"):
                    text = result.assemble()
                ctx.engine.regions_rendered(clock, streamed=False)
                resp = _resp(200, text)
                ctx.observe("regions", time.perf_counter() - t0,
                            rows=result.returned)
            return resp
        finally:
            if not stream_holds_slot:
                ctx.release()

    def _stats_item(self, body: bytes, client: str | None = None,
                    max_ids: int | None = None,
                    deadline_t: float | None = None,
                    tid: str | None = None):
        """Analytics panel: the bulk admission shape (slot + per-client
        budget); bodies are summaries, so there is no streaming shape."""
        ctx = self.ctx
        t0 = time.perf_counter()
        if deadline_t is not None and time.monotonic() >= deadline_t:
            ctx.deadline_shed("admission")
            return _error(504, MSG_DEADLINE_ADMISSION)
        if not ctx.admit():
            ctx.rejected("stats")
            return _error(429, MSG_CAPACITY_STATS, retry_after=1)
        trace = ctx.reqtrace.begin(tid, "stats") if tid is not None \
            else None
        fut = self._submit(
            self._stats_work, trace, body, t0, client, max_ids, deadline_t
        )
        return ("exec", fut, "stats", t0, tid, trace)

    def _stats_work(self, body: bytes, t0: float,
                    client: str | None = None,
                    max_ids: int | None = None,
                    deadline_t: float | None = None, trace=None) -> bytes:
        """Executor half of a stats request (parse, fused panel, render,
        account); never raises — errors become response bytes."""
        ctx = self.ctx
        try:
            if deadline_t is not None and time.monotonic() >= deadline_t:
                ctx.deadline_shed("execute")
                return _error(504, MSG_DEADLINE_EXECUTE)
            if trace is not None:
                trace.since("admission", t0)
            try:
                specs, metrics, windows = parse_stats_body(body)
            except QueryError as err:
                ctx.errored("stats")
                return _error(400, str(err))
            if max_ids is not None and len(specs) > max_ids:
                # the bounded-debt contract of bulk /variants: a panel
                # the bucket could never repay within MAX_DEBT_S is
                # rejected before any scan runs
                ctx.rejected("stats")
                return _error(429, (
                    f"stats batch of {len(specs)} exceeds client rate "
                    f"budget ({max_ids} intervals); split the request"
                ), retry_after=1)
            if client is not None and len(specs) > 1:
                # admission spent ONE token; the other intervals debit
                # the bucket too (on the loop thread — the governor is
                # single-threaded by construction)
                self._loop.call_soon_threadsafe(
                    self.governor.charge, client, float(len(specs) - 1)
                )
            try:
                with reqtrace_mod.stage(trace, "device"):
                    result = ctx.engine.stats_serve(
                        specs, metrics=metrics, windows=windows,
                    )
            except QueryError as err:
                ctx.errored("stats")
                return _error(400, str(err))
            except Exception as err:
                ctx.errored("stats")
                return _error(500, f"{type(err).__name__}: {err}")
            with reqtrace_mod.stage(trace, "render"):
                resp = _resp(200, result.assemble())
                ctx.observe("stats", time.perf_counter() - t0,
                            rows=result.returned)
            return resp
        finally:
            ctx.release()

    def _export_item(self, query: str, deadline_t: float | None = None,
                     tid: str | None = None):
        """``GET /export/stream``: the stats admission shape (inflight
        slot + deadline), execution through the shared payload builder
        on the executor (kernel pack + allele render are CPU/device
        work, never event-loop work — AVDB701)."""
        ctx = self.ctx
        t0 = time.perf_counter()
        if deadline_t is not None and time.monotonic() >= deadline_t:
            ctx.deadline_shed("admission")
            return _error(504, MSG_DEADLINE_ADMISSION)
        if not ctx.admit():
            ctx.rejected("export")
            return _error(429, MSG_CAPACITY_EXPORT, retry_after=1)
        trace = ctx.reqtrace.begin(tid, "export") if tid is not None \
            else None
        fut = self._submit(self._export_work, trace, query, t0, deadline_t)
        return ("exec", fut, "export", t0, tid, trace)

    def _export_work(self, query: str, t0: float,
                     deadline_t: float | None = None, trace=None) -> bytes:
        """Executor half of an export-stream request (parse, pack,
        render, account); never raises — errors become response bytes."""
        ctx = self.ctx
        try:
            if deadline_t is not None and time.monotonic() >= deadline_t:
                ctx.deadline_shed("execute")
                return _error(504, MSG_DEADLINE_EXECUTE)
            if trace is not None:
                trace.since("admission", t0)
            try:
                params = parse_stream_query(query)
            except ValueError as err:  # QueryError subclasses ValueError
                ctx.errored("export")
                return _error(400, str(err))
            try:
                with reqtrace_mod.stage(trace, "device"):
                    body, n_valid = stream_payload(ctx.engine, params)
            except QueryError as err:
                ctx.errored("export")
                return _error(400, str(err))
            except Exception as err:
                ctx.errored("export")
                return _error(500, f"{type(err).__name__}: {err}")
            resp = _resp(200, body)
            ctx.observe("export", time.perf_counter() - t0, rows=n_valid)
            return resp
        finally:
            ctx.release()

    def _region_item(self, spec: str, query: str, http11: bool = True,
                     deadline_t: float | None = None,
                     tid: str | None = None):
        ctx = self.ctx
        t0 = time.perf_counter()
        if deadline_t is not None and time.monotonic() >= deadline_t:
            ctx.deadline_shed("admission")
            return _error(504, MSG_DEADLINE_ADMISSION)
        if not ctx.admit():
            ctx.rejected("region")
            return _error(429, MSG_CAPACITY_REGION, retry_after=1)
        trace = ctx.reqtrace.begin(tid, "region") if tid is not None \
            else None
        fut = self._submit(
            self._region_work, trace, spec, query, t0, http11, deadline_t
        )
        return ("exec", fut, "region", t0, tid, trace)

    def _region_work(self, spec: str, query: str, t0: float,
                     http11: bool = True,
                     deadline_t: float | None = None, trace=None):
        """Executor half of a region request.  Returns response bytes, or
        ``("stream", page)`` — the writer task then streams it chunked and
        releases the admission slot when the body is done.  A non-1.1
        request always buffers (``stream_threshold=None``): chunked
        framing toward an HTTP/1.0 peer corrupts the body it cannot
        de-chunk."""
        ctx = self.ctx
        stream_holds_slot = False
        try:
            if deadline_t is not None and time.monotonic() >= deadline_t:
                ctx.deadline_shed("execute")
                return _error(504, MSG_DEADLINE_EXECUTE)
            if trace is not None:
                trace.since("admission", t0)
            try:
                min_cadd, max_rank, limit, cursor = \
                    parse_region_params(query)
                cap = ctx.governor.region_limit_cap()
                if cap is not None:
                    # brownout level >= 1: bound per-request render work
                    limit = min(limit, cap)
                with reqtrace_mod.stage(trace, "device"):
                    kind, payload = ctx.engine.region_serve(
                        spec,
                        min_cadd=min_cadd,
                        max_conseq_rank=max_rank,
                        limit=limit,
                        cursor=cursor,
                        stream_threshold=(
                            self.stream_threshold if http11 else None
                        ),
                    )
            except QueryError as err:
                ctx.errored("region")
                return _error(400, str(err))
            except Exception as err:
                ctx.errored("region")
                return _error(500, f"{type(err).__name__}: {err}")
            if kind == "text":
                m = _RETURNED_RE.search(payload[:256])
                returned = int(m.group(1)) if m else 0
                ctx.observe("region", time.perf_counter() - t0,
                            rows=returned)
                return _resp(200, payload)
            stream_holds_slot = True
            return ("stream", payload)  # the writer releases that slot
        finally:
            if not stream_holds_slot:
                ctx.release()

    # -- admission / freshness ----------------------------------------------

    def _client_key(self, headers: dict, writer) -> tuple:
        """(bucket key, clamped weight) for this request.  Only called
        with a live governor — key scoping lives in ``resolve_key``."""
        peer = writer.get_extra_info("peername")
        peer_key = str(peer[0]) if peer else "anonymous"
        key = self.governor.resolve_key(peer_key, headers.get("x-client-id"))
        try:
            weight = int(headers.get("x-client-weight", "1"))
        except ValueError:
            weight = 1
        return key, weight

    def _admit_client(self, headers: dict, writer) -> float:
        """Per-client weighted admission: 0.0 = run it, else retry-after."""
        if self.governor is None:
            return 0.0
        key, weight = self._client_key(headers, writer)
        return self.governor.admit(key, weight)

    def _maybe_refresh_snapshot(self) -> None:
        """TTL-coalesced freshness: the cheap due-check runs in-line; the
        (rare) stat+load runs on the pool so a commit swap never stalls
        the event loop — readers serve the old pin meanwhile."""
        due = self._refresh_due
        if due is not None and not self._refresh_inflight and due():
            # one in-flight refresh at a time: a saturated pool must not
            # accumulate duplicate no-op tasks behind slow region renders
            # (the flag flips on the loop thread only; the done-callback
            # reset races at worst into one extra due() check)
            self._refresh_inflight = True
            fut = self._pool.submit(self.ctx.refresh_snapshot)
            fut.add_done_callback(
                lambda _f: setattr(self, "_refresh_inflight", False)
            )

    # -- streaming ----------------------------------------------------------

    async def _stream_region(self, writer, page,
                             trace_id: str | None = None) -> None:
        """Chunked transfer of one RegionPage — or one RegionsResult,
        whose "rows" are whole per-interval envelopes (same
        prefix/rows/suffix surface): prefix, rows in
        ``_STREAM_ROWS_PER_CHUNK`` batches, suffix.  The rows render
        lazily, a block of ``engine.REGION_RENDER_BLOCK`` at a time as
        the chunks draw on them — RSS holds one chunk and the rest of
        one rendered block, not the body.  De-chunked, the bytes are
        exactly ``page.assemble()``.

        A SIGTERM drain (or the drain-budget cancellation) arriving
        mid-stream must not tear the chunked framing: the stream CLEANLY
        TRUNCATES — close the variants array at a row boundary, append a
        ``"truncated": true`` trailer field, and emit the terminating
        0-chunk — so the client holds valid JSON that SAYS it is partial
        instead of a connection reset it must guess about.

        What runs here between two ``await``s — framing, a chunk's render,
        its write — is the loop clock's ``stream`` section."""
        loop_clock = self.loop_clock
        t_sync = time.perf_counter_ns()
        head = _STATUS[200]
        if trace_id:
            head += _TRACE_HEADER_B + trace_id.encode("latin-1") + b"\r\n"
        writer.write(
            head
            + b"Content-Type: application/json\r\n"
            + b"Transfer-Encoding: chunked\r\n\r\n"
        )
        _write_chunk(writer, page.prefix().encode())
        # a panel's rows render inside ``avdb.regions.render``, one span a
        # chunk (which renders the next block of rows when the chunk
        # reaches past the last one), on this (the event loop's) thread —
        # never across the ``await`` between chunks, where other
        # connections run
        clock = getattr(page, "clock", None)
        chunks = _row_chunks(page.rows())
        first = True
        truncated = cancelled = False
        try:
            while True:
                if self._stop is not None and self._stop.is_set():
                    # graceful drain: finish THIS response as truncated
                    # within the budget instead of racing the cancel
                    truncated = True
                    break
                with (clock.span("regions.render") if clock is not None
                      else contextlib.nullcontext()):
                    chunk = next(chunks, None)  # renders what the chunk needs
                if chunk is None:
                    break
                _write_chunk(
                    writer, (("" if first else ",") + chunk).encode()
                )
                first = False
                loop_clock.stream_ns += time.perf_counter_ns() - t_sync
                await writer.drain()  # flow control + loop fairness
                t_sync = time.perf_counter_ns()
        except asyncio.CancelledError:
            # the drain budget expired with this stream still writing:
            # terminate the framing before the cancellation propagates
            # (the writes below are synchronous buffer appends)
            truncated = cancelled = True
            t_sync = time.perf_counter_ns()
        if clock is not None:  # a panel: its render, observed once
            self.ctx.engine.regions_rendered(clock, streamed=True)
        if truncated:
            _write_chunk(writer, b'],"truncated":true}')
        else:
            _write_chunk(writer, page.suffix().encode())
        writer.write(b"0\r\n\r\n")
        loop_clock.stream_ns += time.perf_counter_ns() - t_sync
        if cancelled:
            raise asyncio.CancelledError
        await writer.drain()


def _row_chunks(rows):
    """``rows`` (rendered lazily) joined into chunk-sized texts: at most
    ``_STREAM_ROWS_PER_CHUNK`` rows, and cut on a byte bound too — a
    RegionsResult "row" is a whole per-interval envelope, and 256 of those
    must not accumulate panel-sized RSS before the first write.  Each
    ``next`` draws one chunk's rows from ``rows``, which renders them a
    block at a time (``engine.REGION_RENDER_BLOCK``)."""
    buf: list[str] = []
    size = 0
    for row in rows:
        buf.append(row)
        size += len(row)
        if len(buf) >= _STREAM_ROWS_PER_CHUNK or size >= _WRITE_HIGH_WATER:
            yield ",".join(buf)
            buf, size = [], 0
    if buf:
        yield ",".join(buf)


def _write_chunk(writer, data: bytes) -> None:
    if data:
        writer.write(b"%x\r\n" % len(data) + data + b"\r\n")


def build_aio_server(store_dir: str | None = None, manager=None,
                     host: str = "127.0.0.1", port: int = 0, sock=None,
                     max_batch: int | None = None,
                     max_wait_s: float | None = None,
                     max_queue: int | None = None,
                     region_cache_size: int | None = None,
                     registry: MetricsRegistry | None = None,
                     residency=None, memtable=None,
                     client_rate: float | None = None,
                     stream_threshold: int | None = None,
                     heartbeat_file: str | None = None,
                     heartbeat_index: int = 0,
                     tracer=None, log=None, flight=None,
                     telemetry_dir: str | None = None,
                     health=None) -> AioServer:
    """Wire manager -> engine -> batcher -> event-loop server (not yet
    serving; call ``serve_forever`` or ``start_background``).  The caller
    owns shutdown order: ``server.shutdown()`` then
    ``server.ctx.batcher.close()``."""
    if manager is None:
        if store_dir is None:
            raise ValueError("build_aio_server needs store_dir or manager")
        manager = SnapshotManager(store_dir, log=log)
    registry = registry if registry is not None else MetricsRegistry()
    from annotatedvdb_tpu.serve.mesh_exec import serve_mesh_executor

    breaker = DeviceBreaker(registry=registry, log=log)
    engine = QueryEngine(
        manager, registry=registry, region_cache_size=region_cache_size,
        residency=residency, breaker=breaker,
        # the mesh state budget rides the residency manager's already-
        # split per-device share (env/flag -> per-worker -> per-device),
        # never the raw env
        mesh=serve_mesh_executor(
            registry=registry, breaker=breaker, log=log,
            budget_bytes=residency.budget if residency is not None
            else None,
        ),
    )
    batcher = LoopBatcher(
        engine, max_batch=max_batch, max_wait_s=max_wait_s,
        max_queue=max_queue, tracer=tracer, registry=registry,
    )
    ctx = ServeContext(manager, engine, batcher, registry,
                       memtable=memtable, log=log, flight=flight,
                       telemetry_dir=telemetry_dir, tracer=tracer,
                       worker_index=heartbeat_index, health=health)
    return AioServer(
        ctx, host=host, port=port, sock=sock, client_rate=client_rate,
        stream_threshold=stream_threshold,
        heartbeat_file=heartbeat_file, heartbeat_index=heartbeat_index,
    )
