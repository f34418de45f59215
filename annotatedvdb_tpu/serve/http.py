"""The serving API's shared grammar: route spellings, body and query
parsers, payload builders, message constants and :class:`ServeContext`
(admission, per-kind metrics, the upsert gate) — everything about a
response that is not socket work.  The one front end
(:mod:`annotatedvdb_tpu.serve.aio`) renders from these.  The route
surface is deliberately small:

====================================  =====================================
``GET /healthz``                      liveness + pinned generation + rows
``GET /metrics``                      Prometheus exposition of the registry
``GET /stats``                        batcher/coalescing + snapshot summary
``GET /variant/<chr:pos:ref:alt>``    point lookup (through the batcher);
                                      404 when absent
``POST /variants``                    bulk: body ``{"ids": [...]}`` →
                                      ``{"results": [rec|null, ...]}``
``GET /region/<chr:start-end>``       region query; ``?minCadd=``,
                                      ``maxConseqRank=``, ``limit=``
``POST /regions``                     batch region join: body
                                      ``{"regions": [...]}`` (+ optional
                                      ``minCadd``/``maxConseqRank``/
                                      ``limit``/``tokenize``) → per-interval
                                      envelopes byte-identical to N single
                                      ``/region`` calls, answered by ONE
                                      BITS kernel call per chromosome group
====================================  =====================================

Admission is bounded everywhere: point queries reject with **429** when the
batcher queue is at ``AVDB_SERVE_MAX_QUEUE``; bulk/region requests count
against an in-flight cap (same bound) and 429 the overflow — so a traffic
spike degrades to fast rejections, never an unbounded thread/memory pile
(the serving twin of the pipeline's bounded-queue backpressure, and the
depth numbers ride the same ``StageStats`` shape).

Every data route refreshes the snapshot pin first (one ``stat`` on the
manifest), so a loader commit becomes visible within one request with no
background poller; client errors map to 400, admission to 429, absence to
404, engine faults to 500 — and the error body is always JSON.
"""

from __future__ import annotations

import itertools
import json
import os
import re
import threading
import time
from urllib.parse import parse_qs

#: pulls "returned":N out of the region envelope prefix (fixed field order)
_RETURNED_RE = re.compile(r'"returned":(\d+)')

from annotatedvdb_tpu.obs import reqtrace as reqtrace_mod
from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.obs.reqtrace import TraceRecorder
from annotatedvdb_tpu.obs.slo import worst_of
from annotatedvdb_tpu.obs.timeseries import derive_series, load_history
from annotatedvdb_tpu.serve import resilience
from annotatedvdb_tpu.serve.engine import (
    QueryEngine,
    QueryError,
    parse_variant_id,
)
from annotatedvdb_tpu.serve.resilience import OverloadGovernor, PointCache
from annotatedvdb_tpu.utils.locks import make_lock

#: per-request latency histogram edges (seconds; sub-ms to 2.5s)
QUERY_SECONDS_EDGES = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5,
)

#: default row cap for region responses (explicit ``?limit=`` overrides)
DEFAULT_REGION_LIMIT = 10_000


def healthz_payload(ctx) -> str:
    """The ``/healthz`` body.  ``/healthz`` is LIVENESS
    (the process answers); the ``ready`` field mirrors ``/readyz``
    (readiness: route traffic here or not)."""
    snap = ctx.manager.current()
    ready, _reason = ctx.ready_state()
    return json.dumps({
        "status": "ok",
        "ready": ready,
        "generation": snap.generation,
        "rows": snap.store.n,
        "shards": len(snap.store.shards),
        "queue_depth": ctx.batcher.depth(),
        "brownout_level": ctx.governor.level,
        "brownout": ctx.governor.level_name,
        "breaker_open": len(
            ctx.engine.breaker.open_groups()
        ) if ctx.engine.breaker is not None else 0,
        # the alert plane's one-glance summary: how many SLOs are
        # firing, and the worst alert state ("disabled" when the health
        # plane is off — absence must be distinguishable from health)
        "alerts_firing": ctx.health.slos.firing()
        if ctx.health is not None else 0,
        "alerts": ctx.health.slos.worst_state()
        if ctx.health is not None else "disabled",
    })


def readyz_payload(ctx) -> tuple[int, str]:
    """(status, body) for ``/readyz`` — readiness is distinct from
    liveness: a worker warming a snapshot swap or browned out past the
    shed-bulk rung answers 503 so a fleet router drains traffic off it
    while the supervisor leaves it alone (it is alive, just not ready)."""
    ready, reason = ctx.ready_state()
    body = json.dumps({"ready": ready, "reason": reason})
    return (200 if ready else 503), body


def stats_payload(ctx) -> str:
    """The ``/stats`` body — shared like :func:`healthz_payload`."""
    from annotatedvdb_tpu.store.variant_store import device_lookup_state
    from annotatedvdb_tpu.utils.runtime import compile_summary

    snap = ctx.manager.current()
    stats = {
        "generation": snap.generation,
        "rows": snap.store.n,
        "snapshot_swaps": ctx.manager.swaps,
        "batcher": ctx.batcher.drain_stats(),
        "device": ctx.device,
        "compile": compile_summary(),
        "device_lookup": device_lookup_state(),
        "render_cache": {"hits": ctx.engine.render_cache_hits,
                         "misses": ctx.engine.render_cache_misses},
        "render_batch": {"rows": ctx.engine.render_batch_rows,
                         "scalar_rows": ctx.engine.render_scalar_rows},
        "region_index": ctx.engine.region_index_stats(),
        "region_panels": dict(ctx.engine.region_panels),
    }
    if ctx.engine.residency is not None:
        stats["residency"] = ctx.engine.residency.stats()
    if hasattr(ctx.manager, "stats"):
        stats["snapshot"] = ctx.manager.stats()
    if ctx.memtable is not None:
        stats["memtable"] = dict(ctx.memtable.stats(),
                                 read_hits=ctx.engine.memtable_read_hits)
    if hasattr(ctx.manager, "generations"):
        stats["overlay"] = {"generations": ctx.manager.generations}
    stats["brownout"] = ctx.governor.stats()
    if ctx.loop_clock is not None:
        # the event loop's own account, computed now (this runs on the
        # loop); a read starts ``max_turn_ms``'s next window
        stats["loop"] = ctx.loop_clock.stats(ctx.batcher.drain_ns,
                                             reset_recent=True)
    if ctx.engine.breaker is not None:
        stats["breaker"] = ctx.engine.breaker.stats()
    if ctx.engine.mesh is not None:
        stats["mesh"] = ctx.engine.mesh.stats()
    return json.dumps(stats)


#: the trace-id echo header returned on EVERY response — the one
#: response-shaping constant of the request-tracing plane (serve/aio.py
#: imports it, never re-spells it)
TRACE_HEADER = "X-Request-Id"

_TRACEPARENT_RE = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-[0-9a-f]{16}-[0-9a-f]{2}$"
)
_TRACE_ID_STRIP_RE = re.compile(r"[^0-9A-Za-z._:\-]")

#: minted-id generator state: 96 random bits drawn ONCE per process + a
#: 32-bit counter.  ``os.urandom`` per request would be a getrandom(2)
#: syscall on the serving hot path (~9µs here, far worse on syscall-
#: expensive sandboxes) — trace ids need uniqueness, not cryptographic
#: freshness, and a counter under a process-unique prefix delivers that
#: for sub-µs
_MINT_PREFIX = os.urandom(12).hex()
_MINT_SEQ = itertools.count(1)


def resolve_trace_id(traceparent: str | None,
                     x_request_id: str | None) -> str:
    """The request's trace id — the ONE resolution (the echoed header
    is a function of the request's headers alone).

    Preference order: a well-formed W3C ``traceparent`` contributes its
    trace-id field; else a client ``X-Request-Id`` (sanitized to header-
    safe characters, capped at 64) is adopted verbatim; else a fresh
    128-bit hex id (96 process-unique bits + a counter — no syscall on
    the hot path) is minted at admission."""
    if traceparent:
        m = _TRACEPARENT_RE.match(traceparent.strip().lower())
        if m and m.group(1) != "0" * 32:
            return m.group(1)
    if x_request_id:
        tid = _TRACE_ID_STRIP_RE.sub("", x_request_id.strip())[:64]
        if tid:
            return tid
    return _MINT_PREFIX + format(next(_MINT_SEQ) & 0xFFFFFFFF, "08x")


def chaos_enabled_from_env() -> bool:
    """``AVDB_SERVE_CHAOS`` — gates the runtime fault-arming route
    (``POST /_chaos``) AND the on-demand trace dump
    (``GET /debug/trace``).  Resolved HERE once; on a production server
    both routes 404
    byte-identically to any unknown route."""
    return os.environ.get("AVDB_SERVE_CHAOS", "") == "1"


def debug_trace_payload(ctx) -> str:
    """The ``GET /debug/trace`` body — this worker's span ring as Chrome
    trace-event JSON, merged with the PR-2 batcher tracer's drain spans
    on one timebase when the server runs with ``--traceOut``.  Chaos-
    gated like ``/_chaos`` (a trace dump is a debugging surface, not a
    production route)."""
    tracer = ctx.tracer
    base_ns = tracer._t0 if tracer is not None else ctx.reqtrace.t0_ns
    events = ctx.reqtrace.chrome_events(base_ns=base_ns)
    if tracer is not None:
        events += tracer.events()
    events.sort(key=lambda e: e.get("ts", 0))
    return json.dumps({"traceEvents": events, "displayTimeUnit": "ms"})


def metrics_payload(ctx, query: str) -> str:
    """The ``GET /metrics`` body.  Plain scrape = this worker's registry; ``?fleet=1`` = the
    fleet-wide view (workers' published snapshots summed/maxed, plus the
    supervisor's ``avdb_fleet_*`` series), answered by WHICHEVER worker
    the kernel handed the connection to."""
    params = parse_qs(query or "")
    if params.get("fleet", ["0"])[0] not in ("1", "true"):
        return ctx.registry.render_prometheus()
    return ctx.fleet_metrics()


def _fleet_wanted(query: str) -> bool:
    return parse_qs(query or "").get("fleet", ["0"])[0] in ("1", "true")


def _health_sibling_docs(ctx) -> dict:
    """Sibling workers' persisted health documents for the ``?fleet=1``
    alert/history views, keyed by worker index: the live ``w*.ts.json``
    mirrors under ``<store>/history``, TTL-aged exactly like the fleet
    metric snapshots (a dead worker's last mirror must age out — its
    HARVESTED history is ``doctor slo``'s business, not the live view's).
    Self is excluded; the live plane is fresher."""
    h = ctx.health
    docs: dict[int, dict] = {}
    if h is None or h.ring.path is None:
        return docs
    d = os.path.dirname(h.ring.path)
    now = time.time()
    if os.path.isdir(d):
        for fname in sorted(os.listdir(d)):
            if not fname.endswith(".ts.json"):
                continue
            try:
                doc = load_history(os.path.join(d, fname))
            except (OSError, ValueError, TypeError):
                continue  # torn persist race: skip, never fail a read
            idx = int(doc.get("worker", -1))
            if idx == ctx.worker_index:
                continue  # self: the live plane is fresher
            if now - float(doc.get("t", 0)) > ctx.FLEET_SNAPSHOT_TTL_S:
                continue  # a dead worker's stale mirror
            docs[idx] = doc
    return docs


def alerts_payload(ctx, query: str) -> str:
    """The ``GET /alerts`` body.  Plain = this worker's live SLO alert states;
    ``?fleet=1`` = per-worker states (self live, siblings from their
    persisted history mirrors, which carry the alert rows), rolled up
    into a fleet-wide ``firing`` count and worst ``state``."""
    h = ctx.health

    def solo() -> dict:
        if h is None or not h.enabled:
            return {"enabled": False, "worker": ctx.worker_index,
                    "state": "disabled", "firing": 0, "alerts": []}
        return {
            "enabled": True,
            "worker": ctx.worker_index,
            "state": h.slos.worst_state(),
            "firing": h.slos.firing(),
            "burn_threshold": h.slos.burn_threshold,
            "windows": {"fast_s": h.slos.fast_s, "slow_s": h.slos.slow_s},
            "alerts": h.slos.alerts(),
        }

    me = solo()
    if not _fleet_wanted(query):
        return json.dumps(me)
    workers = {str(ctx.worker_index): me}
    for idx, doc in _health_sibling_docs(ctx).items():
        rows = doc.get("alerts") or []
        workers[str(idx)] = {
            "enabled": True,
            "worker": idx,
            "state": worst_of(a.get("state", "ok") for a in rows),
            "firing": int(doc.get("firing") or 0),
            "alerts": rows,
        }
    return json.dumps({
        "fleet": True,
        "firing": sum(w["firing"] for w in workers.values()),
        "state": worst_of(w["state"] for w in workers.values()
                          if w["state"] != "disabled"),
        "workers": workers,
    })


#: the history route spelling, single-sourced
HISTORY_ROUTE = "/metrics/history"


def metrics_history_payload(ctx, query: str) -> str:
    """The ``GET /metrics/history`` body — the time-series ring rendered
    as derived series (counters as per-interval rates, histograms as
    rate + p50/p99).  ``?window=S`` trims to the trailing S seconds (an
    unparsable value is ignored — a read surface does not 400 on a
    sloppy dashboard); ``?fleet=1`` = per-worker documents, self live
    and siblings from their persisted mirrors."""
    h = ctx.health
    params = parse_qs(query or "")
    try:
        window = float(params.get("window", [""])[0])
    except (ValueError, IndexError):
        window = 0.0

    def trim(samples: list) -> list:
        if window <= 0 or len(samples) < 2:
            return samples
        cutoff = float(samples[-1]["t"]) - window
        return [s for s in samples if float(s["t"]) >= cutoff]

    def render(worker: int, tick_s, history_s, samples: list) -> dict:
        samples = trim(samples)
        return {
            "enabled": True,
            "worker": worker,
            "tick_s": tick_s,
            "history_s": history_s,
            "samples": len(samples),
            "span_s": round(
                float(samples[-1]["t"]) - float(samples[0]["t"]), 3
            ) if len(samples) >= 2 else 0.0,
            "series": derive_series(samples),
        }

    def solo() -> dict:
        if h is None or not h.enabled:
            return {"enabled": False, "worker": ctx.worker_index,
                    "samples": 0, "span_s": 0.0, "series": []}
        return render(ctx.worker_index, h.ring.tick_s, h.ring.history_s,
                      h.ring.samples())

    me = solo()
    if not _fleet_wanted(query):
        return json.dumps(me)
    workers = {str(ctx.worker_index): me}
    for idx, doc in _health_sibling_docs(ctx).items():
        workers[str(idx)] = render(
            idx, doc.get("tick_s"), doc.get("history_s"),
            doc.get("samples") or [],
        )
    return json.dumps({"fleet": True, "workers": workers})


def parse_region_params(query: str):
    """``(min_cadd, max_conseq_rank, limit, cursor)`` from a region query
    string — the ONE parsing contract (single ``/region`` reads and the
    batch API's per-interval envelopes are pinned byte-identical, so the
    parameter grammar must not fork).  Raises :class:`QueryError` on a bad value;
    ``keep_blank_values`` so ``?cursor=`` (start a paged walk) survives."""
    params = parse_qs(query, keep_blank_values=True)

    def num(name, cast):
        vals = params.get(name)
        # a blank value ("?minCadd=&...", an unfilled client template) is
        # an absent filter, exactly as before keep_blank_values (which
        # only exists so a blank ?cursor= survives)
        if not vals or vals[0] == "":
            return None
        try:
            return cast(vals[0])
        except ValueError:
            raise QueryError(
                f"bad query parameter {name}={vals[0]!r}"
            ) from None

    limit = num("limit", int)  # explicit 0 = count-only query
    return (
        num("minCadd", float),
        num("maxConseqRank", int),
        DEFAULT_REGION_LIMIT if limit is None else limit,
        params.get("cursor", [None])[0],  # "" starts paging
    )


#: the one grammar message for a malformed /regions body
REGIONS_BODY_ERROR = (
    'regions body must be {"regions": ["chr:start-end", ...]} with '
    'optional numeric "minCadd"/"maxConseqRank"/"limit" and boolean '
    '"tokenize"'
)

#: response-shaping messages — one constant each; ``serve/aio.py``
#: imports them, and the tests compare response bodies with them
BULK_BODY_ERROR = 'bulk body must be {"ids": ["chr:pos:ref:alt", ...]}'
MSG_DEADLINE_ADMISSION = "deadline exhausted at admission"
MSG_DEADLINE_EXECUTE = "deadline exhausted before execution"
MSG_BROWNOUT_UPSERT = (
    "brownout: upserts shed (point reads keep serving)"
)
MSG_CAPACITY_UPSERT = "server at capacity (upsert admission bound)"
MSG_UPSERTS_DISABLED = (
    "upserts are not enabled on this server (start with --upserts or "
    "AVDB_SERVE_UPSERTS=1)"
)
#: the 507 Insufficient Storage body — ONE constant: free disk under the store fell below the configured reserve, so
#: new writes are refused while everything that HOLDS or RECLAIMS space
#: keeps running
MSG_DISK_RESERVE = (
    "insufficient storage: free disk space is below the configured "
    "reserve (AVDB_STORE_DISK_RESERVE_BYTES); upserts are suspended "
    "until space is freed — reads, flushes of acknowledged rows, and "
    "compaction keep running"
)

#: the one grammar message for a malformed /variants/upsert body
UPSERT_BODY_ERROR = (
    'upsert body must be {"variants": [{"id": "chr:pos:ref:alt", '
    '"ref_snp": N?, "annotations": {<jsonb column>: <value>, ...}?}, ...]}'
)

#: rows per upsert call cap (a request is one WAL frame + one ack fsync;
#: bigger batches belong to the offline loaders)
UPSERT_MAX_ROWS = 4096

#: the live-write route path
UPSERT_ROUTE = "/variants/upsert"


def parse_upsert_body(body: bytes) -> list[dict]:
    """Validated entries from a ``POST /variants/upsert`` JSON body — the
    ONE body grammar (the :func:`parse_region_params` convention).
    Returns
    ``[{"id", "ref_snp", "annotations"}, ...]``; raises
    :class:`QueryError` on any malformed field (the whole call fails —
    an upsert is atomic per request, never partially applied)."""
    from annotatedvdb_tpu.store.variant_store import JSONB_COLUMNS

    try:
        obj = json.loads(body or b"{}")
    except ValueError:
        raise QueryError(UPSERT_BODY_ERROR) from None
    if not isinstance(obj, dict):
        raise QueryError(UPSERT_BODY_ERROR)
    variants = obj.get("variants")
    if not isinstance(variants, list) or not variants \
            or not all(isinstance(v, dict) for v in variants):
        raise QueryError(UPSERT_BODY_ERROR)
    if len(variants) > UPSERT_MAX_ROWS:
        raise QueryError(
            f"upsert of {len(variants)} rows exceeds the "
            f"{UPSERT_MAX_ROWS}-row cap; split the request (bulk loads "
            "belong to the offline loader CLIs)"
        )
    out = []
    for v in variants:
        vid = v.get("id")
        if not isinstance(vid, str):
            raise QueryError(UPSERT_BODY_ERROR)
        rs = v.get("ref_snp")
        if rs is not None and (isinstance(rs, bool)
                               or not isinstance(rs, int) or rs < 0):
            raise QueryError(f"bad upsert field ref_snp={rs!r}")
        ann = v.get("annotations")
        if ann is not None:
            if not isinstance(ann, dict):
                raise QueryError(UPSERT_BODY_ERROR)
            for col in ann:
                if col not in JSONB_COLUMNS:
                    raise QueryError(
                        f"unknown annotation column {col!r} (one of: "
                        + ", ".join(JSONB_COLUMNS) + ")"
                    )
        out.append({"id": vid, "ref_snp": rs, "annotations": ann})
    return out
MSG_BROWNOUT_BULK = (
    "brownout: bulk reads shed (point reads keep serving)"
)
MSG_BROWNOUT_REGION = (
    "brownout: region reads shed (point reads keep serving)"
)
MSG_BROWNOUT_STATS = (
    "brownout: analytics queries shed (point reads keep serving)"
)
MSG_CAPACITY_BULK = "server at capacity (bulk admission bound)"
MSG_CAPACITY_REGION = "server at capacity (region admission bound)"
MSG_CAPACITY_STATS = "server at capacity (stats admission bound)"
MSG_BROWNOUT_EXPORT = (
    "brownout: export reads shed (point reads keep serving)"
)
MSG_CAPACITY_EXPORT = "server at capacity (export admission bound)"

#: the analytics route path (the UPSERT_ROUTE convention)
STATS_ROUTE = "/stats/region"

#: the one grammar message for a malformed /stats/region body
STATS_BODY_ERROR = (
    'stats body must be {"regions": ["chr:start-end", ...]} with '
    'optional "metrics" (a non-empty subset of ["af", "cadd", '
    '"conseq"]) and integer "windows"'
)


def parse_stats_body(body: bytes):
    """``(specs, metrics, windows)`` from a ``POST /stats/region`` JSON
    body — the ONE parsing contract (the :func:`parse_region_params`
    convention).  Shape/type errors raise
    :class:`QueryError` here; value-level grammar (per-spec region
    syntax, unknown metric names, the windows range) is validated by the
    engine, which fails the one caller the same way."""
    try:
        obj = json.loads(body or b"{}")
    except ValueError:
        raise QueryError(STATS_BODY_ERROR) from None
    if not isinstance(obj, dict):
        raise QueryError(STATS_BODY_ERROR)
    specs = obj.get("regions")
    if not isinstance(specs, list) \
            or not all(isinstance(s, str) for s in specs):
        raise QueryError(STATS_BODY_ERROR)
    metrics = obj.get("metrics")
    if metrics is not None and (
            not isinstance(metrics, list)
            or not all(isinstance(m, str) for m in metrics)):
        raise QueryError(STATS_BODY_ERROR)
    windows = obj.get("windows")
    if windows is not None and (isinstance(windows, bool)
                                or not isinstance(windows, int)):
        raise QueryError(f"bad stats field windows={windows!r}")
    return specs, metrics, windows


def parse_regions_body(body: bytes):
    """``(specs, min_cadd, max_conseq_rank, limit, tokenize)`` from a
    ``POST /regions`` JSON body — the ONE parsing contract (the
    :func:`parse_region_params` convention: the batch
    API's per-interval envelopes are pinned byte-identical to N single
    ``/region`` calls, so the parameter grammar must not fork either).
    Raises :class:`QueryError` on any malformed field; the per-spec
    region grammar itself is validated by the engine (one bad spec fails
    the call, the bulk-``/variants`` contract)."""
    try:
        obj = json.loads(body or b"{}")
    except ValueError:
        raise QueryError(REGIONS_BODY_ERROR) from None
    if not isinstance(obj, dict):
        raise QueryError(REGIONS_BODY_ERROR)
    specs = obj.get("regions")
    if not isinstance(specs, list) \
            or not all(isinstance(s, str) for s in specs):
        raise QueryError(REGIONS_BODY_ERROR)

    def num(name, kinds):
        v = obj.get(name)
        if v is None:
            return None
        if isinstance(v, bool) or not isinstance(v, kinds):
            raise QueryError(f"bad regions field {name}={v!r}")
        return v

    limit = num("limit", int)
    tokenize = obj.get("tokenize", False)
    if not isinstance(tokenize, bool):
        raise QueryError(f"bad regions field tokenize={tokenize!r}")
    return (
        specs,
        num("minCadd", (int, float)),
        num("maxConseqRank", int),
        DEFAULT_REGION_LIMIT if limit is None else limit,
        tokenize,
    )


#: the replication ship route spellings — single-sourced (the
#: UPSERT_ROUTE convention); the follower's tailer
#: (``store/replication.py``) fetches exactly these paths
REPL_MANIFEST_ROUTE = "/repl/manifest"
REPL_SEGMENT_ROUTE = "/repl/segment"
REPL_WAL_ROUTE = "/repl/wal"

#: server-side ceiling on one ship range read (the follower chunks at
#: AVDB_REPL_CHUNK_BYTES; this bounds a misconfigured client's single-
#: request memory on the leader)
REPL_MAX_RANGE_BYTES = 64 << 20

#: the 404 body when the ship surface has no on-disk store to serve from
#: (in-memory test/bench stores)
MSG_REPL_UNAVAILABLE = (
    "replication ship surface unavailable: this server has no on-disk "
    "store directory"
)


def follower_upsert_payload(ctx) -> str:
    """The 403 body an upsert gets on a replication follower — carries
    the leader's location so a well-behaved client redirects its writes
    (ONE builder)."""
    return json.dumps({
        "error": "this server is a replication follower (read-only); "
                 "send writes to the leader",
        "leader": ctx.follow_url,
    })


def repl_manifest_payload(ctx) -> tuple[int, str]:
    """(status, body) for ``GET /repl/manifest`` — the leader's ship
    document (the consistent snapshot cut plus the WAL/ledger stable-
    prefix listing), built by
    :func:`annotatedvdb_tpu.store.replication.ship_manifest`.  ONE
    builder; the front end runs it on the executor pool (it stats and
    reads files — AVDB701)."""
    if ctx.repl_store_dir is None:
        return 404, json.dumps({"error": MSG_REPL_UNAVAILABLE})
    from annotatedvdb_tpu.store.replication import ReplError, ship_manifest

    try:
        return 200, json.dumps(ship_manifest(ctx.repl_store_dir))
    except ReplError as err:
        return 503, json.dumps({"error": str(err)})


def repl_file_response(ctx, query: str) -> tuple[int, "bytes | str"]:
    """(status, body) for ``GET /repl/{segment,wal}?name=&offset=&limit=``
    — raw bytes (200) of one shippable file range, clamped to the file's
    stable prefix for WAL/ledger streams; a JSON error string otherwise.
    Both ship routes share this builder: the NAME (validated against the
    ship namespace by ``ship_file_range``) decides the clamping, never
    the route spelling — so a torn frame can never ship regardless of
    which route a client picked."""
    if ctx.repl_store_dir is None:
        return 404, json.dumps({"error": MSG_REPL_UNAVAILABLE})
    params = parse_qs(query or "")
    name = (params.get("name") or [""])[0]
    try:
        offset = int((params.get("offset") or ["0"])[0])
        limit = int((params.get("limit") or [str(REPL_MAX_RANGE_BYTES)])[0])
    except ValueError:
        return 400, json.dumps(
            {"error": "repl range: offset/limit must be integers"}
        )
    from annotatedvdb_tpu.store.replication import ship_file_range

    blob = ship_file_range(
        ctx.repl_store_dir, name, offset, min(limit, REPL_MAX_RANGE_BYTES)
    )
    if blob is None:
        return 404, json.dumps({"error": f"not a shippable file: {name!r}"})
    return 200, blob


class ServeContext:
    """Everything a request needs of the process, shared across requests."""

    #: published worker metric snapshots older than this are a dead
    #: worker's leavings and drop out of the fleet view
    FLEET_SNAPSHOT_TTL_S = 15.0

    def __init__(self, manager, engine: QueryEngine, batcher,
                 registry: MetricsRegistry, max_inflight: int | None = None,
                 memtable=None, log=None, flight=None,
                 telemetry_dir: str | None = None, tracer=None,
                 worker_index: int = 0, health=None):
        self.manager = manager
        self.engine = engine
        self.batcher = batcher
        self.registry = registry
        #: the observability plane: crash flight recorder (obs/flight.py,
        #: None = disabled), the request-trace recorder (span ring +
        #: avdb_stage_seconds + slow log), the PR-2 batcher tracer (for
        #: the merged /debug/trace dump), and the fleet telemetry dir
        #: workers publish metric snapshots into
        self.flight = flight
        self.tracer = tracer
        self.telemetry_dir = telemetry_dir
        #: the health plane (obs/slo.HealthPlane, None = disabled): the
        #: metrics time-series ring + SLO burn-rate evaluator, ticked
        #: from the server's maintenance tick via the executor pool
        self.health = health
        self.worker_index = int(worker_index)
        self.started_t = time.time()
        #: the serving event loop's clock (``obs/loopclock.py``); the
        #: server that runs the loop sets it, a context without one has
        #: no ``/stats`` ``loop`` block
        self.loop_clock = None
        #: the device this process serves from, as JAX reports it
        #: (``/stats``); resolved once
        from annotatedvdb_tpu.utils.runtime import device_summary

        self.device = device_summary()
        self.debug_trace_enabled = chaos_enabled_from_env()
        #: the live write path (``store/memtable.py``), or None for the
        #: historical read-only server — the upsert route answers
        #: MSG_UPSERTS_DISABLED when unset
        self.memtable = memtable
        #: replication plane.  The ship surface (``GET /repl/*``) serves
        #: from the snapshot manager's on-disk store directory (None for
        #: in-memory stores: the routes 404).  A follower's serve path
        #: sets ``repl`` to its ReplicaTailer (lag gates /readyz) and
        #: ``follow_url`` to the leader base URL (upserts answer 403
        #: pointing there).
        self.repl_store_dir = getattr(
            getattr(manager, "base", manager), "store_dir", None
        )
        self.repl = None
        self.follow_url = None
        self.max_inflight = (
            max_inflight if max_inflight is not None else batcher.max_queue
        )
        self.log = log if log is not None else (lambda msg: None)
        #: disk-pressure degradation (``store/maintenance.py``): while
        #: free disk under the store sits below
        #: AVDB_STORE_DISK_RESERVE_BYTES, upserts answer 507
        #: (upsert_execute below is the one gate).
        #: None when the server is read-only or the store has no
        #: directory (in-memory test stores)
        self.disk_guard = None
        if memtable is not None \
                and getattr(memtable, "store_dir", None):
            from annotatedvdb_tpu.store.maintenance import DiskReserveGuard

            self.disk_guard = DiskReserveGuard(
                memtable.store_dir, log=self.log
            )
        self._lock = make_lock("serve.ctx.inflight")
        #: guarded by self._lock
        self._inflight = 0
        #: default per-request deadline budget (0 = none unless the client
        #: sends X-Deadline-Ms)
        self.default_deadline_s = resilience.default_deadline_s()
        #: the brownout ladder: fed by observe(), stepped on the server's
        #: maintenance tick AND (time-gated) on request completion
        self.governor = OverloadGovernor(
            depth_fn=batcher.depth, max_queue=batcher.max_queue,
            registry=registry, on_change=self._brownout_event,
        )
        self.reqtrace = TraceRecorder(registry, log=self.log, flight=flight)
        # background writers (memtable flushes, compaction groups, WAL
        # rotations) join this worker's observability plane through the
        # module sink — the store layer never imports serve code
        reqtrace_mod.set_background_sink(
            self.reqtrace.background,
            flight.event if flight is not None else None,
        )
        if engine.breaker is not None and flight is not None:
            # breaker trips / re-closes land on the flight timeline
            engine.breaker.events = flight.event
        #: generation-keyed id -> record cache (the cache_first rung)
        self.point_cache = PointCache()
        self._m_inflight = registry.gauge(
            "avdb_serve_inflight", "bulk/region requests being executed"
        )
        self._m_swaps = registry.counter(
            "avdb_serve_snapshot_swaps_total",
            "store generation swaps observed by the server",
        )
        self._m_deadline_shed = {
            stage: registry.counter(
                "avdb_deadline_shed_total",
                "requests shed because their deadline budget ran out",
                {"stage": stage},
            )
            for stage in ("admission", "execute")
        }
        self._m_brownout_shed = registry.counter(
            "avdb_serve_brownout_shed_total",
            "bulk/region requests rejected by the brownout ladder",
        )
        self._m_point_cache_hits = registry.counter(
            "avdb_serve_point_cache_hits_total",
            "point reads served cache-first under brownout",
        )
        self._m_abandoned = registry.counter(
            "avdb_serve_abandoned_responses_total",
            "responses dropped because the client connection died first",
        )
        self._m_upsert_requests = registry.counter(
            "avdb_upsert_requests_total", "upsert requests acknowledged"
        )
        self._m_upsert_rows = registry.counter(
            "avdb_upsert_rows_total", "upsert rows accepted into the memtable"
        )
        self._m_upsert_rejected = registry.counter(
            "avdb_upsert_rejected_total",
            "upsert rows not applied (shadowed by an existing row under "
            "the first-wins policy, or duplicated within the batch)",
        )
        self._m_upsert_ack = registry.histogram(
            "avdb_upsert_ack_seconds", QUERY_SECONDS_EDGES,
            "upsert latency from arrival to durable acknowledgement",
        )
        self._m_upsert_disk_shed = registry.counter(
            "avdb_upsert_disk_shed_total",
            "upserts answered 507 under the free-disk reserve guard "
            "(AVDB_STORE_DISK_RESERVE_BYTES)",
        )
        # per-kind series resolved ONCE: the registry probe (lock + label
        # key assembly) is measurable at serving QPS, so the hot path
        # indexes a dict instead of re-registering per request
        self._kind = {}
        for kind in ("point", "bulk", "region", "regions", "stats",
                     "export", "upsert"):
            labels = {"kind": kind}
            self._kind[kind] = (
                registry.counter(
                    "avdb_query_requests_total", "queries served", labels
                ),
                registry.histogram(
                    "avdb_query_seconds", QUERY_SECONDS_EDGES,
                    "request latency by query kind", labels,
                ),
                registry.counter(
                    "avdb_query_rows_total", "result rows returned", labels
                ),
                registry.counter(
                    "avdb_query_rejected_total",
                    "queries rejected at the admission bound (HTTP 429)",
                    labels,
                ),
                registry.counter(
                    "avdb_query_errors_total",
                    "queries that failed (HTTP 4xx grammar / 5xx engine)",
                    labels,
                ),
            )

    # -- per-kind metrics (kind in {point, bulk, region}) -------------------

    def observe(self, kind: str, seconds: float, rows: int = 0) -> None:
        requests, seconds_h, rows_c, _rej, _err = self._kind[kind]
        requests.inc()
        seconds_h.observe(seconds)
        if rows:
            rows_c.inc(rows)
        # brownout signal: every completed request feeds the ladder; the
        # evaluation itself is time-gated inside maybe_step (one lock +
        # compare per request; the maintenance tick steps it too)
        self.governor.note_latency(seconds)
        self.governor.maybe_step()

    def rejected(self, kind: str) -> None:
        self._kind[kind][3].inc()

    def errored(self, kind: str) -> None:
        self._kind[kind][4].inc()

    # -- resilience ---------------------------------------------------------

    def request_deadline(self, header_value: str | None) -> float | None:
        """Absolute monotonic deadline for a request arriving now."""
        return resilience.deadline_at(header_value, self.default_deadline_s)

    def _brownout_event(self, old: int, new: int) -> None:
        """Brownout ladder transitions go to the server's log, with the
        two signals the ladder stepped on — whether or not anything else
        records them, a run that was shed leaves a word of why — and land
        on the flight timeline: the black box's answer to "what was this
        worker shedding when it died"."""
        step = f"level {old}->{new} ({resilience.LEVEL_NAMES[new]})"
        self.log(f"brownout: {step} "
                 f"exceedance={self.governor.exceedance:.4f} "
                 f"depth={self.batcher.depth()}")
        if self.flight is not None:
            self.flight.event("brownout", step)

    def fleet_metrics(self) -> str:
        """The ``?fleet=1`` exposition body: this worker's live registry
        merged with every sibling's published snapshot file (sum for
        counters/histograms, max for gauges) plus the supervisor's
        ``avdb_fleet_*`` series.  Outside a fleet the same surface
        answers from the one process (workers_live 1) — the contract is
        the VIEW, not the process count."""
        from annotatedvdb_tpu.obs.metrics import (
            merge_snapshots,
            render_snapshot,
        )

        snaps = [self.registry.snapshot()]
        info = None
        now = time.time()
        tdir = self.telemetry_dir
        if tdir and os.path.isdir(tdir):
            for fname in sorted(os.listdir(tdir)):
                path = os.path.join(tdir, fname)
                try:
                    if fname == "fleet.json":
                        with open(path) as f:
                            doc = json.load(f)
                        if now - float(doc.get("t", 0)) \
                                <= self.FLEET_SNAPSHOT_TTL_S:
                            # a dead supervisor's last facts must age out
                            # exactly like a dead worker's snapshot — the
                            # gauges exist to SURFACE that death
                            info = doc
                        continue
                    if not (fname.startswith("worker-")
                            and fname.endswith(".json")):
                        continue
                    with open(path) as f:
                        doc = json.load(f)
                    if int(doc.get("index", -1)) == self.worker_index:
                        continue  # self: the live registry is fresher
                    if now - float(doc.get("t", 0)) \
                            > self.FLEET_SNAPSHOT_TTL_S:
                        continue  # a dead worker's stale snapshot
                    snaps.append(doc.get("metrics") or {})
                except (OSError, ValueError, TypeError):
                    continue  # torn publish race: skip, never fail a scrape
        merged = merge_snapshots(snaps)
        fleet = MetricsRegistry()
        if info:
            live = int(info.get("workers_live", 0))
            respawns = int(info.get("respawns_total", 0))
            age = float(info.get("worker_age_seconds", 0.0))
        else:
            live, respawns = 1, 0
            age = now - self.started_t
        fleet.gauge(
            "avdb_fleet_workers_live",
            "serve worker processes alive in the fleet",
        ).set(live)
        fleet.counter(
            "avdb_fleet_respawns_total",
            "worker respawns since the fleet supervisor started",
        ).inc(respawns)
        fleet.gauge(
            "avdb_fleet_worker_age_seconds",
            "age of the oldest live worker process",
        ).set(round(age, 3))
        return fleet.render_prometheus() + render_snapshot(merged)

    def deadline_shed(self, stage: str) -> None:
        self._m_deadline_shed[stage].inc()

    def brownout_shed(self) -> None:
        self._m_brownout_shed.inc()

    def point_cache_hit(self) -> None:
        self._m_point_cache_hits.inc()

    def abandoned(self) -> None:
        self._m_abandoned.inc()

    def cached_point(self, variant_id: str):
        """(hit, record) from the id-level point cache for the CURRENT
        generation — the brownout cache_first rung's read side."""
        return self.point_cache.get(
            self.manager.current().generation, variant_id
        )

    def point_preflight(self, variant_id: str, deadline_t: float | None):
        """The point-read admission decision (decision logic lives here,
        the front end only renders).  Returns one of::

            ("shed", None)        deadline dead at admission (counted)
            ("cached", record)    cache-first answer (record may be None
                                  = cached absence -> 404)
            ("submit", generation)  proceed through the batcher; cache
                                  the result under this generation —
                                  captured BEFORE submit, so a swap
                                  landing mid-flight writes the entry
                                  under the retired generation's key,
                                  which can never be probed again
        """
        if deadline_t is not None and time.monotonic() >= deadline_t:
            self.deadline_shed("admission")
            return "shed", None
        if self.governor.cache_first():
            hit, record = self.cached_point(variant_id)
            if hit:
                self.point_cache_hit()
                return "cached", record
        return "submit", self.manager.current().generation

    def remember_point(self, generation: int, variant_id: str,
                       record) -> None:
        self.point_cache.put(generation, variant_id, record)

    # -- upserts (the live write path) --------------------------------------

    def upsert_execute(self, body: bytes,
                       max_rows: int | None = None, trace=None):
        """The upsert decision+execution (the ``point_preflight``
        convention: logic lives here, the front end only renders).
        Returns ``(status, json_body, rows_in_request)``.

        The 200 is the ACK: it is built only after the accepted rows'
        WAL frame is fsync'd (``Memtable.upsert`` orders WAL-then-
        visibility), so an acknowledged upsert survives SIGKILL at any
        instant."""
        if self.follow_url is not None:
            # a follower is read-only BY ROLE, not by configuration: its
            # overlay memtable exists purely to apply the leader's shipped
            # stream, so a client write is refused with the leader's
            # location rather than silently forking the replica
            return 403, follower_upsert_payload(self), 0
        memtable = self.memtable
        if memtable is None:
            return 403, json.dumps({"error": MSG_UPSERTS_DISABLED}), 0
        if self.disk_guard is not None and self.disk_guard.breached():
            # disk-pressure degradation ladder: WRITES shed first (507,
            # through this one gate); reads, flushes of already-acknowledged rows, and
            # space-reclaiming compaction keep running.  Nothing durable
            # happened, nothing was acknowledged — the client retries
            # once space is freed.
            self._m_upsert_disk_shed.inc()
            return 507, json.dumps({"error": MSG_DISK_RESERVE}), 0
        t0 = time.perf_counter()
        try:
            entries = parse_upsert_body(body)
            parsed = self.upsert_parse_entries(entries)
        except QueryError as err:
            self.errored("upsert")
            return 400, json.dumps({"error": str(err)}), 0
        if max_rows is not None and len(parsed) > max_rows:
            # bounded-debt contract (the bulk-/variants shape): a batch
            # the client bucket could never repay is rejected before any
            # WAL/memtable work runs
            self.rejected("upsert")
            return 429, json.dumps({"error": (
                f"upsert of {len(parsed)} rows exceeds client rate "
                f"budget ({max_rows} rows); split the request"
            )}), len(parsed)
        base = getattr(self.manager, "base", self.manager)
        try:
            accepted, shadowed, _wal_bytes = memtable.upsert(
                base.current().store, parsed, trace=trace
            )
        except (ValueError, KeyError, TypeError) as err:
            self.errored("upsert")
            return 400, json.dumps({"error": str(err)}), len(parsed)
        except Exception as err:
            # WAL append/fsync failure included: nothing became visible,
            # nothing was acknowledged — the client must retry
            self.errored("upsert")
            return 500, json.dumps(
                {"error": f"{type(err).__name__}: {err}"}
            ), len(parsed)
        generation = self.manager.current().generation
        dt = time.perf_counter() - t0
        self._m_upsert_requests.inc()
        if accepted:
            self._m_upsert_rows.inc(accepted)
        if shadowed:
            self._m_upsert_rejected.inc(shadowed)
        self._m_upsert_ack.observe(dt)
        self.observe("upsert", dt, rows=accepted)
        return 200, (
            f'{{"n":{len(parsed)},"accepted":{accepted},'
            f'"shadowed":{shadowed},"generation":{generation}}}'
        ), len(parsed)

    def upsert_parse_entries(self, entries: list[dict]) -> list[dict]:
        """Validated body entries -> the memtable's plain-data rows:
        ids resolve through the SAME grammar every read path uses
        (:func:`~annotatedvdb_tpu.serve.engine.parse_variant_id`), and
        alleles are bounded by the store width (long-allele rows belong
        to the offline loaders, which retain original strings and digest
        PKs)."""
        width = self.manager.current().store.width
        parsed = []
        for e in entries:
            code, pos, ref, alt = parse_variant_id(e["id"])
            if len(ref) > width or len(alt) > width:
                raise QueryError(
                    f"upsert {e['id']!r}: allele length "
                    f"{max(len(ref), len(alt))} exceeds the store width "
                    f"{width}; load long-allele rows through the offline "
                    "loader CLIs"
                )
            parsed.append({
                "code": code, "pos": pos, "ref": ref, "alt": alt,
                "ref_snp": e.get("ref_snp"),
                "ann": e.get("annotations"),
            })
        return parsed

    def maybe_flush_memtable(self, force: bool = False) -> bool:
        """Kick a background memtable flush when a trigger
        (``AVDB_MEMTABLE_BYTES`` / ``AVDB_MEMTABLE_FLUSH_S``) is due.
        Called after upsert completions and from the maintenance paths —
        the flush itself runs on its own thread (it writes segment files
        and fsyncs a manifest: seconds, never on a request thread or the
        event loop) and self-guards against duplicates."""
        m = self.memtable
        if m is None:
            return False
        if not (force or m.should_flush()):
            return False
        base = getattr(self.manager, "base", self.manager)
        threading.Thread(
            target=self._flush_memtable, args=(base,), daemon=True,
            name="memtable-flush",
        ).start()
        return True

    def _flush_memtable(self, base_manager) -> None:
        from annotatedvdb_tpu.utils import retry

        try:
            # ENOSPC/EDQUOT (and classic transient-I/O blips) get a
            # bounded backoff-retry on this flush thread: a transiently
            # full disk degrades — the memtable keeps growing under the
            # 507 write shed while compaction reclaims space — instead of
            # wedging the flush path; a still-full disk after the retries
            # lands in the except below, and the next trigger retries
            # from scratch (acknowledged rows stay in memtable + WAL
            # either way)
            retry.with_backoff(
                lambda: self.memtable.flush(base_manager=base_manager),
                attempts=3, base_delay=0.5,
                retryable=lambda exc: (retry.is_disk_full(exc)
                                       or retry.is_transient_io(exc)),
                log=self.log, what="memtable flush",
            )
        except Exception as err:
            self.log(f"memtable flush failed ({type(err).__name__}: "
                     f"{err}); rows stay in the memtable")

    def ready_state(self) -> tuple[bool, str]:
        """(ready, reason): readiness gates routing, not liveness.  Not
        ready while a snapshot swap is loading (the warming-worker case)
        or the brownout ladder reached shed_bulk.  Health polls step the
        ladder too (time-gated), beside the maintenance tick: a
        shed_bulk worker a router has fully DRAINED completes no
        requests, and the router's own readiness probes let the now-idle
        ladder de-escalate back to ready.  Probes also check the
        memtable flush triggers, as the maintenance tick does."""
        self.governor.maybe_step()
        self.maybe_flush_memtable()
        if getattr(self.manager, "swapping", False):
            return False, "snapshot swap in progress"
        if self.repl is not None and self.repl.lag_exceeded():
            # the bounded-staleness contract: a follower past its
            # declared lag bound (AVDB_REPL_MAX_LAG_S) drains out of the
            # router rotation rather than serving reads staler than it
            # promised; it re-enters the instant a tail cycle catches up
            return False, (
                f"replication lag {self.repl.lag_s():.1f}s exceeds the "
                f"declared staleness bound ({self.repl.max_lag_s:g}s)"
            )
        if self.governor.shed_bulk():
            return False, f"brownout level {self.governor.level} " \
                          f"({self.governor.level_name})"
        return True, "ok"

    # -- admission ----------------------------------------------------------

    def admit(self) -> bool:
        """Reserve one bulk/region execution slot; False = reject (429)."""
        with self._lock:
            if self._inflight >= self.max_inflight:
                return False
            self._inflight += 1
            depth = self._inflight
        self._m_inflight.set(depth)
        return True

    def release(self) -> None:
        with self._lock:
            self._inflight -= 1
            depth = self._inflight
        self._m_inflight.set(depth)

    def refresh_snapshot(self) -> None:
        """Pick up a loader commit if one landed — coalesced: at most one
        manifest ``stat`` per ``AVDB_SERVE_SNAPSHOT_TTL_MS`` window across
        every request thread (``SnapshotManager.maybe_refresh``).  A
        refresh failure keeps serving the pinned generation (and must
        never fail the request)."""
        try:
            if self.manager.maybe_refresh():
                self._m_swaps.inc()
        except Exception as err:
            self.log(f"snapshot refresh errored: {err}")
