"""Request-scoped tracing + fleet telemetry through the serving stack.

The trace-id echo contract (W3C ``traceparent`` / ``X-Request-Id`` /
minted), stage attribution through the batcher and the engine, trace-id propagation across a
paged cursor walk and a batched ``/regions`` panel, the WAL-fsync stage
of an upsert ack, the chaos-gated ``/debug/trace`` dump, the
``/metrics?fleet=1`` fleet view, and the lifecycle events (brownout,
breaker) the flight recorder keeps.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.obs.flight import FlightRecorder, decode_ring
from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.serve import MemtableSnapshots, SnapshotManager
from annotatedvdb_tpu.serve.http import resolve_trace_id
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.memtable import Memtable
from annotatedvdb_tpu.store.wal import WriteAheadLog
from annotatedvdb_tpu.types import encode_allele_array
from conftest import ring_records, start_server, stop_server
from test_serve import _build_store, _vid

WIDTH = 8


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    store_dir = str(tmp_path_factory.mktemp("obs_store"))
    truth = _build_store(store_dir)
    return store_dir, truth


@pytest.fixture(scope="module")
def server(store):
    """One server over the module's store: ``(port, ctx)``."""
    store_dir, _truth = store
    srv = start_server(store_dir=store_dir)
    try:
        yield srv.server_address[1], srv.ctx
    finally:
        stop_server(srv)


def _get(port, path, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", headers=headers or {}
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode(), dict(err.headers)


def _post(port, path, payload, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(), method="POST",
        headers=headers or {},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode(), dict(err.headers)


def _records_for(ctx, tid, n=1):
    return ring_records(ctx, tid, n)


# ---------------------------------------------------------------------------
# trace-id grammar (the ONE shared resolver)


def test_resolve_trace_id_grammar():
    tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    assert resolve_trace_id(tp, None) == "ab" * 16
    assert resolve_trace_id(tp, "client-id") == "ab" * 16  # W3C wins
    assert resolve_trace_id(None, "req-42_x") == "req-42_x"
    # sanitization: header-unsafe characters strip, length caps at 64
    assert resolve_trace_id(None, "a b\r\nc") == "abc"
    assert len(resolve_trace_id(None, "x" * 200)) == 64
    # malformed traceparent falls through; all-zero trace id is invalid
    assert resolve_trace_id("garbage", "fallback") == "fallback"
    assert resolve_trace_id("00-" + "0" * 32 + "-" + "cd" * 8 + "-01",
                            "fb") == "fb"
    # nothing usable: a fresh 128-bit id mints, unique per call
    a, b = resolve_trace_id(None, None), resolve_trace_id(None, None)
    assert len(a) == 32 and a != b
    int(a, 16)  # hex by construction


# ---------------------------------------------------------------------------
# header echo


def test_trace_header_echoes_the_resolved_id(store, server):
    """Oracle: ``resolve_trace_id`` called directly on the same headers —
    the echoed ``X-Request-Id`` is exactly what the one resolver gives."""
    port, _ctx = server
    _store_dir, truth = store
    vid = _vid(truth[0])
    for hdrs, want in (
        ({"X-Request-Id": "abc-123"}, "abc-123"),
        ({"traceparent": "00-" + "ef" * 16 + "-" + "12" * 8 + "-00"},
         "ef" * 16),
    ):
        status, _b, got = _get(port, f"/variant/{vid}", hdrs)
        assert status == 200
        assert got.get("X-Request-Id") == want == resolve_trace_id(
            hdrs.get("traceparent"), hdrs.get("X-Request-Id"))
    # minted when absent: 32 hex chars on every route, errors included
    for path in (f"/variant/{vid}", "/variant/zzz", "/healthz",
                 "/nosuchroute"):
        _s, _b, got = _get(port, path)
        assert len(got.get("X-Request-Id", "")) == 32, path


def test_stage_breakdown_recorded_per_point_request(store, server):
    port, ctx = server
    _store_dir, truth = store
    vid = _vid(truth[1])
    tid = f"stages-{port}"
    status, _b, _h = _get(port, f"/variant/{vid}", {"X-Request-Id": tid})
    assert status == 200
    recs = _records_for(ctx, tid)
    assert len(recs) == 1 and recs[0][1] == "point"
    stages = dict(recs[0][5])
    # the queue/device split comes from the batcher drain; the rest
    # from the front end — whose read covers a point read's admission
    # (the preflight: microseconds on the loop)
    assert set(stages) == {"read", "queue", "device", "wake", "render",
                           "reply"}
    assert all(s >= 0 for s in stages.values())


# ---------------------------------------------------------------------------
# propagation: paged cursor walk + batched /regions panel


def test_cursor_walk_pages_share_the_trace_id(store, server):
    port, ctx = server
    tid = "walk-1"
    status, body, hdrs = _get(
        port, "/region/8:1-3000000?limit=25&cursor=",
        {"X-Request-Id": tid},
    )
    assert status == 200
    assert hdrs.get("X-Request-Id") == tid
    pages = 1
    nxt = json.loads(body).get("next")
    while nxt and pages < 4:
        status, body, hdrs = _get(
            port, f"/region/8:1-3000000?limit=25&cursor={nxt}",
            {"X-Request-Id": tid},
        )
        assert status == 200 and hdrs.get("X-Request-Id") == tid
        nxt = json.loads(body).get("next")
        pages += 1
    assert pages >= 2, "walk never continued: the fixture store shrank?"
    recs = _records_for(ctx, tid, pages)
    assert len(recs) == pages
    for r in recs:
        assert r[1] == "region"
        assert any(name.startswith("region.chr8") and parent == "device"
                   for name, _s, _e, parent in r[6]), r[6]


def test_regions_panel_intervals_share_the_trace_id(store, server):
    port, ctx = server
    body = {"regions": ["8:400-600", "8:119000-121000", "1:400-600"],
            "limit": 10}
    tid = f"panel-{port}"
    status, _b, hdrs = _post(port, "/regions", body, {"X-Request-Id": tid})
    assert status == 200
    assert hdrs.get("X-Request-Id") == tid
    recs = _records_for(ctx, tid)
    assert len(recs) == 1 and recs[0][1] == "regions"
    span_names = {name for name, _s, _e, parent in recs[0][6]
                  if parent == "device"}
    # every touched chromosome group's span hangs off the PANEL's id
    assert {"regions.chr8", "regions.chr1"} <= span_names
    stages = dict(recs[0][5])
    assert {"admission", "device", "render"} <= set(stages)


# ---------------------------------------------------------------------------
# /stats region_panels: the routes a panel's rows took, added once a panel

#: two of chromosome 8's segments, the long-allele row's window (positions
#: 500, 501 and 600: the row at 600 keeps its host strings), two other
#: chromosomes and one that holds nothing: one row on the scalar route
_COUNTED_PANEL = ["8:100000-3000000", "1:1-3000000", "8:490-700",
                  "X:1-100000", "11:1-5000"]


class _CountingTally(dict):
    """``region_panels`` that counts how often each key was added to."""

    def __init__(self, tally):
        super().__init__(tally)
        self.additions = dict.fromkeys(tally, 0)

    def __setitem__(self, key, value):
        self.additions[key] += 1
        super().__setitem__(key, value)


@pytest.mark.parametrize("body_form,panels", [
    ("streamed", 1), ("streamed", 3), ("buffered", 1), ("buffered", 3)])
def test_region_panel_rows_are_tallied_by_route_once_a_panel(
        store, body_form, panels):
    store_dir, _truth = store
    srv = start_server(
        store_dir=store_dir, region_cache_size=0,
        stream_threshold=4 if body_form == "streamed" else 1 << 30)
    port, engine = srv.server_address[1], srv.ctx.engine
    tally = engine.region_panels = _CountingTally(engine.region_panels)
    try:
        for _ in range(panels):
            status, body, hdrs = _post(port, "/regions",
                                       {"regions": _COUNTED_PANEL})
            assert status == 200
            assert (hdrs.get("Transfer-Encoding") == "chunked") \
                == (body_form == "streamed")
        doc = json.loads(body)
        rows = sum(e["returned"] for e in doc["results"])
        long_rows = [v for e in doc["results"] for v in e["variants"]
                     if len(v["ref"]) > WIDTH]
        assert len(long_rows) == 1 and rows > 100
        stats = json.loads(_get(port, "/stats")[1])["region_panels"]
    finally:
        stop_server(srv)
    assert stats["panels"] == panels
    assert stats["rows_rendered"] == panels * rows
    assert stats["rows_scalar"] == panels  # the one retained-string row
    assert stats["rows_batched"] == panels * (rows - 1)
    assert stats["streamed"] == (panels if body_form == "streamed" else 0)
    # never per row, per interval or per block: once a panel each
    assert tally.additions["rows_batched"] == panels
    assert tally.additions["rows_scalar"] == panels
    assert tally.additions["rows_rendered"] == panels


def test_a_panel_of_one_retained_string_row_is_one_scalar_row(store):
    """A body of one row in all has nothing to amortise: the scalar
    renderer, and the tally says so."""
    from annotatedvdb_tpu.serve import QueryEngine

    store_dir, _truth = store
    engine = QueryEngine(SnapshotManager(store_dir), region_cache_size=0)
    result = engine.regions_serve(["8:600-600", "1:1-10"])
    doc = json.loads(result.assemble())
    assert [e["returned"] for e in doc["results"]] == [1, 0]
    assert doc["results"][0]["variants"][0]["ref"] == "A" * 20
    engine.regions_rendered(result.clock, streamed=False)
    tally = engine.region_panels
    assert (tally["rows_rendered"], tally["rows_batched"],
            tally["rows_scalar"]) == (1, 0, 1)


# ---------------------------------------------------------------------------
# upsert: the WAL-fsync stage is attributed to the ack


def test_upsert_ack_attributes_wal_fsync(tmp_path):
    store_dir = str(tmp_path / "wstore")
    store = VariantStore(width=WIDTH)
    ref, ref_len = encode_allele_array(["A"], WIDTH)
    alt, alt_len = encode_allele_array(["C"], WIDTH)
    store.shard(3).append(
        {"pos": np.asarray([10], np.int32),
         "h": identity_hashes(WIDTH, ref, alt, ref_len, alt_len),
         "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
    )
    store.save(store_dir)
    registry = MetricsRegistry()
    mgr = SnapshotManager(store_dir, log=lambda m: None)
    mem = Memtable(
        width=WIDTH, store_dir=store_dir,
        wal=WriteAheadLog(store_dir, "serve-obs", log=lambda m: None),
        registry=registry, log=lambda m: None,
    )
    httpd = start_server(manager=MemtableSnapshots(mgr, mem),
                         memtable=mem, registry=registry)
    try:
        port = httpd.server_address[1]
        status, body, hdrs = _post(
            port, "/variants/upsert",
            {"variants": [{"id": "3:77:A:G"}]},
            {"X-Request-Id": "ack-1"},
        )
        assert status == 200, body
        assert hdrs.get("X-Request-Id") == "ack-1"
        recs = _records_for(httpd.ctx, "ack-1")
        assert len(recs) == 1 and recs[0][1] == "upsert"
        stages = dict(recs[0][5])
        assert "wal_fsync" in stages, stages
        assert 0 <= stages["wal_fsync"] <= recs[0][4]
        # histogram series carries it too
        text = registry.render_prometheus()
        assert 'avdb_stage_seconds_count{stage="wal_fsync"} 1' in text
    finally:
        stop_server(httpd)
        mem.wal.close()


# ---------------------------------------------------------------------------
# /debug/trace (chaos-gated)


def test_debug_trace_is_gated_off_like_chaos(store, server):
    # the module fixture server runs WITHOUT AVDB_SERVE_CHAOS: the route
    # must 404 with the body any unknown route gets
    port, _ctx = server
    status, body, _h = _get(port, "/debug/trace")
    assert status == 404
    assert json.loads(body) == {"error": "no such route: /debug/trace"}
    assert _get(port, "/_chaos")[0] == 404


def test_debug_trace_dumps_chrome_events_when_enabled(store, monkeypatch):
    monkeypatch.setenv("AVDB_SERVE_CHAOS", "1")
    store_dir, truth = store
    vid = _vid(truth[0])
    httpd = start_server(store_dir=store_dir)
    try:
        port = httpd.server_address[1]
        _get(port, f"/variant/{vid}", {"X-Request-Id": "dump-me"})
        status, body, _h = _get(port, "/debug/trace")
        assert status == 200
        doc = json.loads(body)
        assert doc["displayTimeUnit"] == "ms"
        reqs = [e for e in doc["traceEvents"]
                if e.get("ph") == "X" and e.get("cat") == "request"]
        assert any(e["args"]["trace_id"] == "dump-me" for e in reqs)
        tracks = [e for e in doc["traceEvents"]
                  if e.get("name") == "thread_name"]
        assert {t["args"]["name"] for t in tracks} >= {
            "requests", "background"}
    finally:
        stop_server(httpd)


# ---------------------------------------------------------------------------
# fleet telemetry plane (/metrics?fleet=1)


def test_plain_metrics_unchanged_and_fleet_view_single_process(store,
                                                               server):
    port, _ctx = server
    status, body, _h = _get(port, "/metrics")
    assert status == 200
    assert "avdb_fleet_workers_live" not in body  # plain scrape
    status, body, _h = _get(port, "/metrics?fleet=1")
    assert status == 200
    assert "avdb_fleet_workers_live 1" in body
    assert "avdb_fleet_respawns_total 0" in body
    assert "avdb_fleet_worker_age_seconds" in body
    assert "avdb_query_requests_total" in body


def test_fleet_view_sums_published_worker_snapshots(store, tmp_path):
    store_dir, truth = store
    tdir = str(tmp_path / "tm")
    import os

    os.makedirs(tdir)

    def publish(index, n, t=None):
        reg = MetricsRegistry()
        reg.counter("avdb_query_requests_total",
                    labels={"kind": "point"}).inc(n)
        reg.gauge("avdb_serve_queue_depth").set(n)
        with open(os.path.join(tdir, f"worker-{index}.json"), "w") as f:
            json.dump({"index": index,
                       "t": time.time() if t is None else t,
                       "metrics": reg.snapshot()}, f)

    publish(1, 10)
    publish(2, 7)
    publish(3, 1000, t=time.time() - 3600)  # stale: a dead worker's file
    with open(os.path.join(tdir, "fleet.json"), "w") as f:
        json.dump({"t": time.time(), "workers_live": 3,
                   "respawns_total": 4, "worker_age_seconds": 12.5}, f)
    # a DEAD supervisor's fleet.json must age out exactly like a dead
    # worker's snapshot (checked below via the fresh file; see the
    # stale-supervisor test for the other side)
    httpd = start_server(store_dir=store_dir, telemetry_dir=tdir)
    try:
        port = httpd.server_address[1]
        vid = _vid(truth[0])
        assert _get(port, f"/variant/{vid}")[0] == 200  # own: 1 point
        status, body, _h = _get(port, "/metrics?fleet=1")
        assert status == 200
        assert "avdb_fleet_workers_live 3" in body
        assert "avdb_fleet_respawns_total 4" in body
        assert "avdb_fleet_worker_age_seconds 12.5" in body
        # own live registry (1 request) + workers 1 and 2; the stale
        # worker-3 snapshot drops out of the view
        assert 'avdb_query_requests_total{kind="point"} 18' in body
        # gauges take the fleet max
        assert "avdb_serve_queue_depth 10" in body
    finally:
        stop_server(httpd)


def test_fleet_view_ages_out_a_dead_supervisors_facts(store, tmp_path):
    """fleet.json past the snapshot TTL is a dead supervisor's leavings:
    the view falls back to the single-process defaults instead of
    serving frozen workers_live/age gauges forever — the gauges exist to
    SURFACE that death."""
    store_dir, _truth = store
    tdir = str(tmp_path / "tm3")
    import os

    os.makedirs(tdir)
    with open(os.path.join(tdir, "fleet.json"), "w") as f:
        json.dump({"t": time.time() - 3600, "workers_live": 4,
                   "respawns_total": 9, "worker_age_seconds": 77.0}, f)
    httpd = start_server(store_dir=store_dir, telemetry_dir=tdir)
    try:
        status, body, _h = _get(httpd.server_address[1],
                                "/metrics?fleet=1")
        assert status == 200
        assert "avdb_fleet_workers_live 1" in body  # NOT the stale 4
        assert "avdb_fleet_respawns_total 0" in body
    finally:
        stop_server(httpd)


def test_fleet_view_ignores_torn_snapshot_files(store, tmp_path):
    store_dir, _truth = store
    tdir = str(tmp_path / "tm2")
    import os

    os.makedirs(tdir)
    with open(os.path.join(tdir, "worker-1.json"), "w") as f:
        f.write('{"index": 1, "t":')  # torn mid-publish
    httpd = start_server(store_dir=store_dir, telemetry_dir=tdir)
    try:
        status, body, _h = _get(httpd.server_address[1],
                                "/metrics?fleet=1")
        assert status == 200  # the scrape never fails on a torn sibling
        assert "avdb_fleet_workers_live 1" in body
    finally:
        stop_server(httpd)


# ---------------------------------------------------------------------------
# lifecycle events -> flight recorder


@pytest.mark.parametrize("with_flight", [True, False])
def test_the_ladder_says_when_it_steps(store, tmp_path, with_flight):
    """A step of the brownout ladder is one line of the server's log,
    flight directory or none, and ``/stats`` ``brownout`` keeps how far up
    it went: a run that was shed leaves a word of why."""
    store_dir, _truth = store
    lines: list = []
    flight = FlightRecorder(str(tmp_path / "w0.ring"), slots=32) \
        if with_flight else None
    httpd = start_server(store_dir=store_dir, flight=flight,
                         log=lines.append)
    try:
        ctx = httpd.ctx
        port = httpd.server_address[1]
        assert json.loads(_get(port, "/stats")[1])["brownout"] == {
            "level": 0, "name": "normal", "max_level": 0, "steps": 0,
            "exceedance": 0.0}
        ctx.governor.force_level(2)
        ctx.governor.force_level(1)
        said = [ln for ln in lines if ln.startswith("brownout: ")]
        assert said == [
            "brownout: level 0->2 (cache_first) exceedance=0.0000 depth=0",
            "brownout: level 2->1 (limit) exceedance=0.0000 depth=0"]
        assert json.loads(_get(port, "/stats")[1])["brownout"] == {
            "level": 1, "name": "limit", "max_level": 2, "steps": 2,
            "exceedance": 0.0}
        # the ladder's own evaluation steps through the same hook, with
        # the signal it stepped on
        for _ in range(200):
            ctx.governor.note_latency(10.0)
        ctx.governor._next_eval = 0.0
        assert ctx.governor.maybe_step() == 2
        assert lines[-1].startswith(
            "brownout: level 1->2 (cache_first) exceedance=0.9")
    finally:
        stop_server(httpd)
        if flight is not None:
            flight.close()


def test_brownout_and_breaker_transitions_land_on_the_flight(store,
                                                             tmp_path):
    store_dir, _truth = store
    ring = str(tmp_path / "w0.ring")
    flight = FlightRecorder(ring, slots=32)
    httpd = start_server(store_dir=store_dir, flight=flight)
    try:
        ctx = httpd.ctx
        ctx.governor.force_level(3)
        ctx.governor.force_level(0)
        assert ctx.engine.breaker is not None
        for _ in range(ctx.engine.breaker.failure_threshold):
            ctx.engine.breaker.record_failure(8, RuntimeError("dev down"))
        events = [e for e in decode_ring(ring)["events"]
                  if e["type"] == "event"]
        names = [(e["name"], e["detail"]) for e in events]
        assert ("brownout", "level 0->3 (shed_bulk)") in names
        assert ("brownout", "level 3->0 (normal)") in names
        assert any(n == "breaker" and "group 8 tripped open" in d
                   for n, d in names)
    finally:
        stop_server(httpd)
        flight.close()


def test_request_summaries_land_on_the_flight(store, tmp_path):
    store_dir, truth = store
    ring = str(tmp_path / "wr.ring")
    flight = FlightRecorder(ring, slots=32)
    httpd = start_server(store_dir=store_dir, flight=flight)
    try:
        port = httpd.server_address[1]
        vid = _vid(truth[0])
        assert _get(port, f"/variant/{vid}",
                    {"X-Request-Id": "boxed"})[0] == 200
        assert _records_for(httpd.ctx, "boxed")  # sealed after its write
        flight.flush()  # the serving flush cadence, forced for the test
        reqs = [e for e in decode_ring(ring)["events"]
                if e["type"] == "request"]
        assert any(e["trace"] == "boxed" and e["kind"] == "point"
                   and e["status"] == 200 and "stages" in e
                   for e in reqs), reqs
    finally:
        stop_server(httpd)
        flight.close()


# ---------------------------------------------------------------------------
# doctor flight / doctor trace (the black-box CLIs)


def _seed_blackbox(store_dir):
    from annotatedvdb_tpu.obs import flight as flight_mod

    ring = flight_mod.ring_path(store_dir, 0)
    fr = FlightRecorder(ring, slots=16, event_slots=16)
    fr.event("brownout", "level 0->1 (limit)")
    fr.request("abc", "point", 200, 0.0031,
               [("queue", 0.001), ("device", 0.002)])
    fr.event("breaker", "group 8 tripped open (OSError)")
    fr.close()
    return flight_mod.harvest(ring, store_dir, 0, "died rc=-9",
                              log=lambda m: None)


def test_doctor_flight_renders_harvested_blackbox(tmp_path, capsys):
    from annotatedvdb_tpu.cli import doctor

    store_dir = str(tmp_path / "dstore")
    import os

    os.makedirs(store_dir)
    out = _seed_blackbox(store_dir)
    assert out is not None
    rc = doctor.main(["flight", "--storeDir", store_dir])
    assert rc == 0
    err = capsys.readouterr().err
    assert "died rc=-9" in err
    assert "brownout" in err and "level 0->1" in err
    assert "trace=abc" in err and "device=2.0ms" in err
    # --json emits the structured form
    rc = doctor.main(["flight", "--storeDir", store_dir, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["harvested"][0]["meta"]["worker"] == 0
    kinds = [e["type"] for e in doc["harvested"][0]["events"]]
    assert kinds == ["event", "request", "event"]


def test_doctor_flight_decodes_live_rings_without_harvest(tmp_path,
                                                         capsys):
    """A single-process SIGKILL leaves only the ring (no supervisor to
    harvest): doctor flight decodes it directly."""
    from annotatedvdb_tpu.cli import doctor
    from annotatedvdb_tpu.obs import flight as flight_mod

    store_dir = str(tmp_path / "lstore")
    import os

    os.makedirs(store_dir)
    fr = FlightRecorder(flight_mod.ring_path(store_dir, 0), slots=8)
    fr.request("xyz", "region", 200, 0.5, [])
    fr.flush()
    # no close(): SIGKILL semantics
    rc = doctor.main(["flight", "--storeDir", store_dir])
    assert rc == 0
    err = capsys.readouterr().err
    assert "live ring" in err and "trace=xyz" in err
    fr.close()


def test_doctor_flight_exit_2_without_flight_data(tmp_path, capsys):
    from annotatedvdb_tpu.cli import doctor

    store_dir = str(tmp_path / "estore")
    import os

    os.makedirs(store_dir)
    assert doctor.main(["flight", "--storeDir", store_dir]) == 2
    assert "no flight data" in capsys.readouterr().err
    assert doctor.main(["flight", "--storeDir",
                        str(tmp_path / "missing")]) == 2


def test_doctor_trace_merges_ledger_and_flight(tmp_path, capsys):
    from annotatedvdb_tpu.cli import doctor
    from annotatedvdb_tpu.store.ledger import AlgorithmLedger

    store_dir = str(tmp_path / "tstore")
    import os

    os.makedirs(store_dir)
    ledger = AlgorithmLedger(os.path.join(store_dir, "ledger.jsonl"),
                             log=lambda m: None)
    ledger.compact({"labels": ["8"], "files_before": 4, "files_after": 1,
                    "rows": 100, "seconds": 1.5})
    ledger.flush({"labels": ["8"], "rows": 12, "seconds": 0.2})
    _seed_blackbox(store_dir)
    out_path = str(tmp_path / "trace.json")
    rc = doctor.main(["trace", "--storeDir", store_dir,
                      "--out", out_path])
    assert rc == 0
    doc = json.load(open(out_path))
    assert doc["displayTimeUnit"] == "ms"
    names = [e.get("name") for e in doc["traceEvents"]]
    # background track from the ledger + flight request/lifecycle marks
    assert "ledger.compact" in names and "ledger.flush" in names
    assert "point" in names and "breaker" in names
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert all(e["ts"] >= 0 for e in spans)  # rebased to the earliest
    compact = next(e for e in spans if e["name"] == "ledger.compact")
    assert compact["dur"] == pytest.approx(1.5e6)
    # empty store: nothing to render is exit 2
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    assert doctor.main(["trace", "--storeDir", empty]) == 2
