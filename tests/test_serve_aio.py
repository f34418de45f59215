"""The asyncio serving front end: byte-parity against the engine and
the shared payload builders called directly (point/bulk/region, hits and
errors), weighted per-client fairness under a hog, chunked region
streaming, continuation paging, the coalesced snapshot TTL, and the
loop batcher it rides on."""

from __future__ import annotations

import http.client
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from annotatedvdb_tpu.serve import QueryEngine, QueryError, SnapshotManager
from annotatedvdb_tpu.serve import snapshot as snapshot_mod
from annotatedvdb_tpu.serve.http import (
    BULK_BODY_ERROR,
    healthz_payload,
    parse_region_params,
    stats_payload,
)
from conftest import bulk_envelope
from test_serve import _build_store, _commit_more_rows, _vid


# ---------------------------------------------------------------------------
# fixtures: one store, one server, and the engine as the oracle


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    store_dir = str(tmp_path_factory.mktemp("aio_store"))
    truth = _build_store(store_dir)
    return store_dir, truth


@pytest.fixture(scope="module")
def aio_server(store):
    from annotatedvdb_tpu.serve.aio import build_aio_server

    store_dir, _truth = store
    server = build_aio_server(store_dir=store_dir, port=0)
    server.start_background()
    try:
        yield server
    finally:
        server.shutdown()
        server.ctx.batcher.close()


@pytest.fixture(scope="module")
def oracle(store):
    """A ``QueryEngine`` of the test's own over the same store: what the
    server's bytes are compared with (``test_serve.py`` pins the engine
    itself against the brute-force scan)."""
    store_dir, _truth = store
    return QueryEngine(SnapshotManager(store_dir), region_cache_size=0)


def _error(message: str) -> str:
    return json.dumps({"error": message})


def _get(port: int, path: str, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", headers=headers or {}
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode(), dict(err.headers)


def _post(port: int, path: str, payload: bytes):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=payload, method="POST"
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


# ---------------------------------------------------------------------------
# byte parity vs the engine called directly


def test_point_parity_hits_misses_errors(store, aio_server, oracle):
    """Oracle: ``engine.lookup`` on the test's own engine; a miss is the
    404 body, a grammar error the 400 body with the ``QueryError``."""
    _dir, truth = store
    port = aio_server.server_address[1]
    for vid in [_vid(r) for r in truth[::5]]:
        assert _get(port, f"/variant/{vid}")[:2] \
            == (200, oracle.lookup(vid)), vid
    for vid in ("8:499:A:G",       # miss
                "2:500:A:G"):      # unloaded chromosome
        assert oracle.lookup(vid) is None
        assert _get(port, f"/variant/{vid}")[:2] \
            == (404, _error(f"variant {vid!r} not in store")), vid
    with pytest.raises(QueryError) as grammar:
        oracle.lookup("garbage")
    assert _get(port, "/variant/garbage")[:2] \
        == (400, _error(str(grammar.value)))


def test_bulk_parity_including_bad_bodies(store, aio_server, oracle):
    """Oracle: ``engine.lookup_many`` in the bulk envelope; every
    malformed body is 400 with the one grammar message."""
    _dir, truth = store
    port = aio_server.server_address[1]
    ids = [_vid(r) for r in truth[:40]] + ["8:499:A:G"]
    payload = json.dumps({"ids": ids}).encode()
    assert _post(port, "/variants", payload) \
        == (200, bulk_envelope(oracle.lookup_many(ids)))
    for bad in (b"[1,2]", b'{"ids": [1]}', b'{"ids": "x"}', b"{nope"):
        assert _post(port, "/variants", bad) \
            == (400, _error(BULK_BODY_ERROR)), bad


def test_region_parity_with_filters(store, aio_server, oracle):
    """Oracle: the shared query grammar (``parse_region_params``) and
    ``engine.region`` called directly; a refusal is 400 with the
    ``QueryError`` either raises."""
    port = aio_server.server_address[1]
    statuses = []
    for path in (
        "/region/8:1-10000",
        "/region/8:1-10000?minCadd=5&limit=4",
        "/region/8:1-3000000?maxConseqRank=10",
        "/region/8:1-10000?limit=0",          # count-only
        "/region/11:1-5000",                   # unloaded chromosome
        "/region/8:9-3",                       # bad range -> 400
        "/region/8:1-10000?limit=zebra",       # bad param -> 400
    ):
        spec, _, query = path[len("/region/"):].partition("?")
        try:
            min_cadd, max_rank, limit, cursor = parse_region_params(query)
            want = 200, oracle.region(
                spec, min_cadd=min_cadd, max_conseq_rank=max_rank,
                limit=limit, cursor=cursor)
        except QueryError as err:
            want = 400, _error(str(err))
        assert _get(port, path)[:2] == want, path
        statuses.append(want[0])
    assert statuses == [200, 200, 200, 200, 200, 400, 400]


def test_aio_routes_and_metrics(aio_server):
    port = aio_server.server_address[1]
    status, body, _ = _get(port, "/healthz")
    assert status == 200 and json.loads(body)["status"] == "ok"
    status, body, _ = _get(port, "/nope")
    assert status == 404
    status, body, _ = _get(port, "/metrics")
    assert status == 200
    for metric in ("avdb_query_requests_total", "avdb_query_seconds",
                   "avdb_serve_batches_total"):
        assert metric in body, metric
    status, body, _ = _get(port, "/stats")
    batcher = json.loads(body)["batcher"]
    assert status == 200 and batcher["queries"] >= 1
    # what the loop batcher counts, and no field that can only read 0
    assert set(batcher) == {"batches", "queries", "batch_fill", "queue"}
    assert batcher["queue"] == {"items": batcher["queries"],
                                "max_depth": batcher["queue"]["max_depth"]}


def test_aio_429_at_queue_bound(store):
    from annotatedvdb_tpu.serve.aio import build_aio_server

    store_dir, truth = store
    server = build_aio_server(store_dir=store_dir, port=0, max_queue=0)
    server.start_background()
    try:
        port = server.server_address[1]
        status, _body, headers = _get(port, f"/variant/{_vid(truth[0])}")
        assert status == 429
        assert headers.get("Retry-After") == "1"
    finally:
        server.shutdown()
        server.ctx.batcher.close()


# ---------------------------------------------------------------------------
# pipelining: many requests in flight on ONE connection, answers in order


def _pipeline_point_gets(port: int, vids: list) -> list:
    """Send every GET on one socket up front; return the bodies in
    arrival order."""
    import socket

    req = b"".join(
        f"GET /variant/{v} HTTP/1.1\r\nHost: t\r\n\r\n".encode()
        for v in vids
    )
    with socket.create_connection(("127.0.0.1", port), timeout=30) as sock:
        sock.sendall(req)
        buf = b""
        bodies = []
        while len(bodies) < len(vids):
            chunk = sock.recv(1 << 16)
            assert chunk, "server closed mid-pipeline"
            buf += chunk
            while True:
                he = buf.find(b"\r\n\r\n")
                if he < 0:
                    break
                cl = buf.find(b"Content-Length: ")
                blen = int(buf[cl + 16:he])
                if len(buf) < he + 4 + blen:
                    break
                bodies.append(buf[he + 4:he + 4 + blen].decode())
                buf = buf[he + 4 + blen:]
    return bodies


def test_pipelined_connection_answers_in_order(store, aio_server):
    _dir, truth = store
    port = aio_server.server_address[1]
    vids = [_vid(r) for r in truth[:30]]
    bodies = _pipeline_point_gets(port, vids)
    for vid, body in zip(vids, bodies):
        rec = json.loads(body)
        assert rec["metaseq_id"].split(":")[1] == vid.split(":")[1], vid


def test_writer_flushes_mid_batch_above_high_water(store, aio_server,
                                                   monkeypatch):
    """The coalescing writer flushes once the buffer crosses
    _WRITE_HIGH_WATER instead of accumulating the whole pipelined batch
    (batch-count x response-size RSS); bodies must stay complete and in
    request order across the forced mid-batch flushes."""
    from annotatedvdb_tpu.serve import aio as aio_mod

    monkeypatch.setattr(aio_mod, "_WRITE_HIGH_WATER", 8)
    _dir, truth = store
    port = aio_server.server_address[1]
    vids = [_vid(r) for r in truth[:20]]
    bodies = _pipeline_point_gets(port, vids)
    for vid, body in zip(vids, bodies):
        rec = json.loads(body)
        assert rec["metaseq_id"].split(":")[1] == vid.split(":")[1], vid


# ---------------------------------------------------------------------------
# weighted per-client fairness


def test_hog_cannot_starve_polite_client(store):
    """A hog blasting unpaced traffic gets throttled to its bucket; a
    polite client under its share sees zero rejections and bounded
    latency — the weighted-share contract of the ISSUE."""
    from annotatedvdb_tpu.serve.aio import build_aio_server

    store_dir, truth = store
    server = build_aio_server(
        store_dir=store_dir, port=0, client_rate=5.0,
    )
    server.start_background()
    try:
        port = server.server_address[1]
        vid = _vid(truth[0])
        results = {}

        def hog():
            # weight 1 -> 5 req/s share; blasts unpaced
            ok = rejected = 0
            lat = []
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                t0 = time.perf_counter()
                status, _b, _h = _get(
                    port, f"/variant/{vid}",
                    headers={"X-Client-Id": "hog"},
                )
                lat.append(time.perf_counter() - t0)
                if status == 200:
                    ok += 1
                elif status == 429:
                    rejected += 1
            results["hog"] = (ok, rejected, lat)

        def polite():
            # weight 4 -> 20 req/s share; paces at ~8 req/s, well under
            ok = rejected = 0
            lat = []
            for _ in range(16):
                t0 = time.perf_counter()
                status, _b, _h = _get(
                    port, f"/variant/{vid}",
                    headers={"X-Client-Id": "polite",
                             "X-Client-Weight": "4"},
                )
                lat.append(time.perf_counter() - t0)
                if status == 200:
                    ok += 1
                elif status == 429:
                    rejected += 1
                time.sleep(0.12)
            results["polite"] = (ok, rejected, lat)

        threads = [threading.Thread(target=hog),
                   threading.Thread(target=polite)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        hog_ok, hog_rej, hog_lat = results["hog"]
        pol_ok, pol_rej, pol_lat = results["polite"]
        # the hog was actually throttled...
        assert hog_rej > 0
        # ...to roughly its bucket (rate*duration + burst, with slack)
        assert hog_ok <= 5 * 2.0 + 4 + 20
        # the polite client never starved: no rejects, every call answered
        assert pol_rej == 0 and pol_ok == 16
        # p99 ratio bound: the polite client's tail latency stays within
        # an order of magnitude of the hog's (it is NOT queued behind it)
        pol_lat.sort()
        hog_lat.sort()
        pol_p99 = pol_lat[int(0.99 * (len(pol_lat) - 1))]
        hog_p99 = hog_lat[int(0.99 * (len(hog_lat) - 1))]
        assert pol_p99 <= max(hog_p99 * 10, 0.5)
    finally:
        server.shutdown()
        server.ctx.batcher.close()


def test_weighted_client_gets_larger_share(store):
    from annotatedvdb_tpu.serve.aio import ClientGovernor

    governor = ClientGovernor(10.0)
    heavy = sum(
        1 for _ in range(200) if governor.admit("heavy", 4) == 0.0
    )
    light = sum(
        1 for _ in range(200) if governor.admit("light", 1) == 0.0
    )
    # burst capacity scales with weight: 4x the weight, ~4x the admitted
    assert heavy >= 2 * light
    retry = governor.admit("light", 1)
    assert retry > 0.0  # a drained bucket reports a concrete wait


def test_region_blank_params_mean_absent():
    """`?minCadd=&limit=` (an unfilled client template) means 'no filter',
    exactly as before keep_blank_values — only a blank cursor is
    meaningful (it starts a paged walk)."""
    from annotatedvdb_tpu.serve.http import parse_region_params

    min_cadd, max_rank, limit, cursor = parse_region_params(
        "minCadd=&maxConseqRank=&limit=&cursor="
    )
    assert min_cadd is None and max_rank is None
    assert limit == 10_000
    assert cursor == ""
    assert parse_region_params("minCadd=2.5&limit=7")[:1] == (2.5,)
    with pytest.raises(Exception):
        parse_region_params("minCadd=abc")


def test_bind_failure_raises_cleanly(store):
    """A taken port must surface the real OSError immediately, not a 30s
    startup-timeout hang with the cause buried in a daemon thread."""
    import socket as socket_mod

    from annotatedvdb_tpu.serve.aio import build_aio_server

    store_dir, _truth = store
    blocker = socket_mod.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    try:
        server = build_aio_server(
            store_dir=store_dir, port=blocker.getsockname()[1]
        )
        t0 = time.monotonic()
        with pytest.raises(OSError):
            server.start_background()
        assert time.monotonic() - t0 < 10
        server.ctx.batcher.close()
    finally:
        blocker.close()


def test_healthz_stats_and_bad_content_length_parity(store, aio_server):
    """Oracle: ``healthz_payload`` / ``stats_payload`` called directly
    on the server's context; the malformed-Content-Length POST is 400
    with the one bulk grammar message."""
    aport = aio_server.server_address[1]
    ctx = aio_server.ctx
    assert _get(aport, "/healthz")[:2] == (200, healthz_payload(ctx))
    status, body, _h = _get(aport, "/stats")
    # counters move between the request and the direct call; the surface
    # (status + key set, and what holds still) must not fork
    assert status == 200
    got, want = json.loads(body), json.loads(stats_payload(ctx))
    assert got.keys() == want.keys()
    for key in ("generation", "rows", "device", "device_lookup"):
        assert got[key] == want[key], key

    def bad_cl(port):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            conn.putrequest("POST", "/variants")
            conn.putheader("Content-Length", "abc")
            conn.endheaders()
            r = conn.getresponse()
            return r.status, r.read()
        finally:
            conn.close()

    assert bad_cl(aport) == (400, _error(BULK_BODY_ERROR).encode())


def test_bulk_charges_per_id_against_bucket(store):
    """Batching must not bypass per-client fairness: a /variants POST
    debits one token per id (with bounded debt), so after one big bulk
    the same client's next request is throttled while strangers are
    unaffected — and a bulk too large for the bucket to ever repay is
    rejected outright instead of served-then-forgiven."""
    from annotatedvdb_tpu.serve.aio import (
        MAX_DEBT_S,
        ClientGovernor,
        build_aio_server,
    )

    # governor unit: the debt lands, is bounded, and unknown keys no-op
    gov = ClientGovernor(10.0)
    assert gov.admit("hog", 1) == 0.0
    gov.charge("hog", 9999.0)
    retry = gov.admit("hog", 1)
    assert retry > 0.0
    assert retry <= MAX_DEBT_S + 1.0
    gov.charge("stranger", 5.0)  # LRU-evicted key: forfeits, no crash
    # the refillable budget scales with weight and floors at 1
    assert gov.bulk_budget(1) == int(10.0 * MAX_DEBT_S)
    assert gov.bulk_budget(4) == int(40.0 * MAX_DEBT_S)
    assert gov.bulk_budget(999) == gov.bulk_budget(16)  # weight clamp
    assert ClientGovernor(0.001).bulk_budget(1) == 1

    # end to end: a within-budget 100-id bulk indebts the bucket (the
    # charge lands on the loop just after the executor parses), so the
    # same client's point GET goes 429 while a fresh client stays
    # admitted
    store_dir, truth = store
    server = build_aio_server(store_dir=store_dir, port=0, client_rate=5.0)
    server.start_background()
    try:
        port = server.server_address[1]
        vid = _vid(truth[0])
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/variants",
            data=json.dumps({"ids": [vid] * 100}).encode(),
            headers={"X-Client-Id": "bulkhog"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
        deadline = time.monotonic() + 5.0
        throttled = False
        while time.monotonic() < deadline and not throttled:
            status, _b, hdrs = _get(
                port, f"/variant/{vid}", headers={"X-Client-Id": "bulkhog"}
            )
            throttled = status == 429
        assert throttled, "bulk ids never debited the client bucket"
        assert int(hdrs["Retry-After"]) >= 1
        status, _b, _h = _get(
            port, f"/variant/{vid}", headers={"X-Client-Id": "fresh"}
        )
        assert status == 200
        # a bulk beyond the refillable budget (rate 5 * 30s = 150 ids)
        # is rejected BEFORE any lookup runs — the debt clamp must not
        # forgive work already done
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/variants",
            data=json.dumps({"ids": [vid] * 200}).encode(),
            headers={"X-Client-Id": "jumbo"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=30)
        assert exc.value.code == 429
        body = json.loads(exc.value.read().decode())
        assert "rate budget" in body["error"]
        assert int(exc.value.headers["Retry-After"]) >= 1
        # ...and the rejection did not wedge the jumbo client's bucket:
        # only the admit token was spent, so its next point GET is fine
        status, _b, _h = _get(
            port, f"/variant/{vid}", headers={"X-Client-Id": "jumbo"}
        )
        assert status == 200
    finally:
        server.shutdown()
        server.ctx.batcher.close()


def test_bad_env_knob_exits_cleanly(store):
    """An unparseable ``AVDB_SERVE_*`` knob must exit ``serve: cannot
    start`` rc=1, not a traceback — a fleet worker dying with a
    traceback would respawn into a crash loop."""
    import os
    import subprocess
    import sys

    store_dir, _truth = store
    env = dict(os.environ, AVDB_SERVE_BATCH_MAX="abc")
    p = subprocess.run(
        [sys.executable, "-m", "annotatedvdb_tpu", "serve",
         "--storeDir", store_dir, "--port", "0"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 1, p.stderr[-2000:]
    assert "serve: cannot start" in p.stderr
    assert "Traceback" not in p.stderr


def test_frontend_option_is_a_usage_error(store, capsys):
    """There is one front end and no switch: ``--frontend`` in either of
    its old spellings is an unknown option (argparse exit 2), refused
    before a store is opened or a port bound."""
    from annotatedvdb_tpu.cli.serve import main

    store_dir, _truth = store
    for value in ("threaded", "aio"):
        with pytest.raises(SystemExit) as usage:
            main(["--storeDir", store_dir, "--port", "0",
                  "--frontend", value])
        assert usage.value.code == 2
        out, err = capsys.readouterr()
        assert "unrecognized arguments: --frontend" in err
        assert "serving" not in out  # no banner: nothing was bound


def test_the_second_front_end_stays_gone():
    """The threaded server, its batcher and the bridge between them and
    the loop were one fork of the serving path; nothing may bring a piece
    back by import.  (``serve.http`` stays: it holds the API's grammar
    and ``ServeContext``, and serves nothing.)"""
    import importlib

    gone = {
        "annotatedvdb_tpu.serve": ("QueryBatcher",),
        "annotatedvdb_tpu.serve.batcher": ("QueryBatcher", "_Pending"),
        "annotatedvdb_tpu.serve.http": ("build_server", "ServeHandler"),
        "annotatedvdb_tpu.serve.aio": ("_CompletionBridge",
                                       "_resolve_pending"),
        "annotatedvdb_tpu.cli.serve": ("_run_threaded",),
    }
    for module, names in gone.items():
        mod = importlib.import_module(module)
        for name in names:
            assert not hasattr(mod, name), f"{module}.{name}"
    import annotatedvdb_tpu.serve as serve_pkg

    assert "QueryBatcher" not in serve_pkg.__all__
    with pytest.raises(ImportError):
        importlib.import_module("annotatedvdb_tpu.analysis.rules_parity")


def test_abandoned_stream_items_release_admission_slots(store):
    """Exec items a cancelled writer abandons must still release their
    bulk/region admission slots (regression: a pipelining client that
    stopped reading streamed regions permanently burned
    ``ctx.max_inflight`` slots on an otherwise healthy server)."""
    import asyncio

    from annotatedvdb_tpu.serve.aio import build_aio_server

    store_dir, _truth = store
    server = build_aio_server(store_dir=store_dir, port=0)
    ctx = server.ctx

    async def scenario():
        loop = asyncio.get_running_loop()
        # mid-await cancellation path: the settle rides a done callback
        assert ctx.admit()
        fut = loop.create_future()
        fut.set_result(("stream", object()))
        server._settle_when_done(fut)
        await asyncio.sleep(0)
        assert ctx._inflight == 0
        # teardown-drain path: a queued exec item that never reached _emit
        assert ctx.admit()
        fut2 = loop.create_future()
        fut2.set_result(("stream", object()))
        await server._settle(("exec", fut2, "region", 0.0, None, None))
        assert ctx._inflight == 0
        # buffered results (bytes) released on the executor side: no-op
        fut3 = loop.create_future()
        fut3.set_result(b"HTTP/1.1 200 OK\r\n\r\n")
        await server._settle(("exec", fut3, "bulk", 0.0, None, None))
        assert ctx._inflight == 0

    asyncio.run(scenario())
    server.ctx.batcher.close()


def test_client_weight_applies_per_request():
    """The declared weight binds per request, not per bucket lifetime: a
    client whose first request omitted X-Client-Weight must ride its real
    share once it declares one (and drop back when it stops)."""
    from annotatedvdb_tpu.serve.aio import ClientGovernor

    governor = ClientGovernor(10.0)
    governor.admit("c", 1)
    bucket = governor._buckets["c"]
    assert bucket.rate == 10.0
    governor.admit("c", 8)
    assert bucket.rate == 80.0 and bucket.burst == 20.0
    governor.admit("c", 1)
    assert bucket.rate == 10.0


# ---------------------------------------------------------------------------
# chunked region streaming + paging


def test_region_streams_chunked_above_threshold(store, oracle):
    from annotatedvdb_tpu.serve.aio import build_aio_server

    store_dir, _truth = store
    server = build_aio_server(
        store_dir=store_dir, port=0, stream_threshold=5,
    )
    server.start_background()
    try:
        port = server.server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/region/8:1-3000000")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Transfer-Encoding") == "chunked"
        assert resp.getheader("Content-Length") is None
        streamed = resp.read().decode()
        conn.close()
        # de-chunked bytes identical to the engine's buffered text
        assert streamed == oracle.region(
            "8:1-3000000", limit=parse_region_params("")[2])
        rec = json.loads(streamed)
        assert rec["returned"] > 5
        # small regions stay buffered (Content-Length, not chunked)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/region/8:1-10000?limit=3")
        resp = conn.getresponse()
        assert resp.getheader("Transfer-Encoding") is None
        assert resp.getheader("Content-Length") is not None
        resp.read()
        conn.close()
    finally:
        server.shutdown()
        server.ctx.batcher.close()


def test_region_paging_walk_matches_unpaged(store, aio_server):
    port = aio_server.server_address[1]
    _status, full, _ = _get(port, "/region/8:1-3000000?minCadd=3")
    want = [v["primary_key"] for v in json.loads(full)["variants"]]
    got = []
    cursor = ""
    pages = 0
    while cursor is not None:
        _s, body, _ = _get(
            port, f"/region/8:1-3000000?minCadd=3&limit=7&cursor={cursor}"
        )
        rec = json.loads(body)
        assert rec["returned"] <= 7
        got.extend(v["primary_key"] for v in rec["variants"])
        cursor = rec["next"]
        pages += 1
        assert pages < 100
    assert got == want
    assert pages == (len(want) + 6) // 7


def test_region_paging_rejects_foreign_and_junk_cursors(store, aio_server):
    port = aio_server.server_address[1]
    status, _b, _ = _get(port, "/region/8:1-10000?cursor=junk!!")
    assert status == 400
    _s, body, _ = _get(port, "/region/8:1-3000000?limit=3&cursor=")
    token = json.loads(body)["next"]
    assert token
    # replaying the token against DIFFERENT bounds is a client error
    status, body, _ = _get(port, f"/region/8:1-20000?limit=3&cursor={token}")
    assert status == 400 and "cursor" in json.loads(body)["error"]


def test_client_id_rotation_cannot_bypass_rate_limit():
    """A hog rotating X-Client-Id per request must not mint a fresh
    burst every time: ids are scoped to the peer and capped at
    PEER_KEY_CAP distinct buckets, beyond which the sprayer shares the
    peer's aggregate bucket — and the spray cannot evict another peer's
    bucket."""
    from annotatedvdb_tpu.serve.aio import ClientGovernor

    gov = ClientGovernor(base_rate=1.0)
    victim = gov.resolve_key("10.0.0.2", "steady")
    assert gov.admit(victim, 1) == 0.0
    admitted = 0
    for i in range(1000):
        key = gov.resolve_key("10.0.0.9", f"spray-{i}")
        if gov.admit(key, 1) == 0.0:
            admitted += 1
    # bounded by cap buckets' bursts plus the aggregate bucket's burst
    # (each burst is max(rate*0.25, 4) = 4 tokens), nowhere near 1000
    assert admitted <= (gov.PEER_KEY_CAP + 1) * 4 + 8, admitted
    assert victim in gov._buckets  # spray never evicted the other peer


def test_paged_walk_scans_region_once(store, monkeypatch):
    """A cursor walk must reuse its match list across pages: without the
    walk cache every page re-runs the full interval search + filter pass
    (O(pages x region)).  The scan unit is one ``_interval_spans`` call
    (the BITS search against the generation's interval index)."""
    from annotatedvdb_tpu.serve import QueryEngine, SnapshotManager

    store_dir, _truth = store
    engine = QueryEngine(SnapshotManager(store_dir), region_cache_size=0)
    calls = {"n": 0}
    real = engine._interval_spans

    def counting(index, code, starts, ends, host_only=False):
        calls["n"] += 1
        return real(index, code, starts, ends, host_only)

    monkeypatch.setattr(engine, "_interval_spans", counting)
    body = json.loads(engine.region("8:1-3000000", limit=5, cursor=""))
    pages = [body]
    while body.get("next"):
        body = json.loads(
            engine.region("8:1-3000000", limit=5, cursor=body["next"])
        )
        pages.append(body)
    assert len(pages) > 2
    assert calls["n"] == 1, calls["n"]
    # and the walk still matches the unpaged body row-for-row
    unpaged = json.loads(engine.region("8:1-3000000"))
    walked = [v for p in pages for v in p["variants"]]
    assert walked == unpaged["variants"]


def test_cursor_schema_requires_generation_field():
    """The token schema is the full (g, o, k) triple: a hand-built token
    missing ``g`` is malformed, while a well-formed token from ANY
    generation stays replayable (best-effort continuation contract)."""
    import base64

    from annotatedvdb_tpu.serve.engine import (
        QueryError, decode_cursor, encode_cursor,
    )

    token = encode_cursor(3, 7, 42)
    assert decode_cursor(token, 42) == 7
    truncated = base64.urlsafe_b64encode(
        b'{"o":7,"k":42}'
    ).decode().rstrip("=")
    with pytest.raises(QueryError):
        decode_cursor(truncated, 42)


# ---------------------------------------------------------------------------
# coalesced snapshot freshness (AVDB_SERVE_SNAPSHOT_TTL_MS)


def test_snapshot_ttl_coalesces_stats(tmp_path, monkeypatch):
    store_dir = str(tmp_path / "ttl_store")
    _build_store(store_dir)
    calls = {"n": 0}
    real = snapshot_mod._manifest_fingerprint

    def counting(path):
        calls["n"] += 1
        return real(path)

    monkeypatch.setattr(snapshot_mod, "_manifest_fingerprint", counting)
    manager = SnapshotManager(store_dir, ttl_s=60.0)
    base = calls["n"]
    for _ in range(100):
        assert manager.maybe_refresh() is False
    assert calls["n"] == base + 1  # one stat for the whole TTL window
    # refresh() keeps its always-stat semantics
    assert manager.refresh() is False
    assert calls["n"] == base + 2
    # ttl 0: every maybe_refresh stats (the uncoalesced PR-5 behavior)
    manager0 = SnapshotManager(store_dir, ttl_s=0.0)
    base = calls["n"]
    for _ in range(5):
        manager0.maybe_refresh()
    assert calls["n"] == base + 5


def test_snapshot_ttl_commit_visible_within_window(tmp_path):
    store_dir = str(tmp_path / "ttl_live")
    _build_store(store_dir)
    manager = SnapshotManager(store_dir, ttl_s=0.05)
    engine = QueryEngine(manager, region_cache_size=0)
    assert json.loads(engine.region("8:4999999-5001000"))["count"] == 0
    manager.maybe_refresh()  # arm the window
    _commit_more_rows(store_dir)
    # within the window: stale is acceptable and expected...
    deadline = time.monotonic() + 5.0
    while manager.current().generation == 1:
        manager.maybe_refresh()
        if time.monotonic() > deadline:
            raise AssertionError("commit never became visible via TTL path")
        time.sleep(0.01)
    # ...and after it lapses the commit is visible with no forced refresh
    assert json.loads(engine.region("8:4999999-5001000"))["count"] > 0


# ---------------------------------------------------------------------------
# the loop batcher


def test_loop_batcher_close_fails_queued_and_refuses_new():
    """Shutdown contract: a query still queued when the batcher closes
    is failed (cancelled), never left to hang its waiter, and the engine
    is not called for it; a submission after close is refused."""
    import asyncio

    from annotatedvdb_tpu.serve.aio import LoopBatcher

    class _Engine:
        calls = 0

        def lookup_many(self, ids, parsed=None):
            self.calls += 1
            return [None] * len(ids)

    async def scenario():
        engine = _Engine()
        b = LoopBatcher(engine, max_batch=8, max_wait_s=30.0, max_queue=8)
        queued = [b.submit_future(f"1:{100 + i}:A:T") for i in range(3)]
        assert b.depth() == 3
        b.close()
        assert b.depth() == 0 and b._timer is None
        done = await asyncio.gather(*queued, return_exceptions=True)
        assert all(isinstance(e, asyncio.CancelledError) for e in done)
        assert engine.calls == 0
        with pytest.raises(RuntimeError, match="closed"):
            b.submit_future("1:200:A:T")

    asyncio.run(scenario())


def test_loop_batcher_burst_leaves_no_orphan_drain():
    """A submit burst past max_batch schedules exactly one follow-up
    drain.  The old path queued one ``call_soon`` per submit at full
    depth and dropped the backlog timer handle without cancelling it, so
    a request arriving in the same loop slice as the burst's drains was
    left behind a stale armed timer (and could be drained by an orphan
    handle before its coalescing window)."""
    import asyncio

    from annotatedvdb_tpu.serve.aio import LoopBatcher

    class _Engine:
        def lookup_many(self, ids, parsed=None):
            return [None] * len(ids)

    async def scenario():
        b = LoopBatcher(_Engine(), max_batch=4, max_wait_s=30.0,
                        max_queue=64)
        loop = asyncio.get_running_loop()
        burst = [b.submit_future(f"1:{100 + i}:A:T") for i in range(5)]
        lone = []
        # lands in the same loop pass as the burst's drain — the window
        # where the old code's duplicate/orphan handles did damage
        loop.call_soon(lambda: lone.append(b.submit_future("1:900:A:T")))
        for _ in range(4):
            await asyncio.sleep(0)
        # the single follow-up drain coalesced the backlog AND the fresh
        # arrival (max_wait is 30s: a timer could not have done this) in
        # exactly TWO microbatches; the old path's duplicate call_soon
        # plus the orphaned backlog handle executed three, the last a
        # premature single-query batch
        assert all(f.done() for f in burst)
        assert lone and lone[0].done()
        assert b._batches == 2
        assert b.depth() == 0
        # nothing may survive the burst: a stale timer or queued drain
        # here is exactly the orphan that fired into later lone queues
        assert b._timer is None and not b._drain_soon
        b.close()

    asyncio.run(scenario())


def test_heartbeat_mmap_preopened_at_worker_start(store, tmp_path):
    """The heartbeat file is opened + mmap'd ONCE at construction (worker
    start) — never on the event loop (AVDB701: the maintenance tick only
    pack_intos the established mapping).  Pinned by unlinking the file
    before the loop starts: a per-tick reopen would fail and stop the
    beats, while the preopened mapping keeps advancing."""
    import os
    import struct

    from annotatedvdb_tpu.serve.aio import build_aio_server

    store_dir, _truth = store
    from annotatedvdb_tpu.serve.fleet import HB_SLOT

    hb = tmp_path / "hb"
    hb.write_bytes(b"\x00" * HB_SLOT.size)
    server = build_aio_server(
        store_dir=store_dir, port=0, heartbeat_file=str(hb),
        heartbeat_index=0,
    )
    try:
        # the mapping exists BEFORE any loop does
        assert server._hb_mm is not None
        os.unlink(hb)  # a reopen from here on is impossible
        server.start_background()
        deadline = time.monotonic() + 10
        beat1 = 0.0
        while beat1 == 0.0 and time.monotonic() < deadline:
            beat1 = struct.unpack_from("<d", server._hb_mm, 0)[0]
            time.sleep(0.05)
        assert beat1 > 0.0, "first heartbeat never landed"
        beat2 = beat1
        while beat2 <= beat1 and time.monotonic() < deadline:
            beat2 = struct.unpack_from("<d", server._hb_mm, 0)[0]
            time.sleep(0.05)
        assert beat2 > beat1, "heartbeat stopped advancing after unlink"
    finally:
        server.shutdown()
        server.ctx.batcher.close()


def test_heartbeat_unusable_file_logs_and_serves(store, tmp_path):
    """A missing/unopenable heartbeat file degrades exactly as before:
    the worker logs, serves, and the watchdog just never sees it."""
    from annotatedvdb_tpu.serve.aio import build_aio_server

    store_dir, _truth = store
    logs: list = []
    server = build_aio_server(
        store_dir=store_dir, port=0,
        heartbeat_file=str(tmp_path / "missing_hb"),
        log=logs.append,
    )
    try:
        assert server._hb_mm is None
        assert any("heartbeat file unusable" in m for m in logs)
        server.start_background()
        port = server.server_address[1]
        status, body, _hdrs = _get(port, "/healthz")
        assert status == 200 and json.loads(body)["status"] == "ok"
    finally:
        server.shutdown()
        server.ctx.batcher.close()
