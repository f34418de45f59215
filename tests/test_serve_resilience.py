"""Resilient-serving battery: deadline propagation (admission / batcher
queue / executor sheds, 504 mapping, slot release under a full queue),
the brownout ladder (governor state machine, region limit caps,
cache-first points, bulk/region shedding, liveness-vs-readiness split),
the device circuit breaker (trip/half-open/re-close, snapshot swap while
open), the SIGTERM-vs-stream drain fix, and the /_chaos arming route."""

from __future__ import annotations

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from annotatedvdb_tpu.serve import (
    DeadlineExceeded,
    DeviceBreaker,
    OverloadGovernor,
    QueryEngine,
    SnapshotManager,
    StaticSnapshots,
)
from annotatedvdb_tpu.serve import resilience
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.utils import faults
from conftest import BatcherOnLoop, start_server, stop_server
from test_serve import _build_store, _commit_more_rows, _vid


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.reset("")


# ---------------------------------------------------------------------------
# fixtures


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    store_dir = str(tmp_path_factory.mktemp("resil_store"))
    truth = _build_store(store_dir)
    return store_dir, truth


def _wide_store(n: int = 2000) -> VariantStore:
    """One chr8 segment with n rows — enough that the brownout region cap
    (256) and chunked streaming both actually bite."""
    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.types import encode_allele_array

    width = 8
    store = VariantStore(width=width)
    refs = ["A", "C"] * (n // 2)
    alts = ["G", "T"] * (n // 2)
    ref, ref_len = encode_allele_array(refs, width)
    alt, alt_len = encode_allele_array(alts, width)
    store.shard(8).append(
        {"pos": np.arange(1000, 1000 + 7 * n, 7, dtype=np.int32)[:n],
         "h": identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts),
         "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
        annotations={"info": [{"p": "x" * 64} for _ in range(n)]},
    )
    return store


def _get(port: int, path: str, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", headers=headers or {}
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode(), dict(err.headers)


def _post(port: int, path: str, payload: bytes, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=payload, method="POST",
        headers=headers or {},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


# ---------------------------------------------------------------------------
# OverloadGovernor: the ladder state machine (injected clock + depth)


class _Sim:
    def __init__(self):
        self.t = 0.0
        self.depth = 0

    def governor(self, **kw):
        return OverloadGovernor(
            depth_fn=lambda: self.depth, max_queue=100,
            p99_target_s=0.1, clock=lambda: self.t,
            eval_interval_s=0.1, hold_s=0.5, **kw,
        )


def test_governor_escalates_one_level_per_eval_on_depth():
    sim = _Sim()
    g = sim.governor()
    sim.depth = 80  # 0.8 of the bound: hot
    for want in (1, 2, 3, 3):  # one level per evaluation, capped at 3
        sim.t += 0.11
        assert g.maybe_step() == want
    assert g.shed_bulk() and g.cache_first()
    assert g.region_limit_cap() == resilience.BROWNOUT_REGION_LIMIT


def test_governor_latency_exceedance_escalates():
    sim = _Sim()
    g = sim.governor()
    for _ in range(100):
        g.note_latency(0.5)  # 5x the target: exceedance ewma saturates
    sim.t += 0.11
    assert g.maybe_step() == 1


def test_governor_hysteresis_holds_then_deescalates():
    sim = _Sim()
    g = sim.governor()
    sim.depth = 80
    sim.t += 0.11
    assert g.maybe_step() == 1
    sim.depth = 0  # instantly calm — but the hold must out-wait flapping
    sim.t += 0.11
    assert g.maybe_step() == 1  # inside hold_s: stays up
    sim.t += 0.6
    assert g.maybe_step() == 0  # past hold: steps down


def test_governor_idle_decay_releases_latency_signal():
    sim = _Sim()
    g = sim.governor()
    for _ in range(100):
        g.note_latency(0.5)
    sim.t += 0.11
    assert g.maybe_step() == 1
    # no further samples: the ewma halves per idle eval until calm
    level = 1
    for _ in range(20):
        sim.t += 0.6
        level = g.maybe_step()
        if level == 0:
            break
    assert level == 0


# ---------------------------------------------------------------------------
# deadline: batcher-queue shedding under a FULL queue (satellite)


class _CountingEngine:
    """lookup_many answers absence and counts its calls."""

    def __init__(self):
        self.calls = 0

    def lookup_many(self, ids, parsed=None):
        self.calls += 1
        return [None] * len(ids)


def test_deadline_shed_under_full_queue_releases_admission_slots():
    """The drain runs on the loop, so the queue fills while the loop is
    busy: one turn admits the first query and four whose budget dies
    before the loop gets back to draining."""
    import asyncio

    from annotatedvdb_tpu.serve import QueueFull

    engine = _CountingEngine()
    batcher = BatcherOnLoop(engine, max_batch=1, max_wait_s=0.0,
                            max_queue=5)

    async def one_busy_turn(b):
        first = b.submit_future("3:10:A:C")
        # the queue fills with requests whose budget dies immediately
        dead = [
            b.submit_future("3:10:A:C", deadline_t=time.monotonic() + 0.01)
            for _ in range(4)
        ]
        assert b.depth() == 5
        # admission bound reached: the 429 path still works
        with pytest.raises(QueueFull):
            b.submit_future("3:10:A:C")
        # the loop stays busy (as under an engine call); every deadline lapses
        time.sleep(0.05)  # avdb: noqa[AVDB701] -- the test's subject IS a blocked loop
        return await first, await asyncio.gather(
            *dead, return_exceptions=True)

    try:
        first, dead = batcher.run(one_busy_turn)
        assert first is None  # served: the row is absent
        # the shed drains release their queue slots and fail their callers
        # with the honest cause
        assert len(dead) == 4
        assert all(isinstance(e, DeadlineExceeded) for e in dead)
        assert batcher.batcher.depth() == 0
        # slots released: a fresh submission is admitted AND served
        assert batcher.submit("3:10:A:C") is None
        # the shed pendings never reached the engine: exactly the first
        # drain and the fresh one executed
        assert engine.calls == 2
    finally:
        batcher.close()


def test_queued_submit_surfaces_deadline_exceeded():
    """A query whose budget lapses inside the batch-wait window fails
    with DeadlineExceeded when its drain comes, before the engine."""
    engine = _CountingEngine()
    batcher = BatcherOnLoop(engine, max_batch=8, max_wait_s=0.08,
                            max_queue=8)
    try:
        with pytest.raises(DeadlineExceeded):
            batcher.submit("3:10:A:C",
                           deadline_t=time.monotonic() + 0.01)
        assert engine.calls == 0
    finally:
        batcher.close()


# ---------------------------------------------------------------------------
# deadline: HTTP 504 end-to-end


@pytest.mark.parametrize("dies_at, budget_ms", [("batcher", "10"),
                                                ("admission", "0.0001")])
def test_point_deadline_maps_to_504_and_counter(store, dies_at, budget_ms):
    """A point read whose budget runs out is shed as 504 where it dies,
    and counted there: in the batcher's queue (the drain sheds it with
    ``DeadlineExceeded``), or already at admission (the one message
    constant, ``MSG_DEADLINE_ADMISSION``)."""
    from annotatedvdb_tpu.serve.http import MSG_DEADLINE_ADMISSION

    store_dir, truth = store
    # a batcher that waits 80ms before draining: a 10ms request deadline
    # deterministically lapses in the queue; a sub-microsecond one is
    # dead by the admission check
    server = start_server(store_dir=store_dir, max_wait_s=0.08)
    port = server.server_address[1]
    try:
        vid = _vid(truth[0])
        # generous deadline: served normally
        status, _body, _ = _get(port, f"/variant/{vid}",
                                headers={"X-Deadline-Ms": "5000"})
        assert status == 200
        status, body, _ = _get(port, f"/variant/{vid}",
                               headers={"X-Deadline-Ms": budget_ms})
        assert status == 504, body
        if dies_at == "admission":
            assert json.loads(body) == {"error": MSG_DEADLINE_ADMISSION}
            assert server.ctx.batcher.drain_stats()["queries"] == 1
        else:
            assert json.loads(body) == {"error": (
                f"query {vid!r} exceeded its deadline in the serve queue")}
        # poll until the counter of the stage that shed it lands
        counted = f'avdb_deadline_shed_total{{stage="{dies_at}"}} 1'
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            _s, metrics, _h = _get(port, "/metrics")
            if counted in metrics:
                break
            time.sleep(0.05)
        assert counted in metrics
    finally:
        stop_server(server)


# ---------------------------------------------------------------------------
# brownout ladder end-to-end (forced levels)


@pytest.fixture()
def ladder_server():
    """A server over the wide store (region cap must bite)."""
    server = start_server(manager=StaticSnapshots(_wide_store()))
    try:
        yield server
    finally:
        stop_server(server)


def test_brownout_level1_caps_region_limits(ladder_server):
    ctx, port = ladder_server.ctx, ladder_server.server_address[1]
    status, body, _ = _get(port, "/region/8:1-100000?limit=2000")
    assert status == 200 and json.loads(body)["returned"] == 2000
    ctx.governor.force_level(1)
    try:
        status, body, _ = _get(port, "/region/8:1-100000?limit=2000")
        assert status == 200
        assert json.loads(body)["returned"] \
            == resilience.BROWNOUT_REGION_LIMIT
    finally:
        ctx.governor.force_level(0)


def test_brownout_level2_serves_points_cache_first(ladder_server):
    ctx, port = ladder_server.ctx, ladder_server.server_address[1]
    # level 0 populates the id-keyed cache (hit and miss both cache)
    s1, cached_body, _ = _get(port, "/variant/8:1000:A:G")
    assert s1 == 200
    s2, _b, _ = _get(port, "/variant/8:999:A:G")
    assert s2 == 404
    ctx.governor.force_level(2)
    real = ctx.engine.lookup_many

    def boom(ids, parsed=None):
        raise RuntimeError("engine must not be consulted")

    ctx.engine.lookup_many = boom
    try:
        # cached id answers without touching the (broken) engine —
        # byte-identical to the level-0 response
        status, body, _ = _get(port, "/variant/8:1000:A:G")
        assert (status, body) == (200, cached_body)
        status, _body, _ = _get(port, "/variant/8:999:A:G")
        assert status == 404  # cached absence is absence
        # an UNcached id still goes to the engine (and fails here)
        status, _body, _ = _get(port, "/variant/8:1001:C:T")
        assert status == 500
    finally:
        ctx.engine.lookup_many = real
        ctx.governor.force_level(0)


def test_brownout_level3_sheds_bulk_region_keeps_points(ladder_server):
    ctx, port = ladder_server.ctx, ladder_server.server_address[1]
    ctx.governor.force_level(3)
    try:
        status, body, headers = _get(port, "/region/8:1-100000")
        assert status == 503 and "brownout" in body
        assert headers.get("Retry-After") == "1"
        status, body = _post(
            port, "/variants",
            json.dumps({"ids": ["8:1000:A:G"]}).encode(),
        )
        assert status == 503 and "brownout" in body
        # the traffic that matters keeps serving
        status, _body, _ = _get(port, "/variant/8:1000:A:G")
        assert status == 200
        # readiness flips (liveness stays 200); re-pin the level
        # right before the probes — health polls legitimately step
        # the ladder, and a slow test run must not race the hold
        ctx.governor.force_level(3)
        status, body, _ = _get(port, "/readyz")
        assert status == 503 and not json.loads(body)["ready"]
        ctx.governor.force_level(3)
        status, body, _ = _get(port, "/healthz")
        assert status == 200
        h = json.loads(body)
        assert h["brownout_level"] == 3 and h["ready"] is False
    finally:
        ctx.governor.force_level(0)
    status, _body, _ = _get(port, "/readyz")
    assert status == 200


def test_health_polls_deescalate_a_fully_drained_worker(ladder_server):
    """A shed_bulk worker a router has DRAINED completes no requests —
    the router's own readiness probes (and the server's maintenance
    tick) step the idle ladder back down to ready."""
    ctx, port = ladder_server.ctx, ladder_server.server_address[1]
    g = ctx.governor
    old_interval, old_hold = g.eval_interval_s, g.hold_s
    g.eval_interval_s = 0.0
    g.hold_s = 0.0
    g.force_level(3)
    try:
        status = None
        for _ in range(10):  # readiness probes ONLY, no data traffic
            status, _body, _ = _get(port, "/readyz")
            if status == 200:
                break
            # a pre-existing eval window (set before the test shrank
            # the interval) may still be open: pace the probes like a
            # real router would
            time.sleep(0.3)
        assert status == 200
        assert g.level < 3  # readiness returns as soon as shed_bulk clears
        # and continued probes unwind the ladder all the way down
        for _ in range(10):
            if g.level == 0:
                break
            _get(port, "/readyz")
            time.sleep(0.15)
        assert g.level == 0
    finally:
        g.eval_interval_s, g.hold_s = old_interval, old_hold
        g.force_level(0)


def test_healthz_and_readyz_parity_with_the_builders(ladder_server):
    """Oracle: ``healthz_payload`` / ``readyz_payload`` called directly
    on the idle server's context."""
    from annotatedvdb_tpu.serve.http import healthz_payload, readyz_payload

    ctx, port = ladder_server.ctx, ladder_server.server_address[1]
    assert _get(port, "/healthz")[:2] == (200, healthz_payload(ctx))
    assert _get(port, "/readyz")[:2] == readyz_payload(ctx)
    ctx.governor.force_level(3)
    try:
        status, body = readyz_payload(ctx)
        assert status == 503
        assert _get(port, "/readyz")[:2] == (status, body)
    finally:
        ctx.governor.force_level(0)


def test_snapshot_manager_reports_swapping_during_generation_load(
        tmp_path, monkeypatch):
    """The REAL readiness signal: while refresh() loads a new generation
    the manager reports ``swapping`` (readyz 503), and the flag clears
    whether the swap lands or fails."""
    store_dir = str(tmp_path / "swapstore")
    _build_store(store_dir)
    manager = SnapshotManager(store_dir)
    assert manager.swapping is False
    _commit_more_rows(store_dir)
    seen = {}
    real_load = VariantStore.load

    def spy(d, readonly=False):
        seen["during_load"] = manager.swapping
        return real_load(d, readonly=readonly)

    monkeypatch.setattr(VariantStore, "load", spy)
    assert manager.refresh() is True
    assert seen["during_load"] is True
    assert manager.swapping is False
    # a FAILED swap (snapshot.swap raise) must clear the flag too
    _commit_more_rows(store_dir)
    faults.reset("snapshot.swap:1:raise")
    with pytest.raises(Exception):
        manager.refresh()
    assert manager.swapping is False


def test_readyz_not_ready_during_snapshot_swap(ladder_server):
    port = ladder_server.server_address[1]
    manager = ladder_server.ctx.manager
    manager.swapping = True  # StaticSnapshots: simulate a loading swap
    try:
        status, body, _ = _get(port, "/readyz")
        assert status == 503
        assert "swap" in json.loads(body)["reason"]
    finally:
        manager.swapping = False
    status, _body, _ = _get(port, "/readyz")
    assert status == 200


# ---------------------------------------------------------------------------
# circuit breaker: snapshot swap arriving while OPEN (satellite)


def test_snapshot_swap_while_breaker_open_serves_host_then_recloses(
        tmp_path, store):
    store_dir, truth = store
    clock = {"t": 0.0}
    manager = SnapshotManager(store_dir)
    breaker = DeviceBreaker(cooldown_s=5.0, clock=lambda: clock["t"])
    engine = QueryEngine(manager, region_cache_size=0, breaker=breaker)
    vid = _vid(truth[0])
    want = engine.lookup(vid)
    assert want is not None

    # trip the breaker for this id's chromosome group
    faults.reset("engine.device_probe:prob:1.0:eio")
    code = truth[0]["chrom"]
    for _ in range(breaker.failure_threshold):
        assert engine.lookup(vid) == want
    assert breaker.state(code) == "open"

    # a loader commit lands and swaps in WHILE the breaker is open: the
    # new generation must serve (host path) immediately — including rows
    # only the new generation has — with the breaker still open
    _commit_more_rows(store_dir)  # appends 8:5000000+11i A->C rows
    assert manager.refresh() is True
    assert breaker.state(code) == "open"
    assert engine.lookup(vid) == want  # old row: byte-stable across gens
    got = engine.lookup("8:5000000:A:C")
    assert got is not None and '"position":5000000' in got

    # fault gone + cooldown over: the new generation re-probes the device
    # path half-open and re-closes
    faults.reset("")
    clock["t"] = 100.0
    assert engine.lookup(vid) == want
    assert breaker.state(code) == "closed"


# ---------------------------------------------------------------------------
# SIGTERM drain vs in-flight chunked stream (satellite regression)


def _dechunk(raw: bytes) -> tuple[bytes, bool]:
    """(body, saw_terminator) from a chunked-encoded byte stream."""
    body = b""
    saw_end = False
    while raw:
        line, _, rest = raw.partition(b"\r\n")
        size = int(line, 16)
        if size == 0:
            saw_end = True
            break
        body += rest[:size]
        raw = rest[size + 2:]
    return body, saw_end


def test_drain_mid_stream_truncates_cleanly_with_trailer():
    from annotatedvdb_tpu.serve.aio import build_aio_server

    wide = _wide_store(6000)
    server = build_aio_server(
        manager=StaticSnapshots(wide), port=0, stream_threshold=4
    )
    server.drain_s = 2.0
    server.start_background()
    port = server.server_address[1]
    sock = socket.create_connection(("127.0.0.1", port), timeout=10)
    received = bytearray()
    done = threading.Event()

    def read_slowly():
        # a slow consumer: the server MUST be mid-stream when the drain
        # starts (the whole 1MB+ body cannot fit the socket buffers)
        try:
            while True:
                chunk = sock.recv(2048)
                if not chunk:
                    break
                received.extend(chunk)
                time.sleep(0.005)
        except OSError:
            pass
        finally:
            done.set()

    try:
        sock.sendall(b"GET /region/8:1-100000 HTTP/1.1\r\nHost: t\r\n\r\n")
        reader = threading.Thread(target=read_slowly, daemon=True)
        reader.start()
        deadline = time.monotonic() + 10
        while len(received) < 4096 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(received) >= 4096, "stream never started"
        server.shutdown()  # SIGTERM-equivalent drain, stream in flight
        assert done.wait(30), "client never saw the stream end"
    finally:
        sock.close()
        server.ctx.batcher.close()

    head, _, rest = bytes(received).partition(b"\r\n\r\n")
    assert b"200 OK" in head and b"chunked" in head
    body, saw_end = _dechunk(rest)
    # the framing terminated properly (no torn chunk), and the body is
    # VALID JSON that says whether it was cut short
    assert saw_end, "chunked framing was torn (no terminating 0-chunk)"
    doc = json.loads(body)
    if len(doc["variants"]) < doc["count"]:
        assert doc.get("truncated") is True
    else:
        assert doc["returned"] == doc["count"]


def _panel_request(specs: list) -> bytes:
    body = json.dumps({"regions": specs}).encode()
    return (b"POST /regions HTTP/1.1\r\nHost: t\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)


#: the two bodies the streaming writer carries: one region's rows, and a
#: panel's per-interval envelopes (60 intervals of 100 rows each)
_STREAMS = {
    "region": b"GET /region/8:1-100000 HTTP/1.1\r\nHost: t\r\n\r\n",
    "regions_panel": _panel_request(
        [f"8:{1000 + 700 * k}-{1699 + 700 * k}" for k in range(60)]),
}


@pytest.mark.parametrize("stream", sorted(_STREAMS))
def test_drain_between_render_blocks_ends_the_body_whole(stream,
                                                         monkeypatch):
    """The drain arrives while the body's third block of rows renders (on
    the event loop's thread, where a signal handler would set it): the
    chunk in hand is written, and the body closes at a row — for a panel
    an envelope — boundary, saying it is partial."""
    from annotatedvdb_tpu.serve import engine as engine_mod
    from annotatedvdb_tpu.serve.aio import build_aio_server

    server = build_aio_server(
        manager=StaticSnapshots(_wide_store(6000)), port=0,
        stream_threshold=4,
    )
    server.drain_s = 5.0
    blocks = []
    real = engine_mod._render_segment_rows

    def third_block_drains(seg, j, label, width, clock):
        blocks.append(int(j.shape[0]))
        if len(blocks) == 3:
            server._stop.set()
        return real(seg, j, label, width, clock)

    monkeypatch.setattr(engine_mod, "_render_segment_rows",
                        third_block_drains)
    server.start_background()
    received = bytearray()
    try:
        with socket.create_connection(
            ("127.0.0.1", server.server_address[1]), timeout=30
        ) as sock:
            sock.sendall(_STREAMS[stream])
            # to the chunked terminator (the connection is keep-alive)
            while not received.endswith(b"\r\n0\r\n\r\n"):
                chunk = sock.recv(1 << 16)
                assert chunk, "connection closed before the body ended"
                received.extend(chunk)
    finally:
        server.shutdown()
        server.ctx.batcher.close()
    head, _, rest = bytes(received).partition(b"\r\n\r\n")
    assert b"200 OK" in head and b"chunked" in head
    body, saw_end = _dechunk(rest)
    assert saw_end, "chunked framing was torn (no terminating 0-chunk)"
    doc = json.loads(body)
    assert doc["truncated"] is True
    assert len(blocks) == 3  # nothing rendered after the drain was seen
    if stream == "region":
        assert doc["returned"] == doc["count"] == 6000
        assert 0 < len(doc["variants"]) <= sum(blocks) < 6000
        return
    assert doc["n"] == 60
    assert 0 < len(doc["results"]) < 60
    for envelope in doc["results"]:
        assert envelope["returned"] == len(envelope["variants"]) == 100
    assert 100 * len(doc["results"]) <= sum(blocks)


# ---------------------------------------------------------------------------
# /_chaos runtime arming route


def test_chaos_route_is_gated_and_arms_with_ttl(store, monkeypatch):
    from annotatedvdb_tpu.serve.aio import build_aio_server

    store_dir, _truth = store
    # gate OFF: the route does not exist
    server = build_aio_server(store_dir=store_dir, port=0)
    server.start_background()
    try:
        status, body = _post(server.server_address[1], "/_chaos",
                             b'{"spec": "serve.batch:1:raise"}')
        assert status == 404
    finally:
        server.shutdown()
        server.ctx.batcher.close()

    # gate ON: arms in-process, ttl auto-disarms
    monkeypatch.setenv("AVDB_SERVE_CHAOS", "1")
    server = build_aio_server(store_dir=store_dir, port=0)
    server.start_background()
    try:
        port = server.server_address[1]
        status, body = _post(
            port, "/_chaos",
            json.dumps({"spec": "serve.batch:1:raise",
                        "ttl_s": 0.2}).encode(),
        )
        assert status == 200 and json.loads(body)["armed"] \
            == "serve.batch:1:raise"
        assert faults.armed_point() == "serve.batch"
        deadline = time.monotonic() + 5
        while faults.armed_point() is not None \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert faults.armed_point() is None  # ttl disarmed it
        status, body = _post(port, "/_chaos", b'{"spec": "nope:1"}')
        assert status == 400
        # a malformed ttl must refuse BEFORE arming (a fault armed with
        # its promised auto-disarm missing is the dangerous outcome)
        status, _body = _post(
            port, "/_chaos",
            b'{"spec": "serve.batch:1:raise", "ttl_s": "bogus"}',
        )
        assert status == 400
        assert faults.armed_point() is None
        # non-object bodies are 400, not a dropped connection
        status, _body = _post(port, "/_chaos", b"[1, 2]")
        assert status == 400
        # a stale ttl timer must not disarm a NEWER arming
        status, _body = _post(
            port, "/_chaos",
            json.dumps({"spec": "serve.batch:1:raise",
                        "ttl_s": 0.2}).encode(),
        )
        assert status == 200
        status, _body = _post(
            port, "/_chaos",
            json.dumps({"spec": "serve.accept:1:raise"}).encode(),
        )
        assert status == 200
        time.sleep(0.5)  # the first arm's ttl fires into the second arm
        assert faults.armed_point() == "serve.accept"
    finally:
        server.shutdown()
        server.ctx.batcher.close()
        faults.reset("")
