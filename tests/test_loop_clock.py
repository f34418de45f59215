"""The serving event loop's own account (``obs/loopclock.py``) and the
three spans that close a request's life on the loop.

The selector wrapper against a fake clock (every turn is a wait and a busy
stretch, the parts stay inside ``busy``), a real loop whose callback blocks
(``max_turn_ms`` sees it, ``wait_s`` does not), the ``avdb.loop.*``
annotations' run-riding rule, and a served point read, bulk lookup and
streamed panel: ``read``, ``wake``, ``reply`` on the request's trace, in
order, beside reply bytes that are what they were, byte for byte.
"""

from __future__ import annotations

import asyncio
import json
import selectors
import socket
import time

import pytest

from annotatedvdb_tpu.obs import loopclock
from annotatedvdb_tpu.obs.loopclock import PARTS, LoopClock, TimedSelector
from annotatedvdb_tpu.obs.reqtrace import LOOP_STAGES, STAGES
from conftest import (bulk_envelope, ring_records, start_server,
                      stop_server)
from test_serve import _build_store, _vid


class _FakeClock:
    def __init__(self):
        self.t = 1_000

    def __call__(self) -> int:
        return self.t


class _FakeSelector:
    """``select`` "waits" by advancing the fake clock by the next planned
    duration; the other methods are the wrapped selector's own."""

    def __init__(self, clock: _FakeClock, waits):
        self.clock, self.waits = clock, iter(waits)
        self.timeouts: list = []

    def select(self, timeout=None):
        self.timeouts.append(timeout)
        self.clock.t += next(self.waits, 0)  # past the plan: a poll
        return ["event"]

    def register(self, *a):
        return ("registered", a)

    unregister = modify = get_key = register

    def close(self):
        pass

    def get_map(self):
        return {}


def _turns(plan, capturing=lambda: False):
    """Drive a TimedSelector through ``plan`` = [(busy_ns, wait_ns,
    timeout)]: busy before each select, then the wait inside it."""
    now = _FakeClock()
    clock = LoopClock(now=now)
    inner = _FakeSelector(now, [w for _b, w, _t in plan])
    sel = TimedSelector(inner, clock, capturing=capturing)
    for busy, _wait, timeout in plan:
        now.t += busy
        assert sel.select(timeout) == ["event"]
    return now, clock, sel, inner


def test_every_turn_is_a_wait_and_a_busy_stretch():
    plan = [(300, 2_000, None), (50_000, 10, 0), (700, 90_000, 0.002),
            (1_200, 0, 0)]
    now, clock, sel, inner = _turns(plan)
    assert inner.timeouts == [None, 0, 0.002, 0]  # handed through untouched
    assert sel.register(3, 1) == ("registered", (3, 1))  # and so is the rest
    clock.read_ns, clock.reply_ns, clock.tick_ns = 20_000, 9_000, 500
    now.t += 4_000  # the stretch in progress when /stats is read
    stats = clock.stats(drain_ns=15_000, reset_recent=True)
    assert stats["turns"] == 4
    assert stats["wait_s"] == pytest.approx(92_010 / 1e9)
    assert stats["busy_s"] == pytest.approx((52_200 + 4_000) / 1e9)
    assert stats["busy_s"] + stats["wait_s"] == pytest.approx(stats["wall_s"])
    parts = sum(stats[f"{p}_s"] for p in PARTS)
    assert parts == pytest.approx(44_500 / 1e9) and parts <= stats["busy_s"]
    assert stats["other_s"] == pytest.approx(stats["busy_s"] - parts)
    assert stats["other_s"] >= 0
    assert stats["max_turn_ms"] == pytest.approx(0.05)
    assert stats["max_turn_ms_since_start"] == pytest.approx(0.05)
    # a read of /stats starts the recent window again; since-start stays
    now.t += 1_000
    sel.select(0)
    again = clock.stats()
    assert again["max_turn_ms"] == pytest.approx(0.005)
    assert again["max_turn_ms_since_start"] == pytest.approx(0.05)
    assert "loop_max_turn=0.05ms" in clock.max_turn_note()


def test_a_blocking_callback_shows_in_max_turn_and_nowhere_in_wait():
    clock = LoopClock()

    async def main():
        loop = asyncio.get_running_loop()
        done = loop.create_future()

        def block():
            time.sleep(0.05)
            done.set_result(None)

        await asyncio.sleep(0.02)  # a real wait first
        loop.call_soon(block)
        await done
        return clock.stats()

    stats = asyncio.run(main(), loop_factory=lambda: asyncio.SelectorEventLoop(
        TimedSelector(selectors.DefaultSelector(), clock)))
    assert stats["max_turn_ms"] >= 50.0
    assert stats["busy_s"] >= 0.05
    assert 0.015 <= stats["wait_s"] < 0.05  # the sleep, never the callback
    assert stats["busy_s"] + stats["wait_s"] == pytest.approx(stats["wall_s"])
    assert stats["other_s"] >= 0 and stats["turns"] >= 3


class _Annotations:
    """Stands in for ``profiling.annotation``: records (name, start, end,
    metadata) by the fake clock."""

    def __init__(self, now):
        self.now, self.closed = now, []

    def __call__(self, name, **args):
        outer = self

        class Span:
            def __enter__(self):
                self.start = outer.now()
                self.meta = dict(args)
                return self

            def set_metadata(self, **more):
                self.meta.update(more)

            def __exit__(self, *exc):
                outer.closed.append((name, self.start, outer.now(),
                                     self.meta))

        return Span()


def test_a_run_rides_through_short_waits_and_ends_with_a_real_one(
        monkeypatch):
    ride = loopclock.RUN_RIDES_NS
    plan = [(1_000, ride // 4, None),      # a short wait: the run rides on
            (2_000, 10, 0),                # a poll: no wait span at all
            (3_000, ride - 1, 0.5),        # just under: still riding
            (4_000, 5 * ride, None),       # a real wait ends the run
            (6_000, ride // 2, None)]      # the next run, still open
    now = _FakeClock()
    spans = _Annotations(now)
    monkeypatch.setattr(loopclock.profiling, "annotation", spans)
    capturing = [True]
    clock = LoopClock(now=now)
    inner = _FakeSelector(now, [w for _b, w, _t in plan])
    sel = TimedSelector(inner, clock, capturing=lambda: capturing[0])
    t_start = now.t
    for busy, _wait, timeout in plan:
        now.t += busy
        sel.select(timeout)
    waits = [s for s in spans.closed if s[0] == "avdb.loop.wait"]
    runs = [s for s in spans.closed if s[0] == "avdb.loop.run"]
    assert [e - s for _n, s, e, _m in waits] == [
        ride // 4, ride - 1, 5 * ride, ride // 2]  # never around the poll
    (run,) = runs  # opened at the first select, closed by the real wait
    assert run[1] == t_start + 1_000 and run[2] == waits[2][2]
    assert run[3] == {"turns": 4,
                      "wait_us": (ride // 4 + ride - 1 + 5 * ride) // 1000}
    # each wait it rode through, and the one that ended it, lies inside it
    assert all(run[1] <= s and e <= run[2] for _n, s, e, _m in waits[:3])
    # the capture ends: the open run closes at the next select, and an
    # untraced server creates nothing
    capturing[0] = False
    now.t += 500
    sel.select(None)
    assert len([s for s in spans.closed if s[0] == "avdb.loop.run"]) == 2
    n = len(spans.closed)
    sel.select(None)
    assert len(spans.closed) == n
    # the counters never depended on the capture
    assert clock.stats()["turns"] == 7


# ---------------------------------------------------------------------------
# a served request's life on the loop


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    store_dir = str(tmp_path_factory.mktemp("loop_store"))
    return store_dir, _build_store(store_dir)


@pytest.fixture(scope="module")
def server(store):
    """A server whose region bodies stream from 5 rows on."""
    srv = start_server(store_dir=store[0], stream_threshold=5)
    try:
        yield srv
    finally:
        stop_server(srv)


def _raw(port: int, request: bytes) -> bytes:
    """One request on a socket of its own, ``Connection: close``: every
    byte the server sent back."""
    with socket.create_connection(("127.0.0.1", port), timeout=30) as sock:
        sock.sendall(request)
        chunks = []
        while True:
            data = sock.recv(1 << 16)
            if not data:
                return b"".join(chunks)
            chunks.append(data)


def _request(method: str, path: str, tid: str, body: bytes = b"") -> bytes:
    head = (f"{method} {path} HTTP/1.1\r\nHost: t\r\nX-Request-Id: {tid}\r\n"
            "Connection: close\r\n")
    if body:
        head += f"Content-Length: {len(body)}\r\n"
    return head.encode() + b"\r\n" + body


def _dechunk(body: bytes) -> bytes:
    out, at = [], 0
    while True:
        eol = body.index(b"\r\n", at)
        size = int(body[at:eol], 16)
        if size == 0:
            assert body[eol:] == b"\r\n\r\n"
            return b"".join(out)
        out.append(body[eol + 2:eol + 2 + size])
        at = eol + 2 + size + 2


def _buffered(tid: str, text: str) -> bytes:
    """A buffered 200 as the server has always framed it."""
    payload = text.encode()
    return (b"HTTP/1.1 200 OK\r\nX-Request-Id: " + tid.encode()
            + b"\r\nContent-Type: application/json\r\nContent-Length: "
            + str(len(payload)).encode() + b"\r\n\r\n" + payload)


def _case(kind: str, truth, engine):
    """(request bytes, the reply's bytes as the engine called directly
    gives them, trace id) for one kind of request."""
    tid = f"loop-{kind}"
    if kind == "point":
        vid = _vid(truth[3])
        return (_request("GET", f"/variant/{vid}", tid),
                _buffered(tid, engine.lookup_many([vid])[0]), tid)
    if kind == "bulk":
        ids = [_vid(r) for r in truth[:25]] + ["8:999999:A:C"]
        return (_request("POST", "/variants", tid,
                         json.dumps({"ids": ids}).encode()),
                _buffered(tid, bulk_envelope(engine.lookup_many(ids))), tid)
    specs = ["8:1-3000000", "1:400-130000", "X:1-100000"]
    body = engine.regions_serve(specs, limit=40).assemble().encode()
    return (_request("POST", "/regions", tid,
                     json.dumps({"regions": specs, "limit": 40}).encode()),
            b"HTTP/1.1 200 OK\r\nX-Request-Id: " + tid.encode()
            + b"\r\nContent-Type: application/json\r\n"
            + b"Transfer-Encoding: chunked\r\n\r\n" + body, tid)


@pytest.mark.parametrize("kind", ["point", "bulk", "regions"])
def test_a_request_carries_read_wake_reply_and_its_bytes_are_unchanged(
        store, server, kind):
    _store_dir, truth = store
    ctx = server.ctx
    request, want, tid = _case(kind, truth, ctx.engine)
    got = _raw(server.server_address[1], request)
    if kind == "regions":  # a streamed body: chunk framing aside
        head, _, chunked = got.partition(b"\r\n\r\n")
        got = head + b"\r\n\r\n" + _dechunk(chunked)
    assert got == want
    # the trace seals after the write the client already read
    (rec,) = ring_records(ctx, tid)
    assert rec[1] == kind and rec[2] == 200
    stages = [s for s in rec[6] if s[3] is None]  # (name, start, end, None)
    names = [s[0] for s in stages]
    by_name = {s[0]: s for s in stages}
    assert set(LOOP_STAGES) <= set(names) and set(names) <= set(STAGES)
    # in order of their starts; every span runs forward
    middle = ["queue", "device"] if kind == "point" else [
        "admission", "device"]
    order = ["read", *middle, "wake", "reply"]
    starts = [by_name[n][1] for n in order]
    assert starts == sorted(starts), list(zip(order, starts))
    assert all(s[1] <= s[2] for s in stages)
    # read starts before the trace existed (the head was complete), wake
    # starts where the work ended, reply where wake ended; total runs to
    # the write, so every stage lies inside it
    assert by_name["read"][1] <= rec[3]
    assert by_name["wake"][1] >= by_name["device"][2]
    assert by_name["reply"][1] == by_name["wake"][2]
    assert by_name["reply"][2] <= rec[3] + int(rec[4] * 1e9) + 1
    if kind == "point":
        assert "admission" not in names  # a point read's is inside read
        assert by_name["render"][1] == by_name["reply"][1]


def test_co_batched_reads_share_one_wake_start(store, server):
    _store_dir, truth = store
    ctx = server.ctx
    port = server.server_address[1]
    vids = [_vid(r) for r in truth[10:16]]
    # pipelined on ONE connection: the six reads meet in one drain
    with socket.create_connection(("127.0.0.1", port), timeout=30) as sock:
        sock.sendall(b"".join(
            f"GET /variant/{v} HTTP/1.1\r\nHost: t\r\n"
            f"X-Request-Id: cobatch-{i}\r\n\r\n".encode()
            for i, v in enumerate(vids)))
        got = b""
        while got.count(b"HTTP/1.1 200 OK") < len(vids):
            got += sock.recv(1 << 16)
    recs = [ring_records(ctx, f"cobatch-{i}")[0] for i in range(len(vids))]
    wakes = {next(s for s in r[6] if s[0] == "wake")[1] for r in recs}
    devices = {next(s for s in r[6] if s[0] == "device")[1:3] for r in recs}
    assert len(devices) == 1, "the reads did not share a drain"
    assert len(wakes) == 1  # one clock read a drain, shared by its requests


def test_stats_and_metrics_expose_the_loop_block(store, server):
    import urllib.request

    port = server.server_address[1]

    def get(path):
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=30) as r:
            return r.read().decode()

    get(f"/variant/{_vid(store[1][0])}")
    loop = json.loads(get("/stats"))["loop"]
    assert set(loop) == {"turns", "wait_s", "busy_s", "wall_s", "max_turn_ms",
                         "max_turn_ms_since_start", "other_s", "write_s",
                         *(f"{p}_s" for p in PARTS)}
    assert loop["turns"] > 0 and loop["read_s"] > 0
    assert 0 < loop["write_s"] < loop["reply_s"]  # the write is part of it
    assert loop["busy_s"] + loop["wait_s"] == pytest.approx(loop["wall_s"])
    assert loop["other_s"] >= 0
    text = get("/metrics")
    series = {line.rpartition(" ")[0]: float(line.rpartition(" ")[2])
              for line in text.splitlines()
              if line.startswith("avdb_loop_")}
    assert set(series) == {
        "avdb_loop_turns_total", "avdb_loop_max_turn_seconds",
        'avdb_loop_seconds_total{state="wait"}',
        'avdb_loop_seconds_total{state="busy"}',
        *(f'avdb_loop_busy_seconds_total{{part="{p}"}}'
          for p in (*PARTS, "other"))}
    # set from the clock at the scrape: at least what /stats just read
    assert series["avdb_loop_turns_total"] >= loop["turns"]
    assert series['avdb_loop_seconds_total{state="busy"}'] >= loop["busy_s"]
    assert series['avdb_loop_busy_seconds_total{part="read"}'] \
        >= loop["read_s"]


def test_sample_zero_records_none_of_them_and_the_loop_still_counts(
        store, monkeypatch):
    monkeypatch.setenv("AVDB_TRACE_SAMPLE", "0")
    srv = start_server(store_dir=store[0])
    try:
        ctx = srv.ctx
        request, want, _tid = _case("point", store[1], ctx.engine)
        assert _raw(srv.server_address[1], request) == want
        request, want, _tid = _case("bulk", store[1], ctx.engine)
        assert _raw(srv.server_address[1], request) == want
        time.sleep(0.05)
        assert ctx.reqtrace.records() == []
        loop = ctx.loop_clock.stats(ctx.batcher.drain_ns)
        assert loop["turns"] > 0 and loop["read_s"] > 0
        assert loop["reply_s"] > 0 and loop["drain_s"] > 0
    finally:
        stop_server(srv)


def test_a_capture_holds_the_loops_waits_and_runs(store, tmp_path):
    """Under a real ``jax.profiler`` capture the loop's thread carries
    ``avdb.loop.wait`` and ``avdb.loop.run``: every drain lies inside a
    run, and a wait for the next request is a span of its own."""
    import jax

    from test_span_clock import _host_events

    srv = start_server(store_dir=store[0])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        request, want, _tid = _case("point", store[1], srv.ctx.engine)
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            # the fourth request only ends the third's wait: the run still
            # open when a capture stops is not in it
            for _ in range(4):
                assert _raw(srv.server_address[1], request) == want
                time.sleep(0.02)  # the loop waits for the next request
        finally:
            jax.profiler.stop_trace()
    finally:
        stop_server(srv)
    events = _host_events(str(tmp_path))
    runs, waits = events["avdb.loop.run"], events["avdb.loop.wait"]
    drains = events["avdb.serve.batch"]
    assert len(drains) == 4
    for start, dur, _stats in drains[:3]:
        assert any(s <= start and start + dur <= s + d
                   for s, d, _a in runs), "a drain outside every run"
    assert all(stats["turns"] >= 1 and "wait_us" in stats
               for _s, _d, stats in runs)
    # the 20 ms pauses are waits of their own, each closing a run
    long_waits = [(s, d) for s, d, _a in waits if d >= 10_000_000]
    assert len(long_waits) >= 3
    for start, dur in long_waits:
        assert any(s <= start and start + dur <= s + d + 1_000
                   and s + d - (start + dur) < 1_000_000
                   for s, d, _a in runs), "a real wait did not end its run"
