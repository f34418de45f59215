"""Span-ring lifecycle + crash flight recorder unit battery.

The request tracer's ring must wrap, survive concurrent writers without
a lock, and export Chrome-trace events; the flight recorder's mmap'd
ring must round-trip, wrap, tolerate torn slots on harvest (the
ledger's torn-tail discipline at slot granularity), survive a simulated
process death (reopen + decode), and absorb injected write failures —
observability never takes down what it observes.
"""

from __future__ import annotations

import json
import os
import threading

import pytest

from annotatedvdb_tpu.obs import flight as flight_mod
from annotatedvdb_tpu.obs import reqtrace
from annotatedvdb_tpu.obs.flight import (
    HEADER,
    SLOT,
    FlightRecorder,
    decode_ring,
    harvest,
    load_harvest,
)
from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.obs.reqtrace import TraceRecorder
from annotatedvdb_tpu.utils import faults


@pytest.fixture(autouse=True)
def _unarmed():
    faults.reset("")
    yield
    faults.reset("")


# ---------------------------------------------------------------------------
# span ring


def test_ring_records_stages_and_wraps():
    rec = TraceRecorder(slots=4, sample=1.0)
    for i in range(10):
        t = rec.begin(f"id{i}", "point")
        t.record("queue", t.t0_ns, t.t0_ns + 1_000 * i)
        t.record("device", t.t0_ns + 1_000 * i, t.t0_ns + 1_000 * i + 2_000_000)
        rec.finish(t, 200)
    records = rec.records()
    assert len(records) == 4  # wrapped: only the last four survive
    ids = {r[0] for r in records}
    assert ids == {"id6", "id7", "id8", "id9"}
    trace_id, kind, status, t0, total, stages, spans = records[-1]
    assert kind == "point" and status == 200 and total >= 0
    assert dict(stages)["device"] == 0.002
    # the spans keep what a span is: name, start, end, parent
    assert spans[-1] == ("device", t0 + 9_000, t0 + 2_009_000, None)


def test_ring_concurrent_writers_never_tear():
    rec = TraceRecorder(slots=64, sample=1.0)
    errors: list = []

    def writer(wid: int):
        try:
            for i in range(200):
                t = rec.begin(f"w{wid}-{i}", "bulk")
                t.record("device", t.t0_ns, t.t0_ns + 1_000_000)
                rec.finish(t, 200)
        except Exception as err:  # pragma: no cover
            errors.append(err)

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    records = rec.records()
    assert len(records) == 64
    # every surviving slot is a complete immutable record, never a hybrid
    for r in records:
        assert len(r) == 7 and r[1] == "bulk" and r[2] == 200
        assert dict(r[5]) == {"device": 0.001}


def test_sampling_zero_disarms_and_fraction_samples():
    rec = TraceRecorder(sample=0.0)
    assert rec.begin("x", "point") is None
    rec.finish(None, 200)  # a disarmed finish is a no-op, never a crash
    assert rec.records() == []
    frac = TraceRecorder(sample=0.5)
    got = sum(1 for i in range(400)
              if frac.begin(str(i), "point") is not None)
    assert 100 < got < 300  # seeded RNG: comfortably inside


def test_stage_histograms_and_slow_log():
    reg = MetricsRegistry()
    lines: list[str] = []
    rec = TraceRecorder(registry=reg, slow_ms=5.0, sample=1.0,
                        log=lines.append)
    t = rec.begin("fast", "point")
    rec.finish(t, 200)
    t = rec.begin("slowone", "region")
    t.t0_ns -= int(20e6)  # backdate 20ms: over the 5ms threshold
    t.since("device", t.t0_ns / 1e9)
    rec.finish(t, 200)
    slow = [ln for ln in lines if "slow request" in ln]
    assert len(slow) == 1
    assert "trace=slowone" in slow[0] and "device=" in slow[0]
    assert "loop_max_turn" not in slow[0]  # no serving loop behind it
    text = reg.render_prometheus()
    assert 'avdb_stage_seconds_count{stage="device"} 1' in text
    assert 'avdb_stage_seconds_count{stage="total"} 2' in text
    assert "avdb_trace_slow_requests_total 1" in text


def test_slow_log_says_whether_the_loop_was_parked():
    """With the server's loop clock beside it, a slow request's line says
    how long the loop's longest turn was and when it ended — under this
    request, or long before it."""
    from annotatedvdb_tpu.obs.loopclock import LoopClock

    lines: list[str] = []
    rec = TraceRecorder(slow_ms=5.0, sample=1.0, log=lines.append)
    rec.loop_clock = clock = LoopClock()
    clock.max_turn_ns = int(18e6)
    clock.max_turn_end_ns = clock.t_resume = clock.now()
    t = rec.begin("parked", "bulk")
    t.t0_ns -= int(20e6)
    t.record("wake", t.t0_ns, t.t0_ns + int(18e6))
    rec.finish(t, 200)
    (line,) = lines
    assert "trace=parked" in line and "wake=18.00ms" in line
    assert "loop_max_turn=18.00ms ended=0.0" in line


def test_span_cap_bounds_subspans():
    rec = TraceRecorder(sample=1.0)
    t = rec.begin("panel", "regions")
    with reqtrace.activate(t):
        for i in range(200):
            reqtrace.record_active(f"regions.chr{i}", t.t0_ns, t.t0_ns + 1000)
    assert len(t.spans) == t.MAX_SPANS
    # the cap is on sub-spans: a stage recorded after it is never dropped
    t.record("render", t.t0_ns, t.t0_ns + 5)
    assert t.stages == [("render", 5e-9)]


def test_chrome_events_merge_with_tracer_timebase():
    from annotatedvdb_tpu.obs.trace import Tracer

    tracer = Tracer(process_name="t")
    rec = TraceRecorder(sample=1.0)
    t = rec.begin("abc", "point")
    t.record("queue", t.t0_ns, t.t0_ns + 1_000_000)
    rec.finish(t, 200)
    with tracer.span("serve.batch", n=3):
        pass
    events = rec.chrome_events(base_ns=tracer._t0) + tracer.events()
    # both sources parse as one trace-event list
    doc = json.loads(json.dumps(
        {"traceEvents": events, "displayTimeUnit": "ms"}
    ))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "point" in names and "serve.batch" in names
    req = [e for e in doc["traceEvents"]
           if e.get("name") == "point" and e.get("ph") == "X"]
    assert req and req[0]["args"]["trace_id"] == "abc"
    stage = [e for e in doc["traceEvents"] if e.get("name") == "queue"]
    assert stage and stage[0]["dur"] == pytest.approx(1000.0)


def test_active_trace_attaches_engine_subspans():
    rec = TraceRecorder(sample=1.0)
    t = rec.begin("x", "regions")
    reqtrace.record_active("orphan", 0, 1)  # no active trace: no-op
    with reqtrace.activate(t):
        reqtrace.record_active("regions.chr8", 10, 3_000_010)
    reqtrace.record_active("late", 0, 1)  # deactivated again
    assert t.spans == [("regions.chr8", 10, 3_000_010, "device")]
    with reqtrace.activate(None):  # None trace: transparent
        reqtrace.record_active("nope", 0, 1)
    assert t.spans == [("regions.chr8", 10, 3_000_010, "device")]
    assert t.stages == []  # a sub-span is never a stage


def test_background_sink_records_span_and_event():
    rec = TraceRecorder(sample=1.0)
    events: list = []
    reqtrace.set_background_sink(
        rec.background, lambda name, detail: events.append((name, detail))
    )
    try:
        with reqtrace.background_span("memtable.flush", groups=2):
            pass
        reqtrace.lifecycle_event("wal", "rotated")
    finally:
        reqtrace.set_background_sink(None, None)
    records = [r for r in rec.records() if r[1] == "background"]
    assert len(records) == 1
    name, start_ns, end_ns, parent = records[0][6][-1]
    assert name == "memtable.flush" and parent == "background"
    assert records[0][3] == start_ns <= end_ns
    assert events == [("wal", "rotated")]
    # cleared sink: everything is a no-op again
    with reqtrace.background_span("x"):
        pass
    reqtrace.lifecycle_event("y", "z")
    assert len([r for r in rec.records() if r[1] == "background"]) == 1


# ---------------------------------------------------------------------------
# flight recorder


def test_flight_roundtrip_requests_and_events(tmp_path):
    path = str(tmp_path / "w0.ring")
    fr = FlightRecorder(path, slots=16)
    fr.request("abc", "point", 200, 0.0042,
               [("queue", 0.001), ("device", 0.002)])
    fr.event("brownout", "level 0->1 (limit)")
    fr.close()
    decoded = decode_ring(path)
    assert decoded["slots"] == 16
    req, ev = decoded["events"]
    assert req["type"] == "request" and req["trace"] == "abc"
    assert req["kind"] == "point" and req["status"] == 200
    assert req["ms"] == pytest.approx(4.2)
    assert req["stages"]["device"] == pytest.approx(2.0)
    assert ev["type"] == "event" and ev["name"] == "brownout"
    assert "level 0->1" in ev["detail"]


def test_flight_ring_wraps_keeping_newest(tmp_path):
    path = str(tmp_path / "w0.ring")
    fr = FlightRecorder(path, slots=8, event_slots=8)
    for i in range(20):
        fr.event("tick", f"n={i}")
    fr.close()
    events = decode_ring(path)["events"]
    assert len(events) == 8
    assert [e["detail"] for e in events] == [
        f"n={i}" for i in range(12, 20)
    ]


def test_flight_request_flood_cannot_wash_out_lifecycle_events(tmp_path):
    """The incident timeline survives serving QPS: lifecycle events live
    in their own ring region, so thousands of request summaries wrap the
    request ring without touching the breaker trip that explains them —
    the full-chaos harvest found the single-ring version losing exactly
    this evidence."""
    path = str(tmp_path / "w0.ring")
    fr = FlightRecorder(path, slots=8, event_slots=16)
    fr.event("breaker", "group 8 tripped open")
    for i in range(5000):  # the flood
        fr.request(f"t{i}", "point", 200, 0.001, [])
    fr.close()
    events = decode_ring(path)["events"]
    reqs = [e for e in events if e["type"] == "request"]
    life = [e for e in events if e["type"] == "event"]
    assert len(reqs) == 8  # request ring wrapped as designed
    assert [e["name"] for e in life] == ["breaker"]  # still aboard


def test_flight_survives_simulated_kill_and_tolerates_torn_slot(tmp_path):
    path = str(tmp_path / "w0.ring")
    fr = FlightRecorder(path, slots=8)
    for i in range(5):
        fr.request(f"t{i}", "point", 200, 0.001, [])
    fr.flush()  # the serving tick's cadence; summaries are mmap-durable
    # no close(): a SIGKILL never runs destructors — the mmap'd bytes
    # are already in the page cache, a fresh reader must decode them
    events = decode_ring(path)["events"]
    assert [e["trace"] for e in events] == [f"t{i}" for i in range(5)]
    # tear one slot (flip a payload byte mid-record): the CRC drops
    # exactly that slot and keeps the rest.  The payload field starts
    # after seq/t/kind/status/crc/plen/trace = 62 bytes into the slot.
    with open(path, "r+b") as f:
        off = HEADER.size + 2 * SLOT.size + 64
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0xFF]))
    survivors = decode_ring(path)["events"]
    assert [e["trace"] for e in survivors] == ["t0", "t1", "t3", "t4"]
    fr.close()


def test_flight_write_failure_is_absorbed(tmp_path):
    lines: list[str] = []
    fr = FlightRecorder(str(tmp_path / "w0.ring"), slots=4,
                        log=lines.append)
    faults.reset("obs.flight:1:raise")
    fr.event("breaker", "boom window")  # injected failure: absorbed
    fr.event("breaker", "after")        # recording continues
    fr.close()
    assert fr.errors == 1
    assert any("ring write failed" in ln for ln in lines)
    events = decode_ring(str(tmp_path / "w0.ring"))["events"]
    assert [e["detail"] for e in events] == ["after"]


def test_harvest_writes_jsonl_and_loads_back(tmp_path):
    store = tmp_path / "store"
    store.mkdir()
    ring = flight_mod.ring_path(str(store), 1)
    fr = FlightRecorder(ring, slots=8)
    fr.request("abc", "upsert", 200, 0.01, [("wal_fsync", 0.004)])
    fr.event("maintain", "pass starting")
    fr.close()
    out = harvest(ring, str(store), 1, "died rc=-9", log=lambda m: None)
    assert out is not None and out.endswith("-w1.jsonl")
    data = load_harvest(out)
    assert data["meta"]["reason"] == "died rc=-9"
    assert data["meta"]["worker"] == 1
    kinds = [(e["type"], e.get("kind") or e.get("name"))
             for e in data["events"]]
    assert kinds == [("request", "upsert"), ("event", "maintain")]
    boxes = flight_mod.list_blackboxes(str(store))
    assert boxes["harvested"] == [out]
    assert boxes["rings"] == [ring]


def test_harvest_of_missing_or_empty_ring_is_none(tmp_path):
    store = tmp_path / "store"
    store.mkdir()
    assert harvest(str(store / "nope.ring"), str(store), 0, "died") is None
    ring = flight_mod.ring_path(str(store), 0)
    FlightRecorder(ring, slots=4).close()  # created, never written
    assert harvest(ring, str(store), 0, "died") is None
    assert flight_mod.list_blackboxes(str(store))["harvested"] == []


def test_decode_rejects_foreign_files(tmp_path):
    p = tmp_path / "junk.ring"
    p.write_bytes(b"not a ring at all" * 10)
    with pytest.raises(ValueError):
        decode_ring(str(p))
    short = tmp_path / "short.ring"
    short.write_bytes(b"ab")
    with pytest.raises(ValueError):
        decode_ring(str(short))


def test_respawn_truncates_the_previous_incarnation(tmp_path):
    path = str(tmp_path / "w0.ring")
    fr = FlightRecorder(path, slots=8)
    fr.event("old", "before death")
    fr.close()
    fr2 = FlightRecorder(path, slots=8)  # the respawned worker's fresh box
    fr2.event("new", "after respawn")
    fr2.close()
    events = decode_ring(path)["events"]
    assert [e["name"] for e in events] == ["new"]


def test_oversized_event_detail_truncates_to_valid_json(tmp_path):
    """A long (or escape-heavy) lifecycle detail SHRINKS until the
    encoded payload fits — byte-slicing encoded JSON used to cut
    mid-string, and the CRC-valid-but-unparseable slot was silently
    dropped on decode (losing exactly the events the box exists for)."""
    path = str(tmp_path / "w0.ring")
    fr = FlightRecorder(path, slots=4, event_slots=8)
    fr.event("breaker", "x" * 500)
    fr.event("brownout", "é" * 80)  # escapes inflate 6x when encoded
    fr.close()
    events = decode_ring(path)["events"]
    assert [e["name"] for e in events] == ["breaker", "brownout"]
    assert events[0]["detail"].startswith("xxx")
    assert events[1]["detail"].startswith("é")


def test_concurrent_flush_and_events_never_collide_slots(tmp_path):
    """Two threads flushing (the threaded front end's inline time-gated
    flushes can race) plus write-through events must never interleave a
    seq reservation and overwrite each other's slot."""
    path = str(tmp_path / "w0.ring")
    fr = FlightRecorder(path, slots=256, event_slots=64)
    for i in range(200):
        fr.request(f"t{i}", "point", 200, 0.001, [])

    def drain():
        fr.flush(limit=10)

    threads = [threading.Thread(target=drain) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    fr.close()
    reqs = [e for e in decode_ring(path)["events"]
            if e["type"] == "request"]
    # every drained record landed in its own slot: seqs are unique and
    # the full set survived (80 capped-flush + the close() drain = 200)
    seqs = [e["seq"] for e in reqs]
    assert len(seqs) == len(set(seqs)) == 200


def test_oversized_payload_drops_stages_not_the_headline(tmp_path):
    path = str(tmp_path / "w0.ring")
    fr = FlightRecorder(path, slots=4)
    stages = [(f"stage_with_a_long_name_{i}", 0.001) for i in range(30)]
    fr.request("big", "regions", 200, 1.5, stages)
    fr.close()
    ev = decode_ring(path)["events"][0]
    assert ev["trace"] == "big" and ev["ms"] == pytest.approx(1500.0)
    assert "stages" not in ev  # trimmed to fit the fixed slot


def test_a_full_slot_drops_the_loops_stages_first_and_says_so(tmp_path):
    """The three stages of a request's life on the loop are the newest
    names in the summary: where the fixed slot has no room for them it
    keeps the others, and says what it cut."""
    from annotatedvdb_tpu.obs.reqtrace import LOOP_STAGES, STAGES

    path = str(tmp_path / "w0.ring")
    fr = FlightRecorder(path, slots=4)
    names = [s for s in STAGES if s not in ("background", "total")]
    fr.request("roomy", "point", 200, 0.012,
               [(s, 0.0012) for s in names])
    fr.request("tight", "regions", 200, 12.345678,
               [(s, 12.345678) for s in names])
    fr.close()
    roomy, tight = decode_ring(path)["events"]
    assert list(roomy["stages"]) == names and "stages_cut" not in roomy
    assert list(tight["stages"]) == [s for s in names
                                     if s not in LOOP_STAGES]
    assert tight["stages_cut"] == list(LOOP_STAGES)
    assert tight["ms"] == pytest.approx(12345.678)


# ---------------------------------------------------------------------------
# fleet metric-snapshot merging (the ?fleet=1 math)


def test_merge_snapshots_sums_counters_maxes_gauges():
    from annotatedvdb_tpu.obs.metrics import merge_snapshots, render_snapshot

    def snap(n):
        reg = MetricsRegistry()
        reg.counter("avdb_query_requests_total", labels={"kind": "point"}) \
            .inc(n)
        reg.gauge("avdb_serve_queue_depth").set(n)
        h = reg.histogram("avdb_query_seconds", (0.1, 1.0),
                          labels={"kind": "point"})
        h.observe(0.05)
        h.observe(0.5 * n)
        return reg.snapshot()

    merged = merge_snapshots([snap(2), snap(5)])
    by = {(name, tuple(sorted(e["labels"].items()))): e
          for name, entries in merged.items() for e in entries}
    c = by[("avdb_query_requests_total", (("kind", "point"),))]
    assert c["value"] == 7  # counters sum
    g = by[("avdb_serve_queue_depth", ())]
    assert g["value"] == 5  # gauges take the max
    h = by[("avdb_query_seconds", (("kind", "point"),))]
    assert h["count"] == 4 and h["counts"][0] == 2  # bucket-wise sum
    text = render_snapshot(merged)
    assert 'avdb_query_requests_total{kind="point"} 7' in text
    assert 'avdb_query_seconds_bucket{kind="point",le="+Inf"} 4' in text
    assert "# TYPE avdb_query_seconds histogram" in text


def test_merge_snapshots_skips_mismatched_edges():
    from annotatedvdb_tpu.obs.metrics import merge_snapshots

    a = MetricsRegistry()
    a.histogram("avdb_query_seconds", (0.1, 1.0),
                labels={"kind": "point"}).observe(0.05)
    b = MetricsRegistry()
    b.histogram("avdb_query_seconds", (0.2, 2.0),
                labels={"kind": "point"}).observe(0.05)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    entry = merged["avdb_query_seconds"][0]
    assert entry["count"] == 1  # the mismatched sibling was dropped
    assert entry["edges"] == [0.1, 1.0]
