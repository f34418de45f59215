"""The program's own stages on the profiler's clock.

One span, several sinks: every stage the loaders and the serving stack time
(``StageTimer``, the pipeline's queue waits, ``reqtrace.stage``, the
engine's lookup sub-stages) is also a ``jax.profiler.TraceAnnotation`` on
the thread that does the work, so a ``jax.profiler`` capture holds the
program's stages on host lines of the same ``.xplane.pb`` as the device's
operations — while the run record, the stage histograms, the span ring and
``/stats`` keep reading what they read.  Spans keep what a span is (name,
start, end, parent, the request's id); nothing records a bare duration.
"""

from __future__ import annotations

import glob
import json
import os
import urllib.request

import pytest

from annotatedvdb_tpu.obs import reqtrace
from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.obs.reqtrace import LOOKUP_STAGES, TraceRecorder
from annotatedvdb_tpu.serve import SnapshotManager
from annotatedvdb_tpu.serve.engine import QueryEngine
from annotatedvdb_tpu.utils import profiling
from conftest import BatcherOnLoop, start_server, stop_server
from test_serve import _build_store, _vid

LOAD_STAGES = ("ingest", "dispatch", "annotate", "lookup", "gather",
               "egress", "build", "mapping", "append", "persist", "maintain")


def _write_vcf(path, rows: int) -> None:
    lines = ["##fileformat=VCFv4.2",
             "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO"]
    for i in range(rows):
        lines.append(f"1\t{1000 + i * 3}\trs{i}\tA\tG\t.\t.\t.")
    path.write_text("\n".join(lines) + "\n")


def _host_events(trace_dir: str) -> dict:
    """{event name: [(start_ns, duration_ns, stats)]} over the host plane's
    lines of the one capture under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    out: dict = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def profiled_load(tmp_path_factory):
    """One small overlapped load through the real CLI entry point under
    ``--profile``: (host events of the capture, the run record)."""
    from annotatedvdb_tpu.cli import load_vcf

    tmp = tmp_path_factory.mktemp("profiled")
    vcf = tmp / "in.vcf"
    _write_vcf(vcf, 3000)
    trace_dir = str(tmp / "prof")
    rc = load_vcf.main(["--fileName", str(vcf), "--storeDir",
                        str(tmp / "vdb"), "--commit", "--commitAfter", "256",
                        "--profile", trace_dir,
                        "--logFilePath", str(tmp / "load.log")])
    assert rc == 0
    runs = [json.loads(line) for line in
            (tmp / "vdb" / "ledger.jsonl").read_text().splitlines()]
    runs = [r for r in runs if r.get("type") == "run"]
    assert len(runs) == 1 and runs[0]["status"] == "completed"
    return _host_events(trace_dir), runs[0]


@pytest.mark.parametrize("stage", LOAD_STAGES)
def test_load_stage_is_on_the_host_plane_and_in_the_run_record(
        profiled_load, stage):
    events, record = profiled_load
    assert stage in record["stages"], sorted(record["stages"])
    spans = events.get(f"avdb.load.{stage}")
    assert spans, sorted(n for n in events if n.startswith("avdb."))
    # the run record's busy seconds and the capture's spans are the same
    # intervals, read by two clocks: they agree to clock-read jitter
    busy = record["stages"][stage]["seconds"]
    traced = sum(d for _s, d, _a in spans) / 1e9
    assert traced == pytest.approx(busy, rel=0.05,
                                   abs=0.02 + 0.005 * len(spans))
    wall = events["avdb.load"]
    assert len(wall) == 1
    lo, hi = wall[0][0], wall[0][0] + wall[0][1]
    assert all(lo <= s and s + d <= hi for s, d, _a in spans)


def test_profile_capture_has_waits_startup_and_no_python_tracer(
        profiled_load):
    events, record = profiled_load
    assert events["avdb.load"][0][1] / 1e9 == pytest.approx(
        record["wall_seconds"], rel=0.05, abs=0.005)
    # the final drain always waits on the writer at least once
    waits = [n for n in events if n.startswith("avdb.wait.")]
    assert "avdb.wait.store-writer" in waits, waits
    assert all(a.get("side") in ("producer", "consumer")
               for n in waits for _s, _d, a in events[n])
    stalls = record["queue_stalls"]
    assert {"ingest", "dispatch", "store-writer"} <= set(stalls)
    traced = sum(d for _s, d, _a in events["avdb.wait.store-writer"]) / 1e9
    assert traced == pytest.approx(
        stalls["store-writer"]["producer_block_s"], rel=0.05, abs=0.01)
    # items ride along as the annotation's arguments
    assert any(a.get("items", 0) > 0
               for _s, _d, a in events["avdb.load.build"])
    # the Python tracer is off: a Python-heavy load would be millions of
    # call events (named "$file:line function"); what the runtime itself
    # records grows with the devices it drives (51,403 events on the
    # suite's 8 CPU devices), so the limit sits an order above that
    assert not [n for n in events if n.startswith("$")]
    assert sum(len(v) for v in events.values()) < 500_000
    # start-up phases: recorded seconds, cumulative for the process
    startup = record["execution"]["startup"]
    assert "programs" in startup and startup["programs"] > 0
    assert all(v >= 0 for v in startup.values())


def test_device_trace_starts_with_the_python_tracer_off(monkeypatch,
                                                        tmp_path):
    import jax

    seen = {}

    class FakeTrace:
        def __init__(self, log_dir, **kw):
            seen["dir"], seen["options"] = log_dir, kw["profiler_options"]

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "trace", FakeTrace)
    with profiling.device_trace(None):  # no directory: nothing starts
        pass
    assert not seen
    with profiling.device_trace(str(tmp_path)):
        pass
    assert seen["dir"] == str(tmp_path)
    assert seen["options"].python_tracer_level == 0
    assert seen["options"].host_tracer_level == 2


def test_startup_phase_accumulates_seconds():
    before = profiling.STARTUP_SECONDS.get("t-phase", 0.0)
    for _ in range(2):
        with profiling.startup_phase("t-phase"):
            pass
    try:
        assert profiling.STARTUP_SECONDS["t-phase"] >= before
    finally:
        profiling.STARTUP_SECONDS.pop("t-phase", None)


# ---------------------------------------------------------------------------
# serving: the lookup stage split where the work happens


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    store_dir = str(tmp_path_factory.mktemp("span_store"))
    return store_dir, _build_store(store_dir)


def _engine(store_dir):
    registry = MetricsRegistry()
    engine = QueryEngine(SnapshotManager(store_dir), registry=registry)
    return engine, registry


def _counts(registry) -> dict:
    """{stage: (count, sum)} of the lookup sub-stage histograms."""
    snap = registry.snapshot()["avdb_stage_seconds"]
    return {e["labels"]["stage"]: (e["count"], e["sum"]) for e in snap
            if e["labels"]["stage"] in LOOKUP_STAGES}


def _ids(truth, n=60, absent=5):
    ids = [_vid(r) for r in truth[:n]]
    return ids + [f"1:{900_000_000 + i}:A:C" for i in range(absent)]


def test_lookup_substages_nest_in_device_and_sum_to_it(store):
    store_dir, truth = store
    engine, registry = _engine(store_dir)
    rec = TraceRecorder(registry, sample=1.0)
    ids = _ids(truth)
    for _ in range(3):  # the last call is warm: no first-touch residue
        trace = rec.begin("req-1", "bulk")
        with reqtrace.stage(trace, "device"):
            results = engine.lookup_many(ids)
    found = sum(r is not None for r in results)
    assert found == 60
    (device,) = [s for s in trace.spans if s[3] is None]
    assert device[0] == "device" and trace.stages == [
        ("device", (device[2] - device[1]) / 1e9)]
    subs = [s for s in trace.spans if s[3] is not None]
    assert {s[0] for s in subs} == set(LOOKUP_STAGES)
    for name, start, end, parent in subs:
        assert parent == "device"  # the span that caused it
        assert device[1] <= start <= end <= device[2]  # lies inside it
    # sub-spans are true intervals, in order, never overlapping
    ordered = sorted(subs, key=lambda s: s[1])
    assert all(a[2] <= b[1] for a, b in zip(ordered, ordered[1:]))
    covered = sum(end - start for _n, start, end, _p in subs)
    assert covered <= device[2] - device[1]
    # ... and account for the stage but a fixed residue (snapshot, loop
    # set-up): generous here, where a call is microseconds — on the chip
    # cell's 100 ms request the four sum to the stage within 3 %
    assert covered >= 0.5 * (device[2] - device[1])
    rec.finish(trace, 200)
    ring = rec.records()[-1]
    assert ring[0] == "req-1"  # the request's id rides on the record
    assert [s for s in ring[6] if s[3] == "device"] == subs


@pytest.mark.parametrize("path", ["direct", "parsed", "batcher"])
def test_each_lookup_histogram_observes_once_per_call(store, path):
    store_dir, truth = store
    engine, registry = _engine(store_dir)
    ids = _ids(truth, n=12, absent=2)
    before = _counts(registry)
    assert set(before) == set(LOOKUP_STAGES)
    if path == "direct":
        engine.lookup_many(ids)
        calls = 1
    elif path == "parsed":
        from annotatedvdb_tpu.serve.engine import parse_variant_id

        engine.lookup_many(ids, parsed=[parse_variant_id(s) for s in ids])
        calls = 1
    else:
        batcher = BatcherOnLoop(engine, max_batch=8, max_wait_s=0.0)
        rec = TraceRecorder(sample=1.0)
        try:
            trace = rec.begin("via-batcher", "point")
            assert batcher.submit(ids[0], trace=trace) is not None
            calls = batcher.batcher.drain_stats()["batches"]
        finally:
            batcher.close()
        assert calls == 1
        # the microbatch's one engine call: its device stage and sub-spans
        # are adopted by the co-batched request, with the queue wait
        assert [n for n, _s in trace.stages] == ["queue", "device"]
        subs = {s[0] for s in trace.spans if s[3] == "device"}
        assert subs == set(LOOKUP_STAGES) - {"lookup.parse"}
    after = _counts(registry)
    for stage in LOOKUP_STAGES:
        assert after[stage][0] - before[stage][0] == calls, stage
    if path != "direct":  # ids parsed at submit: the call spent nothing
        assert after["lookup.parse"][1] == before["lookup.parse"][1]
    else:
        assert after["lookup.parse"][1] > before["lookup.parse"][1]


def test_an_empty_call_observes_nothing(store):
    store_dir, _truth = store
    engine, registry = _engine(store_dir)
    assert engine.lookup_many([]) == []
    assert all(c == 0 for c, _s in _counts(registry).values())


def test_render_cache_counts_add_up_to_the_found_ids(store):
    store_dir, truth = store
    engine, registry = _engine(store_dir)
    ids = _ids(truth, n=50, absent=7)
    first = engine.lookup_many(ids)
    found = sum(r is not None for r in first)
    assert found == 50
    assert (engine.render_cache_hits, engine.render_cache_misses) == (0, 50)
    assert engine.lookup_many(ids) == first  # now served from the cache
    assert (engine.render_cache_hits, engine.render_cache_misses) == (50, 50)
    text = registry.render_prometheus()
    assert "avdb_render_cache_hits_total 50" in text
    assert "avdb_render_cache_misses_total 50" in text


def test_front_end_bulk_request_splits_its_lookup_stage(store):
    store_dir, truth = store
    server = start_server(store_dir=store_dir)
    port, ctx = server.server_address[1], server.ctx
    try:
        def call(path, payload=None, tid=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=None if payload is None
                else json.dumps(payload).encode(),
                headers={"X-Request-Id": tid} if tid else {})
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.read().decode()

        before = json.loads(call("/stats"))["render_cache"]
        counts0 = _counts(ctx.registry)
        ids = _ids(truth, n=30, absent=3)
        body = json.loads(call("/variants", {"ids": ids}, tid="split-me"))
        assert body["found"] == 30
        after = json.loads(call("/stats"))["render_cache"]
        assert (after["hits"] + after["misses"]
                - before["hits"] - before["misses"]) == body["found"]
        counts1 = _counts(ctx.registry)
        for stage in LOOKUP_STAGES:
            assert counts1[stage][0] - counts0[stage][0] == 1, stage
        (rec,) = [r for r in ctx.reqtrace.records() if r[0] == "split-me"]
        stages = dict(rec[5])
        assert {"admission", "device", "render"} <= set(stages)
        subs = [s for s in rec[6] if s[3] == "device"]
        assert {s[0] for s in subs} == set(LOOKUP_STAGES)
        (device,) = [s for s in rec[6] if s[0] == "device"]
        assert all(device[1] <= s[1] <= s[2] <= device[2] for s in subs)
    finally:
        stop_server(server)


# ---------------------------------------------------------------------------
# the span record's shape, and the picture drawn from it


def test_chrome_events_use_recorded_starts_and_name_parents():
    rec = TraceRecorder(sample=1.0)
    trace = rec.begin("drawn", "bulk")
    t0 = trace.t0_ns
    trace.record("admission", t0 - 2_000_000, t0)  # began before the trace
    trace.record("device", t0 + 5_000_000, t0 + 9_000_000)
    trace.record("lookup.rows", t0 + 6_000_000, t0 + 8_500_000,
                 parent="device")
    rec.finish(trace, 200)
    events = {e["name"]: e for e in rec.chrome_events(base_ns=t0)
              if e["ph"] == "X"}
    # a span opened 5 ms into a request is drawn 5 ms in, not at its start
    assert events["device"]["ts"] == pytest.approx(5000.0)
    assert events["device"]["dur"] == pytest.approx(4000.0)
    assert events["lookup.rows"]["ts"] == pytest.approx(6000.0)
    assert events["lookup.rows"]["args"]["parent"] == "device"
    assert events["lookup.rows"]["cat"] == "span"
    assert events["admission"]["ts"] == pytest.approx(-2000.0)
    assert events["device"]["cat"] == "stage"
    assert "parent" not in events["device"]["args"]
    assert all(e["args"]["trace_id"] == "drawn" for e in events.values())
    # nesting is by containment of recorded intervals
    dev, rows = events["device"], events["lookup.rows"]
    assert dev["ts"] <= rows["ts"]
    assert rows["ts"] + rows["dur"] <= dev["ts"] + dev["dur"]


def test_stage_records_a_span_annotates_and_scopes_subspans(tmp_path):
    import jax

    rec = TraceRecorder(sample=1.0)
    trace = rec.begin("abc123", "bulk")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        with reqtrace.stage(trace, "device"):
            reqtrace.record_active("inner", 1, 2)
        with reqtrace.stage(None, "render"):  # unsampled: transparent
            reqtrace.record_active("nowhere", 1, 2)
        with pytest.raises(KeyError):
            with reqtrace.stage(trace, "render"):
                raise KeyError("a stage that failed still ran")
        with reqtrace.background_span("memtable.flush"):
            pass
    assert [s[0] for s in trace.spans] == ["inner", "device", "render"]
    assert trace.spans[0] == ("inner", 1, 2, "device")
    assert [n for n, _s in trace.stages] == ["device", "render"]
    events = _host_events(str(tmp_path))
    (device,) = events["avdb.serve.device"]
    assert device[2] == {"trace_id": "abc123", "kind": "bulk"}
    assert len(events["avdb.serve.render"]) == 1  # the sampled one only
    assert events["avdb.serve.background"][0][2] == {
        "span": "memtable.flush"}


def test_slow_request_log_names_the_slow_part_of_a_stage():
    lines: list = []
    rec = TraceRecorder(slow_ms=1.0, sample=1.0, log=lines.append)
    trace = rec.begin("slow-1", "bulk")
    trace.t0_ns -= 40_000_000  # 40 ms ago
    t0 = trace.t0_ns
    trace.record("device", t0, t0 + 39_000_000)
    trace.record("lookup.probe", t0, t0 + 30_000_000, parent="device")
    trace.record("lookup.rows", t0 + 30_000_000, t0 + 34_000_000,
                 parent="device")
    trace.record("lookup.rows", t0 + 34_000_000, t0 + 39_000_000,
                 parent="device")
    rec.finish(trace, 200)
    (line,) = lines
    assert "trace=slow-1 kind=bulk status=200" in line
    assert "device=39.00ms" in line  # the fields it always had
    assert "spans=3 [lookup.probe=30.00ms lookup.rows=9.00ms]" in line


def test_shared_stage_is_adopted_by_every_sampled_trace():
    rec = TraceRecorder(sample=1.0)
    a, b = rec.begin("a", "point"), rec.begin("b", "point")
    with reqtrace.shared_stage([a, None, b], "device"):
        reqtrace.record_active("lookup.probe", 5, 9)
    assert a.spans == b.spans
    assert [s[0] for s in a.spans] == ["lookup.probe", "device"]
    assert a.spans[0][3] == "device" and a.spans[1][3] is None
    with reqtrace.shared_stage([None], "device"):  # nothing sampled
        reqtrace.record_active("lost", 1, 2)
    assert len(a.spans) == 2


# ---------------------------------------------------------------------------
# stable names on the device clock


def _kernel_cases():
    import numpy as np

    n, w = 64, 8
    u8 = np.zeros((n, w), np.uint8)
    i32 = np.ones(n, np.int32)
    u32 = np.zeros(n, np.uint32)
    flag = np.zeros(n, bool)
    pos = np.arange(1, n + 1, dtype=np.int32)
    seg = (pos, u32, u8, u8, i32, i32)
    packed = np.zeros((n, (w + 1) // 2), np.uint8)
    return [
        ("ops.hashing", "allele_hash_jit", "avdb.hash",
         (u8, u8, i32, i32), {}),
        ("ops.annotate", "annotate_kernel_jit", "avdb.annotate",
         (pos, u8, u8, i32, i32), {}),
        ("ops.binindex", "bin_index_kernel_jit", "avdb.bin_index",
         (pos, pos), {}),
        ("ops.intervals", "bits_spans_kernel_jit", "avdb.bits_spans",
         (pos, pos[:8], pos[:8]), {}),
        ("ops.intervals", "bits_spans_stacked_jit",
         "avdb.bits_spans_stacked",
         (np.stack([pos, pos]), np.stack([pos[:8]] * 2),
          np.stack([pos[:8]] * 2)), {}),
        ("ops.pack", "pack_outputs_jit", "avdb.pack_outputs",
         (u32, flag, i32, i32, flag, flag), {}),
        ("ops.pack", "inflate_alleles_jit", "avdb.inflate_alleles",
         (packed, packed, w), {}),
        ("ops.pack", "pack_vep_outputs_jit", "avdb.pack_vep_outputs",
         (u32, i32, flag), {}),
        ("ops.dedup", "mark_batch_duplicates_jit", "avdb.dedup",
         (pos, u32, u8, u8, i32, i32), {}),
        ("ops.dedup", "lookup_in_sorted_jit", "avdb.probe",
         seg + seg, {}),
        ("ops.dedup", "lookup_in_sorted_packed_jit", "avdb.probe",
         seg + (np.zeros(n * (16 + 2 * w), np.uint8),), {}),
        ("ops.annotate_pallas", "annotate_bin_pallas", "avdb_annotate_bin",
         (pos, u8, u8, i32, i32), {"block_n": 128, "interpret": True}),
    ]


#: program names the benchmark's breakdown (``device_ops``) sums by
PROGRAM_NAMES = {"allele_hash_jit": "jit_allele_hash",
                 "inflate_alleles_jit": "jit_inflate_alleles",
                 "lookup_in_sorted_jit": "jit_lookup_in_sorted",
                 "lookup_in_sorted_packed_jit": "jit_lookup_in_sorted_packed"}


@pytest.mark.parametrize("case", _kernel_cases(),
                         ids=lambda c: f"{c[0]}.{c[1]}")
def test_kernel_scope_is_in_the_lowered_text(case):
    import importlib

    module, entry, scope, args, kwargs = case
    fn = getattr(importlib.import_module(f"annotatedvdb_tpu.{module}"),
                 entry)
    lowered = fn.lower(*args, **kwargs)
    assert scope in lowered.as_text(debug_info=True)
    if entry in PROGRAM_NAMES:  # the scope renames nothing
        assert f"module @{PROGRAM_NAMES[entry]} " in lowered.as_text()
