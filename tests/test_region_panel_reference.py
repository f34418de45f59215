"""A BITS target panel (``POST /regions`` of a few hundred short targets)
against the plain reference, and the guarantee that a server which says its
interval indexes are ready builds, uploads and compiles nothing more.

The reference is ``annotatedvdb_tpu/oracle/regions.py``: a linear scan per
interval, no index, no search, no kernel, no cache; it shares only the
record renderer with the engine.  These tests hold that

- the engine's panel — device path forced, host twin, default routing with
  groups on both sides of ``regions_device_min`` — and the server's
  buffered and streamed bodies equal the reference byte for byte, on a
  seeded store with multi-allelic ties, a shadowed duplicate in a newer
  segment, empty intervals and intervals that end exactly on a position;
- one function decides the span program's query shapes, every group size
  from 1 to 4,096 maps to a warmed one, and after the warm step panels of
  drifting group sizes compile no program, build no index and upload none;
- ``/stats`` ``region_index`` reads ready only after build, upload and
  warm, and a segment is reported resident only then;
- ``/stats`` ``region_panels`` and the ``regions.*`` stage histograms add
  up, one observation a panel, buffered or streamed.

The device path is forced the way ``test_point_cobatch`` forces it: the
latch a CPU backend turns off is set, the residency manager uploads at any
segment size.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.ops import intervals as interval_ops
from annotatedvdb_tpu.oracle import regions as reference
from annotatedvdb_tpu.oracle.binindex import closed_form_bin
from annotatedvdb_tpu.serve import (
    QueryEngine,
    ResidencyManager,
    SnapshotManager,
    render_variant,
)
from annotatedvdb_tpu.store import VariantStore, variant_store
from annotatedvdb_tpu.store.variant_store import Segment
from annotatedvdb_tpu.types import chromosome_code, encode_allele_array
from annotatedvdb_tpu.utils import runtime
from conftest import start_server, stop_server

WIDTH = 8
CHROMS = ("1", "2", "22")
SEED = 2147483779
BASES = ("A", "C", "G", "T")
#: stored positions lie in 10,000..60,000: ~9 rows a 150-base target
SPAN = (10_000, 60_000)
ROWS_OLD, ROWS_NEW = 2400, 600
LIMIT = 25


# ---------------------------------------------------------------------------
# a seeded store: per chromosome an older segment and a newer, overlapping
# one that repeats some of the older one's identities (shadowed) and adds
# rows at positions the older one holds too (ties across segments)


def _segment_rows(rng, n: int) -> list:
    """(pos, ref, alt, rs) x n: a fifth of the positions hold two or three
    alleles (multi-allelic ties, ordered by the identity hash)."""
    rows = {}
    while len(rows) < n:
        pos = int(rng.integers(*SPAN))
        ref = BASES[int(rng.integers(4))]
        for _ in range(1 if rng.random() > 0.2 else int(rng.integers(2, 4))):
            alt = BASES[int(rng.integers(4))]
            alt = alt if alt != ref else ref + "TG"[: int(rng.integers(1, 3))]
            rows.setdefault((pos, ref, alt), int(rng.integers(1, 10**6)))
    return [(p, r, a, rs) for (p, r, a), rs in list(rows.items())[:n]]


def _append(shard, rows: list, direct: bool) -> None:
    refs = [r[1] for r in rows]
    alts = [r[2] for r in rows]
    ref, ref_len = encode_allele_array(refs, WIDTH)
    alt, alt_len = encode_allele_array(alts, WIDTH)
    cols = {
        "pos": np.asarray([r[0] for r in rows], np.int32),
        "h": identity_hashes(WIDTH, ref, alt, ref_len, alt_len, refs, alts),
        "ref_len": ref_len, "alt_len": alt_len,
        "ref_snp": np.asarray([r[3] for r in rows], np.int64),
    }
    if direct:  # no membership check: a repeated identity stays, shadowed
        shard.append_segment(Segment.build(cols, ref, alt))
        shard._starts_cache = None
    else:
        shard.append(cols, ref, alt)


@pytest.fixture(scope="module")
def store_dir(tmp_path_factory):
    patch = pytest.MonkeyPatch()
    patch.setattr(variant_store, "_DEVICE_LOOKUP_OK", True)
    runtime._watch_compiles()
    rng = np.random.default_rng(SEED)
    store = VariantStore(width=WIDTH)
    for label in CHROMS:
        shard = store.shard(chromosome_code(label))
        old = _segment_rows(rng, ROWS_OLD)
        _append(shard, old, direct=False)
        repeats = [(p, r, a, rs + 10**6) for p, r, a, rs in old[:40]]
        tied = [(p, r, r + "GG", rs) for p, r, _a, rs in old[40:80]]
        _append(shard, repeats + tied + _segment_rows(rng, ROWS_NEW),
                direct=True)
    path = str(tmp_path_factory.mktemp("panel_store"))
    store.save(path)
    yield path
    patch.undo()


@pytest.fixture(scope="module")
def truth(store_dir):
    """(store, generation) read back the way a server reads it."""
    snap = SnapshotManager(store_dir).current()
    assert all(len(s.segments) == 2 for s in snap.store.shards.values())
    return snap.store, snap.generation


def _panel(rng, n: int, weights=(1, 1, 1)) -> list:
    """``n`` targets of 30-150 bases over the three chromosomes, drawn in
    the given proportions and kept in the order drawn."""
    p = np.asarray(weights, float) / sum(weights)
    specs = []
    for ci in rng.choice(len(CHROMS), size=n, p=p).tolist():
        start = int(rng.integers(SPAN[0] - 200, SPAN[1] + 200))
        specs.append(f"{CHROMS[ci]}:{start}-"
                     f"{start + int(rng.integers(30, 151)) - 1}")
    return specs


def _edge_specs(store) -> list:
    """Targets that end, and start, exactly on a stored position; an empty
    one; one on a chromosome that holds nothing; a repeat."""
    pos = np.sort(store.shards[chromosome_code("2")].segments[0].cols["pos"])
    p = int(pos[len(pos) // 2])
    gap = next(int(a) + 1 for a, b in zip(pos[:-1], pos[1:]) if b - a > 2)
    return [f"2:{p - 40}-{p}", f"2:{p}-{p + 40}", f"2:{p}-{p}",
            f"2:{gap}-{gap}", "11:100-900", f"2:{p - 40}-{p}",
            "22:1-9999", f"1:{SPAN[0]}-{SPAN[0] + 149}"]


def _reference_body(truth, specs, limit) -> str:
    store, generation = truth
    return reference.region_panel(store, generation, specs, limit,
                                  render_variant)


# ---------------------------------------------------------------------------
# the reference itself, on what a reader can check by eye


def test_reference_counts_a_row_at_the_intervals_end_and_not_past_it(truth):
    store, generation = truth
    inside, at_start, lone, empty = _edge_specs(store)[:4]
    p = int(lone.split(":")[1].split("-")[0])
    for spec in (inside, at_start, lone):
        doc = json.loads(reference.region_envelope(
            store, generation, spec, None, render_variant))
        assert doc["count"] == doc["returned"] >= 1
        assert p in [v["position"] for v in doc["variants"]]
        start, end = map(int, spec.split(":")[1].split("-"))
        assert all(start <= v["position"] <= end for v in doc["variants"])
        level, _leaf = closed_form_bin(start, end)
        assert doc["bin_level"] == level
    doc = json.loads(reference.region_envelope(
        store, generation, empty, None, render_variant))
    assert doc["count"] == 0 and doc["variants"] == []


def test_reference_answers_a_repeated_identity_from_the_older_segment(truth):
    store, generation = truth
    code = chromosome_code("1")
    shard = store.shards[code]
    ids = [json.loads(render_variant(shard, code, gid))["metaseq_id"]
           for gid in range(shard.n)]
    shadowed = len(ids) - len(set(ids))
    assert shadowed >= 40  # the newer segment repeats forty on purpose
    doc = json.loads(reference.region_envelope(
        store, generation, f"1:{SPAN[0]}-{SPAN[1]}", None, render_variant))
    assert doc["count"] == len(set(ids))
    got = [v["metaseq_id"] for v in doc["variants"]]
    assert len(got) == len(set(got)) == doc["count"]
    assert [v["position"] for v in doc["variants"]] == sorted(
        v["position"] for v in doc["variants"])
    # the older copy's rs number answers, never the newer one's
    assert all(int(v["ref_snp"][2:]) < 10**6 for v in doc["variants"])


# ---------------------------------------------------------------------------
# the engine against the reference


ROUTES = {
    "device": dict(regions_device_min=1),
    "default": dict(),          # groups of 32 and more on the device
    "host": dict(regions_device_min=10**9),
}


@pytest.mark.parametrize("limit", [LIMIT, 3, 0, None])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_engine_panel_equals_the_reference(store_dir, truth, route, limit):
    store, _generation = truth
    rng = np.random.default_rng([SEED, 1])
    # 384 targets, a tenth of them on chromosome 22: its group falls under
    # the default minimum in some panels and over it in others
    specs = _panel(rng, 384, weights=(5, 4, 1)) + _edge_specs(store)
    order = rng.permutation(len(specs))
    specs = [specs[i] for i in order]
    engine = QueryEngine(SnapshotManager(store_dir), region_cache_size=0,
                         **ROUTES[route])
    result = engine.regions_serve(specs, limit=limit)
    want = _reference_body(truth, specs, limit)
    assert result.assemble() == want
    groups = len({s.split(":")[0] for s in specs} - {"11"})
    tally = engine.region_panels
    assert tally["device_groups"] + tally["host_groups"] == groups
    if route == "device":
        assert tally["host_groups"] == 0
    if route == "host":
        assert tally["device_groups"] == 0
    # and one interval at a time, through the single-region read
    for spec in specs[:24]:
        assert engine.region(spec, limit=limit) == reference.region_envelope(
            *truth, spec, limit, render_variant)


def test_default_routing_puts_groups_on_both_sides_of_the_minimum(
        store_dir, truth):
    rng = np.random.default_rng([SEED, 2])
    engine = QueryEngine(SnapshotManager(store_dir), region_cache_size=0)
    assert engine.regions_device_min == 32
    for _ in range(6):
        specs = _panel(rng, 200, weights=(12, 7, 1))
        assert engine.regions_serve(specs, limit=LIMIT).assemble() \
            == _reference_body(truth, specs, LIMIT)
    tally = engine.region_panels
    assert tally["device_groups"] >= 12 and tally["host_groups"] >= 3
    assert tally["transfers"] \
        == interval_ops.SPAN_TRANSFERS * tally["device_groups"]


# ---------------------------------------------------------------------------
# one place decides the span program's shapes


def test_every_group_size_maps_to_a_warmed_shape():
    floor = interval_ops.SPAN_QUERY_FLOOR
    warmed = interval_ops.span_query_shapes(1, 4096)
    assert warmed == [32, 64, 128, 256, 512, 1024, 2048, 4096]
    assert interval_ops.span_query_shapes(32, 4096) == warmed
    for n in range(1, 4097):
        cap = interval_ops.span_query_capacity(n)
        assert cap in warmed and cap >= max(n, floor)
        assert cap == floor or cap < 2 * n  # the next power of two
    assert interval_ops.span_query_shapes(200, 300) == [256, 512]
    assert interval_ops.span_query_shapes(5000, 4096) == []


@pytest.mark.parametrize("n", [1, 31, 33, 127, 129, 384, 4096])
def test_the_span_search_runs_at_the_decided_shape_and_agrees(n):
    rng = np.random.default_rng([SEED, n])
    pos = np.sort(rng.integers(1, 10**6, size=5000)).astype(np.int32)
    starts = rng.integers(1, 10**6, size=n)
    ends = starts + rng.integers(0, 150, size=n)
    shapes = []
    real = interval_ops.bits_spans_kernel_jit

    def watching(pos_p, s, e):
        shapes.append((s.shape[0], e.shape[0]))
        return real(pos_p, s, e)

    patch = pytest.MonkeyPatch()
    patch.setattr(interval_ops, "bits_spans_kernel_jit", watching)
    try:
        got = interval_ops.interval_spans(pos, starts, ends)
    finally:
        patch.undo()
    cap = interval_ops.span_query_capacity(n)
    assert shapes == [(cap, cap)]
    for a, b in zip(got, interval_ops.interval_spans_host(pos, starts, ends)):
        assert a.shape == (n,) and np.array_equal(a, b)


# ---------------------------------------------------------------------------
# ready before it is said to be ready


def _manager(**kw) -> ResidencyManager:
    kw.setdefault("async_upload", False)
    return ResidencyManager(budget_bytes=1 << 30, upload=True, min_rows=1,
                            plan_interval_s=0.0, **kw)


def _ready(engine) -> bool:
    index = engine.region_index_stats()
    return index["candidates"] > 0 \
        and index["device"] == index["candidates"]


def test_after_the_warm_step_no_panel_compiles_builds_or_uploads(
        store_dir, truth):
    engine = QueryEngine(SnapshotManager(store_dir), region_cache_size=0,
                         residency=_manager(), regions_device_min=1)
    rng = np.random.default_rng([SEED, 3])
    assert engine.region_index_stats() == {
        "candidates": 0, "built": 0, "device": 0, "builds": 0, "uploads": 0,
        "base_builds": 0, "flush_builds": 0, "request_builds": 0,
        "delta_builds": 0, "delta_rows": 0}
    # the first panel's windows heat the segments; the uploader (inline
    # here) uploads them and warms their chromosomes' indexes
    first = _panel(rng, 96)
    assert engine.regions_serve(first, limit=LIMIT).assemble() \
        == _reference_body(truth, first, LIMIT)
    assert engine.region_index_stats() == {
        "candidates": 3, "built": 3, "device": 3, "builds": 3, "uploads": 3,
        "base_builds": 3, "flush_builds": 0, "request_builds": 3,
        "delta_builds": 0, "delta_rows": 0}
    residency = engine.residency.stats()
    assert residency["resident"] == residency["candidates"] == 6
    programs = runtime.compile_summary()["programs"]
    # group sizes from 1 to the cap, drifting: every shape is warm already
    for n, weights in ((3, (1, 1, 1)), (40, (1, 1, 1)), (97, (30, 2, 1)),
                       (384, (1, 1, 1)), (400, (1, 0, 0)),
                       (1500, (5, 3, 1)), (4096, (1, 0, 0)),
                       (4096, (1, 1, 1)), (129, (0, 1, 0)), (1, (0, 0, 1))):
        specs = _panel(rng, n, weights)
        body = engine.regions_serve(specs, limit=2).assemble()
        if n <= 400:
            assert body == _reference_body(truth, specs, 2)
    assert runtime.compile_summary()["programs"] == programs
    index = engine.region_index_stats()
    assert (index["builds"], index["uploads"], index["device"]) == (3, 3, 3)
    assert engine.region_panels["host_groups"] == 0


def test_the_index_reads_ready_only_after_build_upload_and_warm(
        store_dir, monkeypatch):
    """The uploader's thread does the work; while a chromosome's span
    programs are still being run, neither its index nor its segments are
    reported ready."""
    gate, entered = threading.Event(), threading.Event()
    real = interval_ops.warm_spans

    def held(pos_padded, nq_min, nq_max):
        entered.set()
        assert gate.wait(60)
        return real(pos_padded, nq_min, nq_max)

    monkeypatch.setattr(interval_ops, "warm_spans", held)
    engine = QueryEngine(SnapshotManager(store_dir), region_cache_size=0,
                         residency=_manager(async_upload=True))
    specs = _panel(np.random.default_rng([SEED, 4]), 96)
    try:
        want = engine.regions_serve(specs, limit=LIMIT).assemble()
        assert entered.wait(60)
        index = engine.region_index_stats()
        assert index["candidates"] == 3 and index["device"] == 0
        assert not _ready(engine)
        # the segments probe on the device already, but none is reported
        assert engine.residency.stats()["resident"] == 0
        # a panel meanwhile is answered all the same
        assert engine.regions_serve(specs, limit=LIMIT).assemble() == want
    finally:
        gate.set()
    deadline = time.monotonic() + 60
    while not _ready(engine) and time.monotonic() < deadline:
        time.sleep(0.02)
    index = engine.region_index_stats()
    assert index["built"] == index["device"] == index["candidates"] == 3
    assert index["builds"] == 3 and index["uploads"] == 3
    residency = engine.residency.stats()
    assert residency["resident"] == residency["candidates"] == 6
    assert engine.regions_serve(specs, limit=LIMIT).assemble() == want


# ---------------------------------------------------------------------------
# the server: buffered and streamed bodies, counters, stage histograms


def _post(port: int, specs: list, limit) -> tuple:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("POST", "/regions",
                     body=json.dumps({"regions": specs, "limit": limit}),
                     headers={"Content-Type": "application/json"})
        response = conn.getresponse()
        body = response.read()  # de-chunks a streamed body
        return response.status, dict(response.getheaders()), body.decode()
    finally:
        conn.close()


def _get(port: int, path: str) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        return conn.getresponse().read().decode()
    finally:
        conn.close()


def _stage_counts(port: int) -> dict:
    out = {}
    for line in _get(port, "/metrics").splitlines():
        if line.startswith("avdb_stage_seconds_count{") \
                and 'stage="regions.' in line:
            stage = line.split('stage="')[1].split('"')[0]
            out[stage] = int(float(line.rsplit(" ", 1)[1]))
    return out


@pytest.mark.parametrize("route", ["device", "host"])
@pytest.mark.parametrize("body_form", ["buffered", "streamed"])
def test_server_bodies_equal_the_reference(store_dir, truth, body_form,
                                           route):
    store, _generation = truth
    server = start_server(
        store_dir=store_dir, residency=_manager(), region_cache_size=0,
        registry=MetricsRegistry(),
        stream_threshold=40 if body_form == "streamed" else 10**9)
    engine = server.ctx.engine
    engine.regions_device_min = ROUTES[route]["regions_device_min"]
    port = server.server_address[1]
    rng = np.random.default_rng([SEED, 5])
    try:
        panels = [_panel(rng, 384) + _edge_specs(store) for _ in range(3)]
        for specs in panels:
            status, headers, body = _post(port, specs, LIMIT)
            assert status == 200
            assert (headers.get("Transfer-Encoding") == "chunked") \
                == (body_form == "streamed")
            assert body == _reference_body(truth, specs, LIMIT)
        stats = json.loads(_get(port, "/stats"))
        tally = stats["region_panels"]
        docs = [json.loads(_reference_body(truth, s, LIMIT)) for s in panels]
        assert tally["panels"] == 3
        assert tally["intervals"] == sum(len(s) for s in panels)
        assert tally["rows_rendered"] == sum(
            e["returned"] for d in docs for e in d["results"])
        assert tally["streamed"] == (3 if body_form == "streamed" else 0)
        groups = 3 * len(CHROMS)  # chromosome 11 holds nothing: no group
        if route == "device":
            assert (tally["device_groups"], tally["host_groups"]) \
                == (groups, 0)
            assert tally["transfers"] \
                == interval_ops.SPAN_TRANSFERS * groups
            assert stats["region_index"]["device"] \
                == stats["region_index"]["candidates"] == 3
        else:
            assert (tally["device_groups"], tally["host_groups"],
                    tally["transfers"]) == (0, groups, 0)
        # one observation a panel of each stage, whichever thread rendered
        assert _stage_counts(port) == {
            "regions.parse": 3, "regions.spans": 3, "regions.rows": 3,
            "regions.render": 3}
    finally:
        stop_server(server)


def test_a_small_panel_is_buffered_and_a_bad_one_counts_nothing(store_dir,
                                                                truth):
    server = start_server(store_dir=store_dir, region_cache_size=0,
                          registry=MetricsRegistry())
    port = server.server_address[1]
    try:
        specs = _panel(np.random.default_rng([SEED, 6]), 12)
        status, headers, body = _post(port, specs, LIMIT)
        assert status == 200 and "Transfer-Encoding" not in headers
        assert body == _reference_body(truth, specs, LIMIT)
        status, _headers, _body = _post(port, specs + ["1:9-3"], LIMIT)
        assert status == 400
        stats = json.loads(_get(port, "/stats"))
        assert stats["region_panels"]["panels"] == 1
        assert stats["region_panels"]["intervals"] == 12
        groups = len({s.split(":")[0] for s in specs})
        assert stats["region_index"] == {
            "candidates": 0, "built": 0, "device": 0,
            "builds": groups, "uploads": 0, "base_builds": groups,
            "flush_builds": 0, "request_builds": groups,
            "delta_builds": 0, "delta_rows": 0}
        assert _stage_counts(port)["regions.parse"] == 1
    finally:
        stop_server(server)
