"""Batch region join (``POST /regions`` / ``QueryEngine.regions_serve``)
oracle-parity battery.

The contract under test: every per-interval envelope of a batch answer is
**byte-identical** to (a) the corresponding single ``region()`` call and
(b) a brute-force per-row host reference scan that shares only the record
renderer with the engine — across filters, limit, count-only, the
``host_only`` fallback, the forced-device path, and both HTTP front ends.
The interval-index build (including its collision fallback, exercised by
a planted shadowed duplicate) and the tokenization output are pinned
against the scalar bin oracle and the brute counts.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.oracle.binindex import closed_form_bin, closed_form_path
from annotatedvdb_tpu.serve import engine as engine_mod
from annotatedvdb_tpu.serve import (
    DeviceBreaker,
    QueryEngine,
    QueryError,
    SnapshotManager,
    StaticSnapshots,
    render_variant,
)
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.variant_store import RawJson, Segment
from annotatedvdb_tpu.serve.http import DEFAULT_REGION_LIMIT
from annotatedvdb_tpu.types import chromosome_label, encode_allele_array
from conftest import start_server, stop_server

WIDTH = 8
CHROMS = (1, 8, 23)
BASES = ("A", "C", "G", "T")


# ---------------------------------------------------------------------------
# synthetic multi-chromosome store (the test_serve shape: three disjoint
# segments per chromosome plus one OVERLAPPING chr8 segment carrying a
# shadowed duplicate — which forces the interval index down its
# collision-dedup path)


def _rows_for(code: int, base_pos: int, n: int, salt: int):
    rows = []
    for i in range(n):
        pos = base_pos + 977 * i
        k = (i + salt) % 4
        ref = BASES[k]
        alt = BASES[(k + 1) % 4] if i % 3 else ref + "TG"
        rows.append({
            "chrom": code, "pos": pos, "ref": ref, "alt": alt,
            "rs": (1000 * code + i) if i % 2 else -1,
            "cadd": round(0.5 * i + code, 2) if i % 3 == 0 else None,
            "rank": (i % 30) + 1 if i % 4 == 0 else None,
            "vep": i % 5 == 0,
        })
    return rows


def _append(shard, rows, direct: bool = False):
    refs = [r["ref"] for r in rows]
    alts = [r["alt"] for r in rows]
    ref, ref_len = encode_allele_array(refs, WIDTH)
    alt, alt_len = encode_allele_array(alts, WIDTH)
    h = identity_hashes(WIDTH, ref, alt, ref_len, alt_len, refs, alts)
    cols = {
        "pos": np.asarray([r["pos"] for r in rows], np.int32),
        "h": h, "ref_len": ref_len, "alt_len": alt_len,
        "ref_snp": np.asarray([r["rs"] for r in rows], np.int64),
    }
    ann = {
        "cadd_scores": [
            {"CADD_raw_score": r["cadd"] / 10, "CADD_phred": r["cadd"]}
            if r["cadd"] is not None else None for r in rows
        ],
        "adsp_most_severe_consequence": [
            {"conseq": "missense_variant", "rank": r["rank"]}
            if r["rank"] is not None else None for r in rows
        ],
        "vep_output": [
            RawJson(f'{{"input":"{r["chrom"]}:{r["pos"]}","n":{i}}}')
            if r["vep"] else None for i, r in enumerate(rows)
        ],
    }
    long_alleles = [
        (r["ref"], r["alt"])
        if len(r["ref"]) > WIDTH or len(r["alt"]) > WIDTH else None
        for r in rows
    ]
    if direct:
        shard.append_segment(Segment.build(
            cols, ref, alt, annotations=ann, long_alleles=long_alleles
        ))
        shard._starts_cache = None
    else:
        shard.append(cols, ref, alt, annotations=ann,
                     long_alleles=long_alleles)


def _build_store(store_dir: str | None):
    store = VariantStore(width=WIDTH)
    truth: list[dict] = []
    for code in CHROMS:
        shard = store.shard(code)
        for run, base in enumerate((500, 120_000, 2_000_000)):
            rows = _rows_for(code, base, 40, salt=run)
            _append(shard, rows)
            truth.extend(rows)
    shard = store.shard(8)
    dup_src = next(r for r in truth if r["chrom"] == 8 and r["pos"] == 500)
    shadowed = dict(dup_src, cadd=999.0, rank=1, vep=False)
    fresh = {"chrom": 8, "pos": 501, "ref": "T", "alt": "C", "rs": 77,
             "cadd": 33.3, "rank": 2, "vep": False}
    _append(shard, [shadowed, fresh], direct=True)
    truth.append(fresh)
    if store_dir is not None:
        store.save(store_dir)
    return store, truth


# ---------------------------------------------------------------------------
# brute-force reference (plain host Python; shares only the renderer)


def _brute_region_rows(shard, start: int, end: int):
    rows = []
    for si, seg in enumerate(shard.segments):
        for j in range(seg.n):
            p = int(seg.cols["pos"][j])
            if start <= p <= end:
                rows.append((p, int(seg.cols["h"][j]), si, j))
    rows.sort(key=lambda r: (r[0], r[1], r[2]))
    starts = shard._starts()
    kept, seen = [], set()
    for p, h, si, j in rows:
        ident = (p, h) + shard.alleles(int(starts[si]) + j)
        if ident in seen:
            continue
        seen.add(ident)
        kept.append((si, j))
    return kept


def _brute_region_text(store, generation: int, code: int, start: int,
                       end: int, min_cadd=None, max_rank=None, limit=None):
    label = chromosome_label(code)
    level, leaf = closed_form_bin(start, end)
    shard = store.shards.get(code)
    kept = _brute_region_rows(shard, start, end) if shard is not None else []
    if min_cadd is not None or max_rank is not None:
        filtered = []
        for si, j in kept:
            seg = shard.segments[si]

            def field(col, name):
                v = seg.obj[col][j] if seg.obj[col] is not None else None
                return v.get(name) if v is not None else None

            if min_cadd is not None:
                phred = field("cadd_scores", "CADD_phred")
                if phred is None or phred < min_cadd:
                    continue
            if max_rank is not None:
                rank = field("adsp_most_severe_consequence", "rank")
                if rank is None or rank > max_rank:
                    continue
            filtered.append((si, j))
        kept = filtered
    shown = kept if limit is None else kept[:limit]
    starts = shard._starts() if shard is not None else None
    rendered = [
        render_variant(shard, code, int(starts[si]) + j) for si, j in shown
    ]
    return (
        f'{{"region":{json.dumps(f"{label}:{start}-{end}")}'
        f',"bin_level":{level}'
        f',"bin_index":{json.dumps(closed_form_path(label, level, leaf))}'
        f',"count":{len(kept)}'
        f',"returned":{len(rendered)}'
        f',"generation":{generation}'
        ',"variants":[' + ",".join(rendered) + "]}"
    )


#: panel covering every interesting shape: dup/long-allele corners, segment
#: interiors, whole loaded ranges, gaps, an unloaded chromosome, repeats
PANEL = [
    (8, 1, 10_000), (8, 490, 600), (8, 120_000, 160_000),
    (1, 1, 3_000_000), (23, 2_000_000, 2_005_000), (8, 50_000, 60_000),
    (11, 1, 5_000), (1, 500, 500), (8, 490, 600),
    (23, 1, 4_000_000), (1, 2_000_000, 2_038_000),
]


def _specs():
    return [f"{chromosome_label(c)}:{s}-{e}" for c, s, e in PANEL]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    store_dir = str(tmp_path_factory.mktemp("regions_store"))
    _store, truth = _build_store(store_dir)
    manager = SnapshotManager(store_dir)
    engine = QueryEngine(manager, region_cache_size=8)
    return store_dir, truth, manager, engine


# ---------------------------------------------------------------------------
# engine parity


def test_regions_parity_vs_sequential_and_brute(served):
    _dir, _truth, manager, engine = served
    snap = manager.current()
    specs = _specs()
    result = engine.regions_serve(specs)
    assert len(result.pages) == len(specs)
    for (code, start, end), spec, page in zip(PANEL, specs, result.pages):
        body = page.assemble()
        assert body == engine.region(spec), spec
        assert body == _brute_region_text(
            snap.store, snap.generation, code, start, end
        ), spec


def test_regions_filters_and_limit_parity(served):
    _dir, _truth, manager, engine = served
    snap = manager.current()
    specs = _specs()
    for min_cadd, max_rank, limit in (
        (10.0, None, None), (None, 5, None), (4.0, 10, None),
        (None, None, 3), (1.0, 25, 2), (None, None, 0), (5.0, None, 0),
    ):
        result = engine.regions_serve(
            specs, min_cadd=min_cadd, max_conseq_rank=max_rank, limit=limit
        )
        for (code, start, end), spec, page in zip(PANEL, specs,
                                                  result.pages):
            body = page.assemble()
            assert body == engine.region(
                spec, min_cadd=min_cadd, max_conseq_rank=max_rank,
                limit=limit,
            ), (spec, min_cadd, max_rank, limit)
            assert body == _brute_region_text(
                snap.store, snap.generation, code, start, end,
                min_cadd=min_cadd, max_rank=max_rank, limit=limit,
            ), (spec, min_cadd, max_rank, limit)


def test_count_only_never_materializes_rows(served):
    """limit=0 with no filters must answer from span widths alone — no
    (segment, row) pair is ever located."""
    _dir, _truth, _manager, engine = served
    result = engine.regions_serve(_specs(), limit=0)
    for page in result.pages:
        assert page.returned == 0 and page.si.shape == (0,)
    counts = [json.loads(p.assemble())["count"] for p in result.pages]
    assert counts[0] > 0 and counts[6] == 0  # loaded vs unloaded chrom


def test_shadowed_duplicate_stays_hidden_in_batch(served):
    """The chr8 overlapping segment's duplicate identity (cadd=999) must
    stay first-wins-shadowed through the interval index's collision
    build path."""
    _dir, _truth, _manager, engine = served
    result = engine.regions_serve(["8:490-600"])
    recs = json.loads(result.pages[0].assemble())["variants"]
    dup = [r for r in recs if r["position"] == 500]
    assert dup, "expected the pos-500 row in range"
    for r in dup:
        cadd = r["annotations"].get("cadd_scores")
        assert cadd is None or cadd["CADD_phred"] != 999.0


def test_host_only_and_forced_device_byte_identical(served):
    store_dir, _truth, _manager, engine = served
    specs = _specs()
    want = [p.assemble() for p in engine.regions_serve(specs).pages]
    host = engine.regions_serve(specs, host_only=True)
    assert [p.assemble() for p in host.pages] == want
    # forced device: every group goes through the jitted kernel
    dev_engine = QueryEngine(
        SnapshotManager(store_dir), region_cache_size=0,
        regions_device_min=0,
    )
    dev = dev_engine.regions_serve(specs)
    assert [p.assemble() for p in dev.pages] == want
    # the single-region route rides the same machinery
    for spec, body in zip(specs, want):
        assert dev_engine.region(spec) == body
        assert dev_engine.region(spec, host_only=True) == body


def test_device_failure_falls_back_host_and_feeds_breaker(served):
    store_dir, _truth, _manager, engine = served
    specs = _specs()
    want = [p.assemble() for p in engine.regions_serve(specs).pages]
    breaker = DeviceBreaker(cooldown_s=30.0)
    sick = QueryEngine(
        SnapshotManager(store_dir), region_cache_size=0,
        regions_device_min=0, breaker=breaker,
    )
    calls = {"n": 0}

    def boom(index, starts, ends):
        calls["n"] += 1
        raise RuntimeError("injected device kernel failure")

    sick._device_spans = boom
    for _ in range(breaker.failure_threshold):
        got = sick.regions_serve(specs)
        # correct bytes every time: the host twin answered
        assert [p.assemble() for p in got.pages] == want
    # every touched group tripped open; the device path stops being paid
    codes = sorted({c for c, _s, _e in PANEL
                    if sick.snapshots.current().store.shards.get(c)})
    assert all(breaker.state(c) == "open" for c in codes)
    before = calls["n"]
    got = sick.regions_serve(specs)
    assert [p.assemble() for p in got.pages] == want
    assert calls["n"] == before  # open breaker: no device attempt


def test_batch_grammar_and_cap(served):
    store_dir, _truth, _manager, engine = served
    with pytest.raises(QueryError):
        engine.regions_serve(["8:1-100", "not-a-region"])
    with pytest.raises(QueryError):
        engine.regions_serve(["8:9-3"])
    capped = QueryEngine(
        SnapshotManager(store_dir), region_cache_size=0, regions_max=2
    )
    with pytest.raises(QueryError, match="cap"):
        capped.regions_serve(["8:1-10", "8:1-10", "8:1-10"])


def test_tokenize_matches_oracle_and_brute_counts(served):
    _dir, _truth, manager, engine = served
    snap = manager.current()
    specs = _specs()
    result = engine.regions_serve(specs, limit=0, tokenize=True)
    obj = json.loads(result.assemble())
    tok = obj["tokens"]
    assert tok["generation"] == snap.generation
    for i, (code, start, end) in enumerate(PANEL):
        level, leaf = closed_form_bin(start, end)
        label = chromosome_label(code)
        assert tok["bin_level"][i] == level
        assert tok["leaf_bin"][i] == leaf
        assert tok["bin_index"][i] == closed_form_path(label, level, leaf)
        shard = snap.store.shards.get(code)
        brute = len(_brute_region_rows(shard, start, end)) \
            if shard is not None else 0
        assert tok["count"][i] == brute, (i, specs[i])
        if shard is None:
            assert tok["row_lo"][i] == tok["row_hi"][i] == -1
        else:
            assert tok["row_hi"][i] - tok["row_lo"][i] == brute
            # the span indexes the generation's dedup'd position-sorted
            # index: every spanned position sits inside the interval
            index = engine._interval_index(snap, code)
            span = index.pos[tok["row_lo"][i]:tok["row_hi"][i]]
            assert ((span >= start) & (span <= end)).all()


def test_absurd_bounds_answer_identically_on_both_routes(served):
    """A grammatical region whose end bound exceeds int32 must not 500
    on the single route while the batch route answers: both clamp below
    the position sentinel identically (no store position can reach the
    clamp, so the answer — zero rows — is exact)."""
    _dir, _truth, _manager, engine = served
    spec = "8:2147483645-2147483650"
    single = engine.region(spec)
    batch = engine.regions_serve([spec]).pages[0].assemble()
    assert single == batch
    assert json.loads(single)["count"] == 0


def test_index_device_copies_are_byte_bounded(served):
    """Retained device copies of interval indexes live under
    INDEX_DEVICE_BYTES: forcing every group to the device and shrinking
    the ceiling below two copies must leave only the most recent index
    device-resident (answers stay byte-identical off the host arrays)."""
    store_dir, _truth, _manager, _engine = served
    engine = QueryEngine(SnapshotManager(store_dir), region_cache_size=0,
                         regions_device_min=0)
    snap = engine.snapshots.current()
    one = engine._interval_index(snap, 8)
    engine.INDEX_DEVICE_BYTES = one.n * 4  # room for ~one padded copy
    want = [engine.region("8:1-10000"), engine.region("1:1-10000")]
    engine.regions_serve(["8:1-10000"])
    idx8 = engine._interval_index(snap, 8)
    assert idx8._dev_pos is not None
    engine.regions_serve(["1:1-10000"])
    idx1 = engine._interval_index(snap, 1)
    assert idx1._dev_pos is not None
    assert idx8._dev_pos is None  # evicted by the byte ledger
    # the ledger holds only the just-used copy (it always stays, even
    # when its pow2-padded size alone brushes the ceiling)
    assert len(engine._index_device) == 1
    # correctness is unaffected: the host arrays still answer, and the
    # chr8 index transparently re-uploads on its next device call
    got = [engine.regions_serve(["8:1-10000"]).pages[0].assemble(),
           engine.regions_serve(["1:1-10000"]).pages[0].assemble()]
    assert got == want


def test_unfiltered_limit_keeps_full_count_with_lazy_materialization(served):
    """With no filters, only ``limit`` rows are materialized per
    interval but ``count`` must still report the FULL span width (the
    lazy slice must never truncate the count)."""
    _dir, _truth, _manager, engine = served
    result = engine.regions_serve(["8:1-3000000", "1:1-3000000"], limit=3)
    for page in result.pages:
        assert page.returned == 3
        env = json.loads(page.assemble())
        assert env["returned"] == 3
        assert env["count"] > 3  # the whole chromosome matched


def test_concurrent_index_builds_deduplicate(served):
    """After a generation swap every request misses the index cache at
    once: concurrent builders must coalesce onto ONE full-chromosome
    build (a stampede of identical sorts is an N-fold memory spike)."""
    import threading as _threading

    from annotatedvdb_tpu.serve import engine as engine_mod

    store_dir, _truth, _manager, _engine = served
    engine = QueryEngine(SnapshotManager(store_dir), region_cache_size=0)
    snap = engine.snapshots.current()
    builds = {"n": 0}
    real_build = engine_mod.IntervalIndex.build.__func__

    def slow_build(shard, *args):
        builds["n"] += 1
        import time as _time

        _time.sleep(0.05)  # widen the race window
        return real_build(engine_mod.IntervalIndex, shard, *args)

    engine_mod.IntervalIndex.build = slow_build
    try:
        got = []
        threads = [
            _threading.Thread(
                target=lambda: got.append(engine._interval_index(snap, 8))
            )
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        engine_mod.IntervalIndex.build = classmethod(real_build)
    assert builds["n"] == 1, builds["n"]
    assert len(got) == 8 and all(i is got[0] for i in got)


def test_malformed_content_length_is_400_with_the_grammar_body(server_port):
    """A bogus Content-Length on POST /regions answers 400 (the
    fallthrough used to 404 it).  Oracle: the one grammar message,
    ``REGIONS_BODY_ERROR``."""
    import socket

    from annotatedvdb_tpu.serve.http import REGIONS_BODY_ERROR

    raw = (b"POST /regions HTTP/1.1\r\nHost: t\r\n"
           b"Content-Length: abc\r\n\r\n")
    with socket.create_connection(
            ("127.0.0.1", server_port), timeout=15) as s:
        s.sendall(raw)
        s.settimeout(15)
        resp = s.recv(4096)
    head, _, body = resp.partition(b"\r\n\r\n")
    assert b" 400 " in head.split(b"\r\n", 1)[0], head[:80]
    assert json.loads(body) == {"error": REGIONS_BODY_ERROR}


def test_cursor_walk_unaffected_by_interleaved_batches(served):
    """Cursor interplay: a paged single-region walk stays byte-correct
    while /regions panels run between its pages, and the pages
    reassemble the unpaged answer."""
    _dir, _truth, _manager, engine = served
    spec = "8:1-3000000"
    unpaged = json.loads(engine.region(spec))
    rows, cursor, pages = [], "", 0
    while True:
        page = json.loads(engine.region(spec, limit=7, cursor=cursor))
        rows.extend(page["variants"])
        pages += 1
        engine.regions_serve(_specs())  # interleaved batch traffic
        if not page.get("next"):
            break
        cursor = page["next"]
    assert pages > 3
    assert rows == unpaged["variants"]


def test_regions_reflect_snapshot_swap(tmp_path):
    store_dir = str(tmp_path / "swap_store")
    _build_store(store_dir)
    manager = SnapshotManager(store_dir)
    engine = QueryEngine(manager, region_cache_size=0)
    before = json.loads(engine.regions_serve(["8:4999999-5001000"])
                        .pages[0].assemble())
    assert before["count"] == 0

    store = VariantStore.load(store_dir)
    rows = [{"chrom": 8, "pos": 5_000_000 + 11 * i, "ref": "A", "alt": "C",
             "rs": -1, "cadd": None, "rank": None, "vep": False}
            for i in range(25)]
    _append(store.shard(8), rows)
    store.save(store_dir)

    # un-refreshed: the pinned generation (and its index) still answers
    assert json.loads(engine.regions_serve(["8:4999999-5001000"])
                      .pages[0].assemble())["count"] == 0
    assert manager.refresh() is True
    after = json.loads(engine.regions_serve(["8:4999999-5001000"])
                       .pages[0].assemble())
    assert after["count"] == 25
    assert after["generation"] == before["generation"] + 1
    # parity holds on the new generation too
    assert engine.regions_serve(["8:4999999-5001000"]).pages[0].assemble() \
        == engine.region("8:4999999-5001000")


def test_interval_index_cache_bounded_and_generation_keyed(served):
    store_dir, _truth, _manager, _engine = served
    engine = QueryEngine(SnapshotManager(store_dir), region_cache_size=0)
    engine.INDEX_CACHE = 2
    engine.regions_serve(_specs())  # touches 3 loaded chromosomes
    assert len(engine._index_cache) <= 2
    # a re-query rebuilds the evicted index transparently (still correct)
    assert json.loads(engine.regions_serve(["1:1-3000000"])
                      .pages[0].assemble())["count"] > 0


# ---------------------------------------------------------------------------
# HTTP front ends


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


def _post(port: int, path: str, payload) -> tuple[int, str]:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


@pytest.fixture()
def server_port(served):
    """A server that streams any panel above 16 rows."""
    store_dir, truth, _manager, _engine = served
    server = start_server(store_dir=store_dir, stream_threshold=16)
    try:
        yield server.server_address[1]
    finally:
        stop_server(server)


def test_http_regions_byte_parity_with_the_engine(served, server_port):
    """Oracle: ``engine.regions_serve(...).assemble()`` called directly,
    and each interval's single ``GET /region`` body."""
    port = server_port
    engine = served[3]
    specs = _specs()
    payload = {"regions": specs, "minCadd": 4.0, "limit": 6}
    status, body = _post(port, "/regions", payload)
    assert status == 200
    # the server streams this panel (up to 11*6 rows > 16): de-chunked,
    # the bytes are the engine's buffered assembly
    assert body == engine.regions_serve(
        specs, min_cadd=4.0, max_conseq_rank=None, limit=6, tokenize=False,
    ).assemble()
    obj = json.loads(body)
    assert obj["n"] == len(specs)
    for spec, envelope in zip(specs, obj["results"]):
        status, single = _get(
            port, f"/region/{spec}?minCadd=4.0&limit=6"
        )
        assert status == 200
        # byte-identical: the batch envelope is the single body verbatim
        assert json.dumps(envelope, separators=(",", ":")) \
            == json.dumps(json.loads(single), separators=(",", ":"))
        assert single in body


def test_http_regions_count_only_and_tokens(server_port):
    aport = server_port
    st, body = _post(aport, "/regions",
                     {"regions": ["8:1-10000"], "limit": 0,
                      "tokenize": True})
    assert st == 200
    obj = json.loads(body)
    assert obj["results"][0]["returned"] == 0
    assert obj["results"][0]["count"] == obj["tokens"]["count"][0] > 0


def test_http_regions_bad_bodies_are_400(server_port):
    port = server_port
    for bad in ({"regions": "x"}, {"regions": [1]}, {"nope": []},
                {"regions": ["8:9-3"]}, {"regions": ["junk"]},
                {"regions": ["8:1-2"], "limit": "ten"},
                {"regions": ["8:1-2"], "tokenize": "yes"},
                {"regions": ["8:1-2"], "minCadd": True}):
        st, body = _post(port, "/regions", bad)
        assert st == 400, (bad, st, body[:200])
    # the route answers normally afterwards
    st, _ = _post(port, "/regions", {"regions": ["8:1-2"]})
    assert st == 200


def test_http_regions_cap_is_400(served, monkeypatch):
    monkeypatch.setenv("AVDB_SERVE_REGIONS_MAX", "2")
    store_dir, _truth, _manager, _engine = served
    httpd = start_server(store_dir=store_dir)
    try:
        port = httpd.server_address[1]
        st, body = _post(port, "/regions",
                         {"regions": ["8:1-2", "8:1-2", "8:1-2"]})
        assert st == 400 and "cap" in body
        st, _ = _post(port, "/regions", {"regions": ["8:1-2", "8:3-4"]})
        assert st == 200
    finally:
        stop_server(httpd)


def test_http_regions_fault_fails_one_request_and_metrics(server_port):
    from annotatedvdb_tpu.utils import faults

    tport = server_port
    try:
        faults.reset("serve.regions:1:raise")
        st, body = _post(tport, "/regions", {"regions": ["8:1-100"]})
        assert st == 500 and "InjectedFault" in body
        st, _ = _post(tport, "/regions", {"regions": ["8:1-100"]})
        assert st == 200  # exactly one batch failed; serving continues
    finally:
        faults.reset("")
    st, metrics = _get(tport, "/metrics")
    assert st == 200
    assert 'avdb_query_requests_total{kind="regions"}' in metrics
    assert 'avdb_query_errors_total{kind="regions"}' in metrics


def test_http_regions_streaming_parity_with_buffered(served):
    """A panel whose total rows exceed the stream threshold must
    de-chunk to exactly the buffered bytes.  Oracle: the engine's own
    ``regions_serve(...).assemble()``, and the same server's answer with
    streaming out of reach."""
    store_dir, _truth, _manager, engine = served
    streamed = start_server(store_dir=store_dir, stream_threshold=4)
    buffered = start_server(store_dir=store_dir, stream_threshold=1 << 30)
    try:
        payload = {"regions": _specs()}
        st_s, body_s = _post(streamed.server_address[1], "/regions", payload)
        st_b, body_b = _post(buffered.server_address[1], "/regions", payload)
        assert st_s == st_b == 200
        assert body_s == body_b == engine.regions_serve(
            _specs(), min_cadd=None, max_conseq_rank=None,
            limit=DEFAULT_REGION_LIMIT, tokenize=False,
        ).assemble()
        assert json.loads(body_s)["n"] == len(PANEL)
    finally:
        stop_server(streamed)
        stop_server(buffered)


# ---------------------------------------------------------------------------
# block-wise render: a body larger than one block, and its laziness

#: whole chromosomes in turn (120-123 rows each), twelve times over: ~4,400
#: rows, nine blocks of ``REGION_RENDER_BLOCK`` and every block a mix of
#: shards and segments
BIG_PANEL = [f"{chromosome_label(c)}:1-3000000" for c in CHROMS] * 12 \
    + ["11:1-5000", "8:490-600"]


def test_http_panel_of_many_blocks_streamed_buffered_and_single_reads(
        served):
    """De-chunked, a streamed panel of several render blocks is the
    buffered body, and each envelope is its own single ``region()``."""
    store_dir, _truth, _manager, engine = served
    streamed = start_server(store_dir=store_dir, stream_threshold=4)
    buffered = start_server(store_dir=store_dir, stream_threshold=1 << 30)
    try:
        payload = {"regions": BIG_PANEL, "limit": 1000}
        st_s, body_s = _post(streamed.server_address[1], "/regions", payload)
        st_b, body_b = _post(buffered.server_address[1], "/regions", payload)
        assert st_s == st_b == 200
        singles = [engine.region(spec, limit=1000) for spec in BIG_PANEL]
        assert body_s == body_b == \
            f'{{"n":{len(BIG_PANEL)},"results":[' + ",".join(singles) + "]}"
        rows = sum(json.loads(t)["returned"] for t in singles)
        assert rows > 4 * engine_mod.REGION_RENDER_BLOCK
        for server, streams in ((streamed, 1), (buffered, 0)):
            tally = server.ctx.engine.region_panels
            assert tally["rows_batched"] + tally["rows_scalar"] \
                == tally["rows_rendered"] == rows
            assert tally["streamed"] == streams
    finally:
        stop_server(streamed)
        stop_server(buffered)


def test_first_envelope_renders_one_block_and_no_more(served, monkeypatch):
    """``RegionsResult.rows()`` is lazy by the block: taking one envelope
    renders the pages gathered up to ``REGION_RENDER_BLOCK`` rows — one
    columnar call a touched (shard, segment) — and nothing after them."""
    _dir, _truth, _manager, engine = served
    calls = []
    real = engine_mod._render_segment_rows

    def counted(seg, j, label, width, clock):
        calls.append((label, int(j.shape[0])))
        return real(seg, j, label, width, clock)

    monkeypatch.setattr(engine_mod, "_render_segment_rows", counted)
    result = engine.regions_serve(BIG_PANEL, limit=1000)
    assert not calls  # prepared, nothing rendered
    envelopes = result.rows()
    first = next(envelopes)
    assert first == engine.region(BIG_PANEL[0], limit=1000)
    sizes = [page.returned for page in result.pages]
    held, gathered = 0, 0
    while held < engine_mod.REGION_RENDER_BLOCK:
        held += sizes[gathered]
        gathered += 1
    assert gathered < len(sizes) // 2  # a block is a small part of it
    assert sum(n for _label, n in calls) == held
    # one call a (shard, segment) the block touches: each chromosome has
    # three segments (chromosome 8 a fourth, overlapping one)
    assert len(calls) == 3 + 4 + 3
    assert {label for label, _n in calls} == {"1", "8", "X"}
    rest = list(envelopes)
    assert len(rest) == len(BIG_PANEL) - 1
    assert sum(n for _label, n in calls) == result.returned
    assert (result.clock.batch_rows, result.clock.scalar_rows) \
        == (result.returned, 0)
