"""avdb-serve test battery: the query engine against a brute-force
reference scan, the batcher under real concurrency, snapshot isolation
against a committing loader, the HTTP front end end-to-end (including 429
admission), and the read-only store-open contract.

Parity discipline: the reference scan walks every row of every segment in
plain host Python (no hashing, no searchsorted, no bin pruning) and shares
only the final record renderer with the engine — so any divergence in the
engine's hash/probe/slice/dedup machinery shows up as a byte diff, while a
sample of records is additionally field-checked against the original input
data to pin the renderer itself.  Region envelopes are rebuilt in-test from
the scalar bin ORACLE (``oracle.binindex.closed_form_bin``), so the
device-kernel bin answer is cross-checked per query too.
"""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.oracle.binindex import closed_form_bin, closed_form_path
from annotatedvdb_tpu.serve import (
    QueryEngine,
    QueryError,
    QueueFull,
    SnapshotManager,
    StaticSnapshots,
    parse_region,
    parse_variant_id,
    render_variant,
)
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.variant_store import RawJson, Segment
from annotatedvdb_tpu.types import chromosome_label, encode_allele_array
from conftest import BatcherOnLoop, start_server, stop_server

WIDTH = 8
CHROMS = (1, 8, 23)  # "1", "8", "X"
BASES = ("A", "C", "G", "T")


# ---------------------------------------------------------------------------
# synthetic multi-chromosome store


def _rows_for(code: int, base_pos: int, n: int, salt: int):
    """Deterministic row set: SNVs + indels, sparse annotations (CADD on
    every 3rd row, ranked consequence on every 4th, RawJson vep_output on
    every 5th), positions spread across several level-13 bins."""
    rows = []
    for i in range(n):
        pos = base_pos + 977 * i
        k = (i + salt) % 4
        ref = BASES[k]
        alt = BASES[(k + 1) % 4] if i % 3 else ref + "TG"  # every 3rd: indel
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
    if direct:  # overlapping segment: no cascade merge, stays separate
        shard.append_segment(Segment.build(
            cols, ref, alt, annotations=ann, long_alleles=long_alleles
        ))
        shard._starts_cache = None
    else:
        shard.append(cols, ref, alt, annotations=ann,
                     long_alleles=long_alleles)


def _build_store(store_dir: str):
    """Three chromosomes, three disjoint segments each, plus one OVERLAPPING
    extra segment on chr8 carrying a shadowed duplicate identity (the
    store's first-wins policy must hide it) and an over-width long-allele
    row (the host-string hash override path).  Returns the truth rows that
    must be visible (shadowed duplicates excluded)."""
    store = VariantStore(width=WIDTH)
    truth: list[dict] = []
    for code in CHROMS:
        shard = store.shard(code)
        for run, base in enumerate((500, 120_000, 2_000_000)):
            rows = _rows_for(code, base, 40, salt=run)
            _append(shard, rows)
            truth.extend(rows)
    # chr8 extra segment: one duplicate of an existing row (different
    # annotations — must stay shadowed), one fresh in-range row, one
    # over-width long-allele row
    shard = store.shard(8)
    dup_src = next(r for r in truth if r["chrom"] == 8 and r["pos"] == 500)
    shadowed = dict(dup_src, cadd=999.0, rank=1, vep=False)
    fresh = {"chrom": 8, "pos": 501, "ref": "T", "alt": "C", "rs": 77,
             "cadd": 33.3, "rank": 2, "vep": False}
    long_row = {"chrom": 8, "pos": 600, "ref": "A" * 20, "alt": "G",
                "rs": -1, "cadd": None, "rank": None, "vep": False}
    _append(shard, [shadowed, fresh, long_row], direct=True)
    truth.extend([fresh, long_row])
    store.save(store_dir)
    return truth


def _vid(row: dict) -> str:
    return (f"{chromosome_label(row['chrom'])}:{row['pos']}"
            f":{row['ref']}:{row['alt']}")


# ---------------------------------------------------------------------------
# brute-force reference scan (plain host Python, shares only the renderer)


def _brute_find(shard, pos: int, ref: str, alt: str):
    """First-wins global id by walking every row of every segment."""
    starts = shard._starts()
    for si, seg in enumerate(shard.segments):
        for j in range(seg.n):
            if int(seg.cols["pos"][j]) != pos:
                continue
            gid = int(starts[si]) + j
            if shard.alleles(gid) == (ref, alt):
                return gid
    return None


def _brute_region_rows(shard, start: int, end: int):
    """(segment, local) rows in engine order: (pos, hash, segment age),
    duplicates first-wins."""
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
    """The full region response rebuilt from the brute scan + the scalar
    bin ORACLE (cross-checking the device kernel's bin answer)."""
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


# ---------------------------------------------------------------------------
# fixtures


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(store_dir, truth rows, SnapshotManager, QueryEngine)."""
    store_dir = str(tmp_path_factory.mktemp("serve_store"))
    truth = _build_store(store_dir)
    manager = SnapshotManager(store_dir)
    engine = QueryEngine(manager, region_cache_size=8)
    return store_dir, truth, manager, engine


# ---------------------------------------------------------------------------
# grammar


def test_query_grammar():
    assert parse_variant_id("chr8:100:a:g") == (8, 100, "A", "G")
    assert parse_variant_id("X:5:AT:A") == (23, 5, "AT", "A")
    # the store's own primary keys round-trip (trailing rs field tolerated)
    assert parse_variant_id("8:100:A:G:rs55") == (8, 100, "A", "G")
    assert parse_region("chr8:100-2000") == (8, 100, 2000)
    for bad in ("8:100", "8:100:A", "banana:1:A:G", "8:zero:A:G",
                "8:0:A:G", "8:100:A!:G", "8:100:A:G:extra:junk"):
        with pytest.raises(QueryError):
            parse_variant_id(bad)
    for bad in ("8:100", "8:a-b", "nope:1-2", "8:9-3", "8:0-5",
                "8:1-999999999"):
        with pytest.raises(QueryError):
            parse_region(bad)


# ---------------------------------------------------------------------------
# engine parity vs the brute-force scan


def test_point_parity_all_rows(served):
    _dir, truth, manager, engine = served
    store = manager.current().store
    for row in truth:
        shard = store.shards[row["chrom"]]
        gid = _brute_find(shard, row["pos"], row["ref"], row["alt"])
        assert gid is not None, row
        want = render_variant(shard, row["chrom"], gid)
        got = engine.lookup(_vid(row))
        assert got == want, f"point mismatch for {_vid(row)}"


def test_point_renderer_fields_match_inputs(served):
    _dir, truth, _manager, engine = served
    for row in truth[::7]:  # renderer spot-check against the source data
        rec = json.loads(engine.lookup(_vid(row)))
        assert rec["chromosome"] == chromosome_label(row["chrom"])
        assert rec["position"] == row["pos"]
        assert (rec["ref"], rec["alt"]) == (row["ref"], row["alt"])
        assert rec["ref_snp"] == (
            f"rs{row['rs']}" if row["rs"] >= 0 else None
        )
        ann = rec["annotations"]
        if row["cadd"] is not None:
            assert ann["cadd_scores"]["CADD_phred"] == row["cadd"]
        else:
            assert "cadd_scores" not in ann
        if row["rank"] is not None:
            assert ann["adsp_most_severe_consequence"]["rank"] == row["rank"]
        if row["vep"]:  # RawJson splice survives as real JSON
            assert ann["vep_output"]["input"].startswith(str(row["chrom"]))


def test_point_misses_and_shadowed_duplicate(served):
    _dir, truth, manager, engine = served
    assert engine.lookup("8:499:A:G") is None          # absent position
    assert engine.lookup("2:500:A:G") is None          # unloaded chromosome
    assert engine.lookup("8:500:T:C") is None          # wrong alleles
    # the duplicate identity planted in the newer chr8 segment is shadowed:
    # the OLD row's annotations win (first-wins), never cadd=999
    dup = next(r for r in truth if r["chrom"] == 8 and r["pos"] == 500)
    rec = json.loads(engine.lookup(_vid(dup)))
    cadd = rec["annotations"].get("cadd_scores")
    assert cadd is None or cadd["CADD_phred"] != 999.0


def test_overwidth_long_allele_point(served):
    _dir, truth, _manager, engine = served
    long_row = next(r for r in truth if len(r["ref"]) > WIDTH)
    rec = json.loads(engine.lookup(_vid(long_row)))
    assert rec["ref"] == long_row["ref"]  # true string, not the truncation


def test_point_render_cache_byte_bounded(served):
    """The render LRU is bounded in BYTES as well as entries: records
    carrying large annotation blobs must not pin entries x record-size
    of RSS in a long-lived serving process.  The byte ledger stays exact
    under eviction."""
    store_dir, truth, _manager, _engine = served
    eng = QueryEngine(SnapshotManager(store_dir))
    rows = [r for r in truth if r["chrom"] == 8][:20]
    one = len(eng.lookup(_vid(rows[0])))
    eng.POINT_RENDER_CACHE_BYTES = int(one * 2.5)  # room for ~2 records
    for r in rows:
        assert eng.lookup(_vid(r)) is not None
    assert eng._render_cache_bytes <= eng.POINT_RENDER_CACHE_BYTES
    assert eng._render_cache_bytes == sum(
        len(v) for v in eng._render_cache.values()
    )
    assert len(eng._render_cache) >= 1  # the bound evicts, not disables


def test_bulk_parity_thousands(served):
    _dir, truth, _manager, engine = served
    ids = [_vid(r) for r in truth]
    misses = [f"8:{p}:A:G" for p in range(3, 3 + 60)]
    batch = (ids + misses) * 8  # ~3.5k ids through one vectorized call
    got = engine.lookup_many(batch)
    singles = {i: engine.lookup(i) for i in set(batch)}
    assert got == [singles[i] for i in batch]
    assert sum(1 for r in got if r is None) == len(misses) * 8
    with pytest.raises(QueryError):
        engine.lookup_many(["8:1:A:G", "garbage"])


REGIONS = [
    (8, 1, 10_000),            # spans the overlapping extra segment
    (8, 490, 600),             # duplicate + long-allele corner
    (8, 120_000, 160_000),     # interior of the second run
    (1, 1, 3_000_000),         # whole loaded range, crosses all segments
    (23, 2_000_000, 2_005_000),
    (8, 50_000, 60_000),       # gap: zero rows
    (11, 1, 5_000),            # unloaded chromosome: zero rows
]


@pytest.mark.parametrize("code,start,end", REGIONS)
def test_region_parity(served, code, start, end):
    _dir, _truth, manager, engine = served
    snap = manager.current()
    label = chromosome_label(code)
    got = engine.region(f"{label}:{start}-{end}")
    want = _brute_region_text(snap.store, snap.generation, code, start, end)
    assert got == want  # byte-identical, envelope included


def test_region_filters_and_limit(served):
    _dir, _truth, manager, engine = served
    snap = manager.current()
    for min_cadd, max_rank, limit in (
        (10.0, None, None), (None, 5, None), (4.0, 10, None),
        (None, None, 3), (1.0, 25, 2),
    ):
        got = engine.region("8:1-3000000", min_cadd=min_cadd,
                            max_conseq_rank=max_rank, limit=limit)
        want = _brute_region_text(
            snap.store, snap.generation, 8, 1, 3_000_000,
            min_cadd=min_cadd, max_rank=max_rank, limit=limit,
        )
        assert got == want
        rec = json.loads(got)
        assert rec["returned"] == len(rec["variants"])
        assert rec["returned"] <= rec["count"]


def test_region_lru_cache():
    store = VariantStore(width=WIDTH)
    shard = store.shard(8)
    _append(shard, _rows_for(8, 500, 10, salt=0))
    from annotatedvdb_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    engine = QueryEngine(StaticSnapshots(store), registry=reg,
                         region_cache_size=2)
    first = engine.region("8:1-100000")
    assert engine.region("8:1-100000") == first          # hit
    engine.region("8:1-5")                               # fill
    engine.region("8:6-10")                              # evicts the first
    engine.region("8:1-100000")                          # miss again
    snap = reg.snapshot()
    assert snap["avdb_query_cache_hits_total"][0]["value"] == 1
    assert snap["avdb_query_cache_misses_total"][0]["value"] == 4


# ---------------------------------------------------------------------------
# batcher


def test_batcher_32_concurrent_clients(served):
    _dir, truth, _manager, engine = served
    ids = [_vid(r) for r in truth]
    expected = {i: engine.lookup(i) for i in ids}
    expected["8:499:A:G"] = None
    batcher = BatcherOnLoop(engine, max_batch=64, max_wait_s=0.005,
                            max_queue=10_000)
    n_threads, per_thread = 32, 25
    failures: list = []
    barrier = threading.Barrier(n_threads)

    def client(tid: int):
        try:
            barrier.wait(timeout=10)
            for k in range(per_thread):
                qid = ids[(tid * 7 + k * 13) % len(ids)] \
                    if (tid + k) % 5 else "8:499:A:G"
                got = batcher.submit(qid)
                if got != expected[qid]:
                    failures.append((tid, qid))
        except Exception as exc:
            failures.append((tid, repr(exc)))

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    try:
        assert not failures, failures[:5]
        stats = batcher.batcher.drain_stats()
        assert stats["queries"] == n_threads * per_thread
        # coalescing actually happened: far fewer drains than queries
        assert stats["batches"] < stats["queries"]
        assert 0.0 < stats["batch_fill"] <= 1.0
    finally:
        batcher.close()


def test_batcher_bad_grammar_stays_with_its_caller(served):
    _dir, truth, _manager, engine = served
    batcher = BatcherOnLoop(engine, max_batch=8, max_wait_s=0.001)
    try:
        with pytest.raises(QueryError):
            batcher.submit("not-a-variant")
        # the loop is unharmed and still answers real queries
        assert batcher.submit(_vid(truth[0])) is not None
    finally:
        batcher.close()


def test_batcher_admission_bound(served):
    _dir, truth, _manager, engine = served
    batcher = BatcherOnLoop(engine, max_batch=8, max_wait_s=0.001,
                            max_queue=0)
    try:
        with pytest.raises(QueueFull):
            batcher.submit(_vid(truth[0]))
    finally:
        batcher.close()


# ---------------------------------------------------------------------------
# snapshot isolation


def _commit_more_rows(store_dir: str) -> int:
    """A loader-shaped commit into the serving directory: load writable,
    append, save (atomic manifest swap)."""
    store = VariantStore.load(store_dir)
    rows = [{"chrom": 8, "pos": 5_000_000 + 11 * i, "ref": "A", "alt": "C",
             "rs": -1, "cadd": None, "rank": None, "vep": False}
            for i in range(25)]
    _append(store.shard(8), rows)
    store.save(store_dir)
    return len(rows)


def test_snapshot_isolation_across_commit(tmp_path):
    store_dir = str(tmp_path / "store")
    _build_store(store_dir)
    manager = SnapshotManager(store_dir)
    engine = QueryEngine(manager, region_cache_size=0)
    pinned = manager.current()
    rows_before = pinned.store.n
    before = engine.region("8:4999999-5001000")
    assert json.loads(before)["count"] == 0
    assert manager.refresh() is False  # nothing changed on disk

    added = _commit_more_rows(store_dir)

    # no refresh yet: in-flight readers keep the pinned generation
    assert json.loads(engine.region("8:4999999-5001000"))["count"] == 0
    assert manager.current() is pinned

    assert manager.refresh() is True
    snap = manager.current()
    assert snap.generation == pinned.generation + 1
    assert snap.store.n == rows_before + added
    got = json.loads(engine.region("8:4999999-5001000"))
    assert got["count"] > 0 and got["generation"] == snap.generation
    # the OLD snapshot object still answers exactly the old generation
    assert pinned.store.n == rows_before
    assert manager.refresh() is False


# ---------------------------------------------------------------------------
# HTTP front end


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30
        ) as r:
            return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode(), dict(err.headers)


@pytest.fixture()
def http_server(served):
    store_dir, truth, _manager, _engine = served
    httpd = start_server(store_dir=store_dir)
    try:
        yield httpd, httpd.server_address[1], truth
    finally:
        stop_server(httpd)


def test_http_end_to_end(http_server):
    httpd, port, truth = http_server
    status, body, _ = _get(port, "/healthz")
    assert status == 200 and json.loads(body)["status"] == "ok"

    row = truth[0]
    status, body, _ = _get(port, f"/variant/{_vid(row)}")
    assert status == 200
    assert json.loads(body)["position"] == row["pos"]

    status, body, _ = _get(port, "/variant/8:499:A:G")
    assert status == 404
    status, body, _ = _get(port, "/variant/garbage")
    assert status == 400
    status, body, _ = _get(port, "/nope")
    assert status == 404

    status, body, _ = _get(port, "/region/8:1-10000?minCadd=5&limit=4")
    assert status == 200
    rec = json.loads(body)
    assert rec["returned"] <= 4
    assert all(
        v["annotations"]["cadd_scores"]["CADD_phred"] >= 5
        for v in rec["variants"]
    )
    status, body, _ = _get(port, "/region/8:9-3")
    assert status == 400

    ids = [_vid(r) for r in truth[:50]] + ["8:499:A:G"]
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/variants",
        data=json.dumps({"ids": ids}).encode(), method="POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        rec = json.loads(r.read().decode())
    assert rec["n"] == 51 and rec["found"] == 50
    assert rec["results"][-1] is None

    # explicit limit=0 is a count-only query, NOT the default page size
    status, body, _ = _get(port, "/region/8:1-10000?limit=0")
    rec = json.loads(body)
    assert status == 200 and rec["returned"] == 0 and rec["count"] > 0
    assert rec["variants"] == []

    # malformed bulk bodies are client errors (400), never a dead thread
    for bad in (b"[1,2]", b'{"ids": [1]}', b'{"ids": "x"}', b"{nope"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/variants", data=bad, method="POST"
        )
        try:
            urllib.request.urlopen(req, timeout=30)
            raise AssertionError(f"bulk body {bad!r} was accepted")
        except urllib.error.HTTPError as err:
            assert err.code == 400, (bad, err.code)

    status, body, _ = _get(port, "/metrics")
    assert status == 200
    for metric in ("avdb_query_requests_total", "avdb_query_seconds",
                   "avdb_serve_batches_total"):
        assert metric in body, metric
    status, body, _ = _get(port, "/stats")
    assert status == 200 and json.loads(body)["batcher"]["queries"] >= 2


def test_http_429_under_forced_backpressure(served):
    store_dir, truth, _manager, _engine = served
    httpd = start_server(store_dir=store_dir, max_queue=0)
    try:
        port = httpd.server_address[1]
        status, _body, headers = _get(port, f"/variant/{_vid(truth[0])}")
        assert status == 429
        assert headers.get("Retry-After") == "1"
        status, _body, _ = _get(port, "/region/8:1-10000")
        assert status == 429
        status, body, _ = _get(port, "/metrics")
        assert "avdb_query_rejected_total" in body
    finally:
        stop_server(httpd)


# ---------------------------------------------------------------------------
# read-only store open


def test_readonly_open_contract(tmp_path):
    store_dir = str(tmp_path / "ro")
    _build_store(store_dir)
    store = VariantStore.load(store_dir, readonly=True)
    assert store.readonly
    with pytest.raises(RuntimeError, match="readonly"):
        store.save(store_dir)
    with pytest.raises(RuntimeError, match="readonly"):
        store.shard(2)  # missing shard must not be materialized
    assert store.shards.get(2) is None
    assert store.shard(8).n > 0  # existing shards stay accessible
    # the writable default is unchanged
    assert not VariantStore.load(store_dir).readonly


def test_readonly_storeconfig_never_creates(tmp_path):
    from annotatedvdb_tpu.config import StoreConfig

    missing = str(tmp_path / "absent")
    with pytest.raises(FileNotFoundError):
        StoreConfig(missing).open(readonly=True)
    import os

    assert not os.path.exists(missing)  # no directory side effect
    store_dir = str(tmp_path / "present")
    _build_store(store_dir)
    store, _ledger = StoreConfig(store_dir).open(readonly=True)
    assert store.readonly
