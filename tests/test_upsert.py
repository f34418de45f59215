"""Live write path: WAL durability, memtable semantics, and the
read-your-writes contract — an upserted row is immediately visible
through every read path (point/bulk/region/regions), byte-identical
across BOTH front ends, merged under the store's first-wins dedup policy,
and byte-identical before vs after the memtable flushes it to ordinary
store segments."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.serve import (
    MemtableSnapshots,
    QueryEngine,
    QueryError,
    SnapshotManager,
    StaticSnapshots,
)
from annotatedvdb_tpu.serve.http import (
    MSG_UPSERTS_DISABLED,
    UPSERT_MAX_ROWS,
    parse_upsert_body,
)
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.memtable import (
    Memtable,
    flush_age_from_env,
    flush_bytes_from_env,
)
from annotatedvdb_tpu.store.wal import WriteAheadLog
from annotatedvdb_tpu.types import encode_allele_array
from conftest import bulk_envelope, start_server, stop_server

WIDTH = 8


def _seed_store() -> VariantStore:
    """Three chr3 A->C SNVs (pos 10/20/30) with real identity hashes and
    a CADD annotation on the middle one (filter paths have work to do)."""
    store = VariantStore(width=WIDTH)
    ref, ref_len = encode_allele_array(["A"] * 3, WIDTH)
    alt, alt_len = encode_allele_array(["C"] * 3, WIDTH)
    store.shard(3).append(
        {"pos": np.asarray([10, 20, 30], np.int32),
         "h": identity_hashes(WIDTH, ref, alt, ref_len, alt_len),
         "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
        annotations={"cadd_scores": [None, {"CADD_phred": 22.5}, None]},
    )
    return store


def _request(port, method, path, body=None, timeout=15):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None,
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as err:
        return err.code, err.read()


@pytest.fixture()
def live(tmp_path):
    """A write-enabled server over an on-disk store, with its memtable +
    WAL: ``{"port", "store_dir", "ctx", "mem", "mgr"}``."""
    store_dir = str(tmp_path / "store")
    _seed_store().save(store_dir)
    registry = MetricsRegistry()
    mgr = SnapshotManager(store_dir, log=lambda m: None)
    mem = Memtable(
        width=WIDTH, store_dir=store_dir,
        wal=WriteAheadLog(store_dir, "serve-w0", log=lambda m: None),
        registry=registry, log=lambda m: None,
    )
    server = start_server(manager=MemtableSnapshots(mgr, mem),
                          memtable=mem, registry=registry)
    yield {
        "port": server.server_address[1], "store_dir": store_dir,
        "ctx": server.ctx, "mem": mem, "mgr": mgr,
    }
    stop_server(server)


UPSERT_BODY = {"variants": [
    {"id": "3:15:A:G", "ref_snp": 42,
     "annotations": {"cadd_scores": {"CADD_phred": 31.0},
                     "other_annotation": {"src": "live"}}},
    {"id": "3:25:AT:A"},
]}


# ---------------------------------------------------------------------------
# WAL unit contract


def test_wal_roundtrip_and_rotation(tmp_path):
    d = str(tmp_path)
    wal = WriteAheadLog(d, "serve-w0", log=lambda m: None)
    wal.append({"rows": [{"a": 1}]})
    wal.append({"rows": [{"b": 2}]})
    sealed = wal.rotate()
    assert sealed == 1
    wal.append({"rows": [{"c": 3}]})
    fresh = WriteAheadLog(d, "serve-w0", log=lambda m: None)
    got = list(fresh.replay_records())
    assert got == [{"rows": [{"a": 1}]}, {"rows": [{"b": 2}]},
                   {"rows": [{"c": 3}]}]
    # discard covers exactly the sealed interval
    assert wal.discard_sealed() == 1
    fresh = WriteAheadLog(d, "serve-w0", log=lambda m: None)
    assert list(fresh.replay_records()) == [{"rows": [{"c": 3}]}]
    wal.close()


def test_wal_torn_tail_dropped_earlier_records_survive(tmp_path):
    d = str(tmp_path)
    wal = WriteAheadLog(d, "serve-w0", log=lambda m: None)
    wal.append({"k": 1})
    wal.append({"k": 2})
    wal.close()
    path = wal.pending_files()[0][1]
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 5)  # tear the 2nd frame
    got = list(WriteAheadLog(d, "serve-w0",
                             log=lambda m: None).replay_records())
    assert got == [{"k": 1}]


def test_wal_corrupt_frame_stops_that_file(tmp_path):
    d = str(tmp_path)
    wal = WriteAheadLog(d, "serve-w0", log=lambda m: None)
    wal.append({"k": 1})
    wal.append({"k": 2})
    wal.close()
    path = wal.pending_files()[0][1]
    blob = bytearray(open(path, "rb").read())
    blob[-3] ^= 0xFF  # flip a byte inside the LAST record's payload
    open(path, "wb").write(bytes(blob))
    got = list(WriteAheadLog(d, "serve-w0",
                             log=lambda m: None).replay_records())
    assert got == [{"k": 1}]  # crc catches the flip; earlier record fine


def test_wal_close_removes_record_free_files_only(tmp_path):
    d = str(tmp_path)
    wal = WriteAheadLog(d, "serve-w0", log=lambda m: None)
    wal.append({"k": 1})
    wal.rotate()  # active file now header-only
    wal.close(remove_if_empty=True)
    files = wal.pending_files()
    assert len(files) == 1  # the record-bearing file stayed
    assert list(WriteAheadLog(d, "serve-w0",
                              log=lambda m: None).replay_records()) \
        == [{"k": 1}]


def test_wal_files_are_per_worker(tmp_path):
    d = str(tmp_path)
    WriteAheadLog(d, "serve-w0", log=lambda m: None).append({"w": 0})
    WriteAheadLog(d, "serve-w1", log=lambda m: None).append({"w": 1})
    assert list(WriteAheadLog(d, "serve-w0",
                              log=lambda m: None).replay_records()) \
        == [{"w": 0}]


# ---------------------------------------------------------------------------
# body grammar (single source)


def test_parse_upsert_body_accepts_canonical_shape():
    entries = parse_upsert_body(json.dumps(UPSERT_BODY).encode())
    assert entries[0]["id"] == "3:15:A:G"
    assert entries[0]["ref_snp"] == 42
    assert entries[1]["annotations"] is None


@pytest.mark.parametrize("body", [
    b"not json",
    b"[]",
    b"{}",
    b'{"variants": []}',
    b'{"variants": ["3:15:A:G"]}',
    b'{"variants": [{"id": 7}]}',
    b'{"variants": [{"id": "3:15:A:G", "ref_snp": -1}]}',
    b'{"variants": [{"id": "3:15:A:G", "ref_snp": true}]}',
    b'{"variants": [{"id": "3:15:A:G", "annotations": ["x"]}]}',
    b'{"variants": [{"id": "3:15:A:G", "annotations": {"nope": 1}}]}',
])
def test_parse_upsert_body_rejects_malformed(body):
    with pytest.raises(QueryError):
        parse_upsert_body(body)


def test_parse_upsert_body_row_cap():
    body = json.dumps({"variants": [
        {"id": "3:10:A:C"}] * (UPSERT_MAX_ROWS + 1)}).encode()
    with pytest.raises(QueryError, match="cap"):
        parse_upsert_body(body)


# ---------------------------------------------------------------------------
# read-your-writes: every read path, byte-identical to the engine


def test_upsert_read_your_writes_parity_with_the_engine(live):
    """Oracle: a fresh ``QueryEngine`` over the server's overlay manager
    called directly (``lookup_many``, ``region``,
    ``regions_serve(...).assemble()``)."""
    pt = live["port"]
    status, body = _request(pt, "POST", "/variants/upsert", UPSERT_BODY)
    assert status == 200, body
    assert json.loads(body) == {
        "n": 2, "accepted": 2, "shadowed": 0,
        "generation": json.loads(body)["generation"],
    }
    # IMMEDIATE visibility through every read path, byte-identical to
    # the engine's own answers
    engine = QueryEngine(live["ctx"].manager, region_cache_size=0)
    ids = ["3:15:A:G", "3:25:AT:A", "3:10:A:C", "3:99:A:C"]
    records = engine.lookup_many(ids)
    assert [r is not None for r in records] == [True, True, True, False]
    reads = [
        ("GET", "/variant/3:15:A:G", None, records[0]),
        ("GET", "/variant/3:25:AT:A", None, records[1]),
        # loaded row untouched
        ("GET", "/variant/3:20:A:C", None, engine.lookup("3:20:A:C")),
        ("POST", "/variants", {"ids": ids}, bulk_envelope(records)),
        ("GET", "/region/3:1-100", None, engine.region("3:1-100")),
        # filter sees upsert
        ("GET", "/region/3:1-100?minCadd=30", None,
         engine.region("3:1-100", min_cadd=30.0)),
        ("POST", "/regions", {"regions": ["3:1-100", "3:14-16"]},
         engine.regions_serve(["3:1-100", "3:14-16"]).assemble()),
    ]
    for method, path, body, want in reads:
        status, got = _request(pt, method, path, body)
        assert status == 200, (path, status, got)
        assert got.decode() == want, (path, got, want)
    # and the content is right: the region count grew, the upserted row
    # renders with its annotations, the filter finds the new CADD row
    _s, region = _request(pt, "GET", "/region/3:1-100")
    env = json.loads(region)
    assert env["count"] == 5 and env["returned"] == 5
    _s, rec = _request(pt, "GET", "/variant/3:15:A:G")
    assert b'"rs42"' in rec and b'"src": "live"' in rec
    _s, filtered = _request(pt, "GET", "/region/3:1-100?minCadd=30")
    assert json.loads(filtered)["count"] == 1


def test_upsert_shadowed_by_loaded_row_first_wins(live):
    """An upsert whose identity the store already holds is SHADOWED: the
    stored row keeps answering byte-identically, the response reports
    the shadow, and the rejected-rows counter moves."""
    pt = live["port"]
    _s, before = _request(pt, "GET", "/variant/3:20:A:C")
    status, body = _request(pt, "POST", "/variants/upsert", {"variants": [
        {"id": "3:20:A:C",
         "annotations": {"other_annotation": {"hijack": True}}},
    ]})
    assert status == 200
    assert json.loads(body)["shadowed"] == 1
    assert json.loads(body)["accepted"] == 0
    _s, after = _request(pt, "GET", "/variant/3:20:A:C")
    assert after == before  # first-wins: the loaded row still answers
    # the same identity upserted twice in ONE batch: first occurrence wins
    status, body = _request(pt, "POST", "/variants/upsert", {"variants": [
        {"id": "3:40:A:G", "ref_snp": 1},
        {"id": "3:40:A:G", "ref_snp": 2},
    ]})
    assert json.loads(body) == {
        "n": 2, "accepted": 1, "shadowed": 1,
        "generation": json.loads(body)["generation"],
    }
    _s, rec = _request(pt, "GET", "/variant/3:40:A:G")
    assert b'"rs1"' in rec


def test_upsert_visible_through_concurrent_cursor_walk(live):
    """A paged region walk started BEFORE an upsert picks the new row up
    on pages rendered after it: cursor offsets re-apply against the new
    generation (the best-effort continuation contract cursors already
    have across loader commits)."""
    pt = live["port"]
    s, page1 = _request(pt, "GET", "/region/3:1-100?limit=1&cursor=")
    assert s == 200
    env1 = json.loads(page1)
    assert env1["count"] == 3 and env1["next"]
    status, _b = _request(pt, "POST", "/variants/upsert", {"variants": [
        {"id": "3:25:AT:A"},
    ]})
    assert status == 200
    seen = [v["position"] for v in env1["variants"]]
    cursor = env1["next"]
    for _ in range(8):
        s, page = _request(
            pt, "GET", f"/region/3:1-100?limit=1&cursor={cursor}"
        )
        assert s == 200
        env = json.loads(page)
        seen += [v["position"] for v in env["variants"]]
        assert env["count"] == 4  # the walk now sees the upserted row
        cursor = env["next"]
        if not cursor:
            break
    assert seen == [10, 20, 25, 30]


def test_upserts_disabled_route_403_parity(tmp_path):
    """Oracle: the one message constant, ``MSG_UPSERTS_DISABLED``."""
    store_dir = str(tmp_path / "ro")
    _seed_store().save(store_dir)
    server = start_server(store_dir=store_dir)
    try:
        status, body = _request(server.server_address[1], "POST",
                                "/variants/upsert", UPSERT_BODY)
        assert status == 403
        assert json.loads(body) == {"error": MSG_UPSERTS_DISABLED}
        assert "not enabled" in MSG_UPSERTS_DISABLED
    finally:
        stop_server(server)


def test_upsert_grammar_errors_are_parity_400s(live):
    """Oracle: the shared body grammar (``parse_upsert_body``) and the
    context's id/width validation called directly — the 400 body is the
    ``QueryError`` either raises."""
    ctx = live["ctx"]

    def refusal(body):
        try:
            ctx.upsert_parse_entries(
                parse_upsert_body(json.dumps(body).encode()))
        except QueryError as err:
            return str(err)
        raise AssertionError(f"{body!r} was accepted")

    cases = [
        {"nope": 1},
        {"variants": [{"id": "3:15:A:G", "annotations": {"bogus": 1}}]},
        {"variants": [{"id": "not-an-id"}]},
        {"variants": [{"id": "3:15:" + "A" * 20 + ":G"}]},  # over-width
    ]
    for body in cases:
        status, got = _request(live["port"], "POST", "/variants/upsert",
                               body)
        assert status == 400, (body, status)
        assert json.loads(got) == {"error": refusal(body)}, body
    assert live["mem"].rows == 0  # nothing was applied


# ---------------------------------------------------------------------------
# flush: pre/post byte identity, WAL truncation, ledger record


def test_flush_preserves_read_bytes_and_truncates_wal(live):
    pt, mem, mgr = live["port"], live["mem"], live["mgr"]
    store_dir = live["store_dir"]
    status, _b = _request(pt, "POST", "/variants/upsert", UPSERT_BODY)
    assert status == 200
    reads = [
        ("GET", "/variant/3:15:A:G", None),
        ("GET", "/variant/3:25:AT:A", None),
        ("POST", "/variants", {"ids": ["3:15:A:G", "3:10:A:C"]}),
        ("GET", "/region/3:1-100", None),
        ("POST", "/regions", {"regions": ["3:1-100"]}),
    ]
    before = [_request(pt, m, p, b) for m, p, b in reads]
    result = mem.flush(base_manager=mgr)
    assert result["status"] == "flushed" and result["finalized"], result
    assert mem.rows == 0
    after = [_request(pt, m, p, b) for m, p, b in reads]
    # region envelopes carry the generation, which a flush advances (the
    # view handed over from memtable to store segments) — everything
    # else must be byte-identical
    import re as _re

    def _scrub(pairs):
        return [
            (s, _re.sub(rb'"generation":\d+', b'"generation":G', b))
            for s, b in pairs
        ]

    assert _scrub(before) == _scrub(after)
    # the rows are ordinary store segments now
    store = VariantStore.load(store_dir)
    assert store.shard(3).n == 5
    # the flushed interval's WAL files are gone; a fresh worker replays
    # nothing (the store already holds everything)
    fresh = Memtable(
        width=WIDTH, store_dir=store_dir,
        wal=WriteAheadLog(store_dir, "serve-t", log=lambda m: None),
        log=lambda m: None,
    )
    assert fresh.replay(VariantStore.load(store_dir, readonly=True)) == 0
    # ledger carries the {"type": "flush"} record
    from annotatedvdb_tpu.store import AlgorithmLedger

    ledger = AlgorithmLedger(os.path.join(store_dir, "ledger.jsonl"),
                             log=lambda m: None)
    flushes = ledger.flushes()
    assert flushes and flushes[-1]["rows"] == 2 \
        and flushes[-1]["labels"] == ["3"]


def test_generation_strictly_increases_across_upserts_and_flush(live):
    pt, mem, mgr = live["port"], live["mem"], live["mgr"]
    gens = []

    def healthz_gen():
        _s, b = _request(pt, "GET", "/healthz")
        return json.loads(b)["generation"]

    gens.append(healthz_gen())
    for k in range(3):
        _request(pt, "POST", "/variants/upsert",
                 {"variants": [{"id": f"3:{50 + k}:A:G"}]})
        gens.append(healthz_gen())
    assert mem.flush(base_manager=mgr)["status"] == "flushed"
    gens.append(healthz_gen())
    assert gens == sorted(gens) and len(set(gens)) == len(gens), gens


def test_flush_triggers_and_env_knobs(tmp_path, monkeypatch):
    store_dir = str(tmp_path / "store")
    _seed_store().save(store_dir)
    base = VariantStore.load(store_dir, readonly=True)
    mem = Memtable(width=WIDTH, store_dir=store_dir, flush_bytes=1,
                   flush_age_s=0, log=lambda m: None)
    assert not mem.should_flush()  # empty
    mem.upsert(base, [{"code": 3, "pos": 15, "ref": "A", "alt": "G",
                       "ref_snp": None, "ann": None}])
    assert mem.should_flush()  # one row trips a 1-byte bound
    mem2 = Memtable(width=WIDTH, store_dir=store_dir, flush_bytes=0,
                    flush_age_s=0.05, log=lambda m: None)
    mem2.upsert(base, [{"code": 3, "pos": 16, "ref": "A", "alt": "G",
                        "ref_snp": None, "ann": None}])
    assert not mem2.should_flush()
    time.sleep(0.08)
    assert mem2.should_flush()  # the age trigger
    # env parsing: shared grammar, loud failures
    monkeypatch.setenv("AVDB_MEMTABLE_BYTES", "64m")
    assert flush_bytes_from_env() == 64 << 20
    monkeypatch.setenv("AVDB_MEMTABLE_BYTES", "64mb")
    with pytest.raises(ValueError, match="AVDB_MEMTABLE_BYTES"):
        flush_bytes_from_env()
    monkeypatch.setenv("AVDB_MEMTABLE_FLUSH_S", "2.5")
    assert flush_age_from_env() == 2.5
    monkeypatch.setenv("AVDB_MEMTABLE_FLUSH_S", "soon")
    with pytest.raises(ValueError, match="AVDB_MEMTABLE_FLUSH_S"):
        flush_age_from_env()


def test_upsert_metrics_move(live):
    ctx, mem = live["ctx"], live["mem"]
    reg: MetricsRegistry = ctx.registry
    _request(live["port"], "POST", "/variants/upsert", {"variants": [
        {"id": "3:60:A:G"},
        {"id": "3:10:A:C"},   # shadowed
    ]})
    snap = reg.snapshot()
    assert snap["avdb_upsert_requests_total"][0]["value"] == 1
    assert snap["avdb_upsert_rows_total"][0]["value"] == 1
    assert snap["avdb_upsert_rejected_total"][0]["value"] == 1
    assert snap["avdb_upsert_wal_bytes_total"][0]["value"] > 0
    assert snap["avdb_memtable_bytes"][0]["value"] > 0
    assert snap["avdb_upsert_ack_seconds"][0]["count"] == 1
    kinds = {tuple(sorted(e["labels"].items())): e["value"]
             for e in snap["avdb_query_requests_total"]}
    assert kinds[(("kind", "upsert"),)] == 1
    assert mem.flush(base_manager=live["mgr"])["status"] == "flushed"
    snap = reg.snapshot()
    assert snap["avdb_upsert_flushes_total"][0]["value"] == 1
    assert snap["avdb_memtable_bytes"][0]["value"] == 0


def test_overlay_is_passthrough_until_first_upsert(tmp_path):
    store_dir = str(tmp_path / "store")
    _seed_store().save(store_dir)
    mgr = SnapshotManager(store_dir, log=lambda m: None)
    mem = Memtable(width=WIDTH, store_dir=store_dir, log=lambda m: None)
    prov = MemtableSnapshots(mgr, mem)
    snap = prov.current()
    assert snap is mgr.current()  # the very same object: zero overhead
    base = VariantStore.load(store_dir, readonly=True)
    mem.upsert(base, [{"code": 3, "pos": 15, "ref": "A", "alt": "G",
                       "ref_snp": None, "ann": None}])
    over = prov.current()
    assert over is not snap
    assert over.generation > snap.generation
    assert over.store.n == 4
    # stable while nothing changes (cached overlay, not rebuilt per read)
    assert prov.current() is over


def test_replayed_worker_serves_acked_rows_byte_identical(tmp_path):
    """The respawn story in-process: worker A acks rows and dies
    (abandoned memtable); worker B replays the WAL and serves the exact
    same bytes."""
    store_dir = str(tmp_path / "store")
    _seed_store().save(store_dir)
    base = VariantStore.load(store_dir, readonly=True)
    mem_a = Memtable(
        width=WIDTH, store_dir=store_dir,
        wal=WriteAheadLog(store_dir, "serve-w0", log=lambda m: None),
        log=lambda m: None,
    )
    rows = [
        {"code": 3, "pos": 15, "ref": "A", "alt": "G", "ref_snp": 42,
         "ann": {"other_annotation": {"k": [1, 2]}}},
        {"code": 3, "pos": 25, "ref": "AT", "alt": "A", "ref_snp": None,
         "ann": None},
    ]
    accepted, _s, _b = mem_a.upsert(base, rows)
    assert accepted == 2
    engine_a = QueryEngine(
        MemtableSnapshots(StaticSnapshots(base), mem_a),
        region_cache_size=0,
    )
    want = [engine_a.lookup("3:15:A:G"), engine_a.lookup("3:25:AT:A"),
            engine_a.region("3:1-100")]
    # worker A dies; worker B replays
    mem_b = Memtable(
        width=WIDTH, store_dir=store_dir,
        wal=WriteAheadLog(store_dir, "serve-w0", log=lambda m: None),
        log=lambda m: None,
    )
    assert mem_b.replay(base) == 2
    engine_b = QueryEngine(
        MemtableSnapshots(StaticSnapshots(base), mem_b),
        region_cache_size=0,
    )
    got = [engine_b.lookup("3:15:A:G"), engine_b.lookup("3:25:AT:A"),
           engine_b.region("3:1-100")]
    assert got == want


def test_loader_save_adopts_concurrent_flush_groups(tmp_path):
    """The third-writer hole closed: a loader that loaded the store
    BEFORE a memtable flush committed (and whose WAL was then truncated)
    must not clobber or orphan the flushed segments when it saves —
    save() re-syncs next_seg_id from the live manifest and carries the
    flush's groups forward, on every subsequent checkpoint save too."""
    store_dir = str(tmp_path / "store")
    _seed_store().save(store_dir)

    # the "loader": holds the pre-flush manifest in memory
    loader_store = VariantStore.load(store_dir)

    # a serve worker acks + flushes an upsert meanwhile; the WAL is
    # truncated — the flushed segment is now the ONLY copy of the row
    mem = Memtable(
        width=WIDTH, store_dir=store_dir,
        wal=WriteAheadLog(store_dir, "serve-w0", log=lambda m: None),
        log=lambda m: None,
    )
    base = VariantStore.load(store_dir, readonly=True)
    accepted, _s, _b = mem.upsert(base, [
        {"code": 3, "pos": 15, "ref": "A", "alt": "G", "ref_snp": 7,
         "ann": {"other_annotation": {"live": True}}},
    ])
    assert accepted == 1
    assert mem.flush(base_manager=None)["status"] == "flushed"
    assert not [f for f in os.listdir(store_dir) if f.endswith(".wal")
                and os.path.getsize(os.path.join(store_dir, f)) > 60]

    # the loader commits on top of its STALE view
    import numpy as np_

    from annotatedvdb_tpu.loaders.lookup import identity_hashes as ih

    ref, ref_len = encode_allele_array(["A"], WIDTH)
    alt, alt_len = encode_allele_array(["G"], WIDTH)
    loader_store.shard(3).append(
        {"pos": np_.asarray([40], np_.int32),
         "h": ih(WIDTH, ref, alt, ref_len, alt_len),
         "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
    )
    loader_store.save(store_dir)

    final = VariantStore.load(store_dir)
    assert final.shard(3).n == 5, "flushed row lost to the loader save"
    engine = QueryEngine(StaticSnapshots(final), region_cache_size=0)
    rec = engine.lookup("3:15:A:G")
    assert rec is not None and '"live": true' in rec
    assert engine.lookup("3:40:A:G") is not None

    # a SECOND checkpoint save must keep re-adopting (not a one-shot)
    loader_store.shard(3).append(
        {"pos": np_.asarray([50], np_.int32),
         "h": ih(WIDTH, ref, alt, ref_len, alt_len),
         "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
    )
    loader_store.save(store_dir)
    final = VariantStore.load(store_dir)
    assert final.shard(3).n == 6
    assert QueryEngine(StaticSnapshots(final),
                       region_cache_size=0).lookup("3:15:A:G") == rec

    from annotatedvdb_tpu.store.fsck import fsck

    report = fsck(store_dir, deep=True, log=lambda m: None)
    # only the loader's own record-free wal-less debris may warn; the
    # data findings must be absent
    assert report["exit_code"] in (0, 1), report
    assert not any(f["code"].startswith("segment-")
                   for f in report["findings"]), report


def test_undo_still_drops_rows_despite_adoption(tmp_path):
    """Adoption must never resurrect rows an undo deleted: groups below
    the load-time floor are this store's own to manage."""
    store_dir = str(tmp_path / "store")
    store = VariantStore(width=WIDTH)
    import numpy as np_

    ref, ref_len = encode_allele_array(["A"] * 2, WIDTH)
    alt, alt_len = encode_allele_array(["C"] * 2, WIDTH)
    store.shard(3).append(
        {"pos": np_.asarray([10, 20], np_.int32),
         "h": identity_hashes(WIDTH, ref, alt, ref_len, alt_len),
         "ref_len": ref_len, "alt_len": alt_len,
         "row_algorithm_id": np_.asarray([9, 9], np_.int32)},
        ref, alt,
    )
    store.save(store_dir)
    undoer = VariantStore.load(store_dir)
    assert undoer.delete_by_algorithm(9) == 2
    undoer.save(store_dir)
    assert VariantStore.load(store_dir).n == 0


# ---------------------------------------------------------------------------
# a refresh reads only what changed


def _pinned_segments(mgr) -> dict:
    """{(label, backing group): segment object} of the pinned store."""
    return {
        (code, tuple(seg.backing)): seg
        for code, shard in mgr.current().store.shards.items()
        for seg in shard.segments
    }


def test_refresh_keeps_unchanged_segments_and_reads_new_ones(live):
    """A flush's refresh keeps the pinned store's Segment objects for the
    files the new manifest still lists unchanged, and reads only the
    segment the flush wrote; a compaction that rewrites the group is read
    anew, whole."""
    from annotatedvdb_tpu.store.compact import compact_store

    pt, mem, mgr = live["port"], live["mem"], live["mgr"]
    before = _pinned_segments(mgr)
    assert _request(pt, "POST", "/variants/upsert",
                    UPSERT_BODY)[0] == 200
    assert mem.flush(base_manager=mgr)["finalized"]
    after = _pinned_segments(mgr)
    for key, seg in before.items():
        assert after[key] is seg  # the same object: caches ride along
    new = [seg for key, seg in after.items() if key not in before]
    assert len(new) == 1 and new[0].n == 2
    stats = mgr.stats()
    assert (stats["swaps"], stats["segments_reused"],
            stats["segments_loaded"]) == (1, 1, 1)
    assert stats["reload_s"] > 0

    report = compact_store(live["store_dir"])
    assert report["status"] == "compacted", report
    assert mgr.refresh()
    compacted = _pinned_segments(mgr)
    assert not set(map(id, compacted.values())) & set(map(id, after.values()))
    assert mgr.stats()["segments_loaded"] == 2
    assert mgr.current().store.n == 5


def test_refresh_reads_a_group_whose_files_changed_under_the_same_id(tmp_path):
    """An id reused for other bytes (a rollback, a hand-edited directory)
    is read again: reuse needs the same integrity record and sizes."""
    store_dir = str(tmp_path / "store")
    _seed_store().save(store_dir)
    mgr = SnapshotManager(store_dir, log=lambda m: None)
    (seg,) = _pinned_segments(mgr).values()
    path = os.path.join(store_dir, "manifest.json")
    with open(path) as f:
        manifest = json.load(f)
    (stem,) = manifest["integrity"]
    manifest["integrity"][stem]["npz"]["crc32"] ^= 1
    with open(path, "w") as f:
        json.dump(manifest, f)
    assert mgr.refresh()
    (again,) = _pinned_segments(mgr).values()
    assert again is not seg and again.n == seg.n
    assert mgr.stats()["segments_reused"] == 0
    # nothing is carried into a store that may be written
    rw = VariantStore.load(store_dir, reuse=VariantStore.load(store_dir))
    assert rw.load_counts == {"reused": 0, "loaded": 1}


def test_stats_account_the_write_path(live):
    pt, mem, mgr = live["port"], live["mem"], live["mgr"]
    _request(pt, "POST", "/variants/upsert", UPSERT_BODY)
    _request(pt, "GET", "/variant/3:15:A:G")
    _request(pt, "GET", "/variant/3:10:A:C")
    _s, body = _request(pt, "GET", "/stats")
    stats = json.loads(body)
    assert stats["memtable"]["rows"] == 2 and stats["memtable"]["epoch"] == 1
    assert stats["memtable"]["read_hits"] == 1
    assert stats["overlay"]["generations"] >= 1
    assert mem.flush(base_manager=mgr)["finalized"]
    _s, body = _request(pt, "GET", "/stats")
    stats = json.loads(body)
    assert (stats["memtable"]["flushes"], stats["memtable"]["flush_rows"],
            stats["memtable"]["rows"]) == (1, 2, 0)
    assert stats["memtable"]["flush_s"] > 0
    assert stats["snapshot"]["segments_reused"] == 1
    # the upsert's apply is a stage beside its fsync
    _s, text = _request(pt, "GET", "/metrics")
    assert b'avdb_stage_seconds_count{stage="upsert.apply"} 1' in text
    assert b'avdb_stage_seconds_count{stage="wal_fsync"} 1' in text


def test_wal_fsyncs_count_the_acknowledged_frames(live, monkeypatch):
    """``memtable.wal_fsyncs`` moves once an accepted upsert, not for a
    shadowed one — and not for an ack whose frame was never fsync'd (an
    append planted without its fsync), which the YCSB D cell's
    ``fsync_short`` reads as acks the counter falls short of."""
    pt = live["port"]
    assert _request(pt, "POST", "/variants/upsert", UPSERT_BODY)[0] == 200
    assert live["mem"].stats()["wal_fsyncs"] == 1
    status, body = _request(pt, "POST", "/variants/upsert", UPSERT_BODY)
    assert status == 200 and json.loads(body)["accepted"] == 0
    assert live["mem"].stats()["wal_fsyncs"] == 1

    def append_unsynced(self, payload, trace=None):
        blob = json.dumps(payload).encode()
        with open(self._path(self._seq), "ab") as f:
            f.write(blob)
        return len(blob)

    monkeypatch.setattr(WriteAheadLog, "append", append_unsynced)
    status, body = _request(pt, "POST", "/variants/upsert",
                            {"variants": [{"id": "3:35:A:T"}]})
    assert status == 200 and json.loads(body)["accepted"] == 1
    assert live["mem"].stats()["wal_fsyncs"] == 1


@pytest.mark.parametrize("inserts", [64, 1500])
def test_memtable_merges_scattered_single_row_inserts(tmp_path, inserts):
    """Single-row inserts at scattered positions are disjoint, and one
    batch of reads spans them all: the memtable merges them size-tiered,
    so a shard holds O(log n) segments, not one an insert, and every row
    still answers exactly."""
    rng = np.random.default_rng(inserts)
    pos = rng.choice(np.arange(100, 10_000_000), inserts, replace=False)
    mem = Memtable(width=WIDTH, store_dir=str(tmp_path),
                   wal=WriteAheadLog(str(tmp_path), "serve-w0",
                                     log=lambda m: None),
                   log=lambda m: None)
    for p in pos.tolist():
        accepted, _shadowed, _b = mem.upsert(
            None, [{"code": 3, "pos": p, "ref": "A", "alt": "G"}])
        assert accepted == 1
    _epoch, segments, rows, _bytes = mem.view()
    assert rows == inserts
    assert len(segments[3]) <= 2 * int(np.log2(inserts)) + 1
    assert mem.stats()["wal_fsyncs"] == inserts
    ref, ref_len = encode_allele_array(["A"] * inserts, WIDTH)
    alt, alt_len = encode_allele_array(["G"] * inserts, WIDTH)
    found, _gid = mem._shards[3].lookup(
        pos.astype(np.int32), identity_hashes(WIDTH, ref, alt, ref_len,
                                              alt_len),
        ref, alt, ref_len, alt_len, host_only=True)
    assert found.all()


def test_should_flush_answers_while_a_write_holds_the_lock(tmp_path):
    """The event loop's tick asks ``should_flush``; the write path holds
    the memtable lock across its WAL fsync, so the answer must not wait
    for it."""
    store_dir = str(tmp_path / "store")
    _seed_store().save(store_dir)
    base = VariantStore.load(store_dir, readonly=True)
    mem = Memtable(width=WIDTH, store_dir=store_dir, flush_bytes=1,
                   flush_age_s=0, log=lambda m: None)
    mem.upsert(base, [{"code": 3, "pos": 15, "ref": "A", "alt": "G",
                       "ref_snp": None, "ann": None}])
    with mem._lock:  # a write in its fsync
        t0 = time.monotonic()
        answer = []
        thread = threading.Thread(target=lambda: answer.append(
            mem.should_flush()))
        thread.start()
        thread.join(timeout=5)
        assert answer == [True] and time.monotonic() - t0 < 5
