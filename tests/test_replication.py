"""Replica fleets: WAL/ledger shipping, bounded-staleness follower
reads, and kill-the-leader failover (``store/replication.py`` + the
``/repl/*`` ship surface + ``serve --follow`` + ``doctor promote``).

Covers the ship reader's torn-frame guarantee (stable prefixes only),
the snapshot-cut bootstrap (resumable, CRC-verified against the
manifest's own integrity records), the tail/apply loop (byte-identical
follower reads at the applied LSN), the staleness contract (lag gauge,
/readyz 503, upserts 403-with-leader-location), and promote failover
(WAL replay into segments, fencing epoch, deposed-leader flush abort).
"""

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
from annotatedvdb_tpu.serve import MemtableSnapshots, SnapshotManager
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store import replication as repl
from annotatedvdb_tpu.store.memtable import Memtable
from annotatedvdb_tpu.store.wal import WriteAheadLog, count_records
from annotatedvdb_tpu.types import encode_allele_array
from conftest import start_server, stop_server

WIDTH = 8


def _seed_store() -> VariantStore:
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


class _Leader:
    """One in-process leader: on-disk store + memtable + WAL."""

    def __init__(self, store_dir: str):
        self.store_dir = store_dir
        _seed_store().save(store_dir)
        self.registry = MetricsRegistry()
        self.mgr = SnapshotManager(store_dir, log=lambda m: None)
        self.mem = Memtable(
            width=WIDTH, store_dir=store_dir,
            wal=WriteAheadLog(store_dir, "serve-w0", log=lambda m: None),
            registry=self.registry, log=lambda m: None,
        )
        self.httpd = start_server(
            manager=MemtableSnapshots(self.mgr, self.mem),
            memtable=self.mem, registry=self.registry,
        )
        self.port = self.httpd.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"

    def upsert(self, variants):
        status, body = _request(self.port, "POST", "/variants/upsert",
                                {"variants": variants})
        assert status == 200, body
        return json.loads(body)

    def close(self):
        stop_server(self.httpd)


@pytest.fixture()
def leader(tmp_path):
    led = _Leader(str(tmp_path / "leader"))
    yield led
    led.close()


def _follower_server(follower_dir, tailer):
    """A read-only follower server over the mirrored store directory
    with the tailer's overlay — the serve --follow wiring, in-process."""
    registry = MetricsRegistry()
    mgr = SnapshotManager(follower_dir, log=lambda m: None)
    mem = Memtable(width=WIDTH, store_dir=None, wal=None,
                   flush_bytes=0, flush_age_s=0.0, log=lambda m: None)
    manager = MemtableSnapshots(mgr, mem)
    httpd = start_server(manager=manager, memtable=None,
                         registry=registry)
    httpd.ctx.repl = tailer
    httpd.ctx.follow_url = tailer.leader_url
    return httpd, manager, mem, mgr


# -- satellite: WAL stable-prefix / count_records battery --------------------


def _wal_with_records(tmp_path, n=3, name="serve-w0"):
    wal = WriteAheadLog(str(tmp_path), name=name, log=lambda m: None)
    for i in range(n):
        wal.append({"rows": [{"id": f"3:{100 + i}:A:G"}]})
    return wal


def test_count_records_and_stable_prefix_intact(tmp_path):
    wal = _wal_with_records(tmp_path, n=3)
    wal.close()
    path = wal.pending_files()[0][1]
    assert count_records(path) == 3
    stable, records = repl.stable_wal_prefix(path)
    assert records == 3
    assert stable == os.path.getsize(path)


def test_torn_tail_mid_frame_returns_stable_prefix(tmp_path):
    """A torn tail (kill mid-append) never ships and never counts: both
    readers stop at the last intact frame boundary."""
    wal = _wal_with_records(tmp_path, n=3)
    wal.close()
    path = wal.pending_files()[0][1]
    full, _ = repl.stable_wal_prefix(path)
    for cut in (full - 1, full - 7, full - 20):
        with open(path, "r+b") as f:
            f.truncate(full)  # restore, then tear mid-3rd-frame
            f.truncate(cut)
        assert count_records(path) == 2
        stable, records = repl.stable_wal_prefix(path)
        assert records == 2
        # the stable prefix is a frame boundary: re-reading exactly those
        # bytes yields whole records, never a torn frame
        assert repl.read_wal_records(path, 0, stable) == [
            {"rows": [{"id": "3:100:A:G"}]},
            {"rows": [{"id": "3:101:A:G"}]},
        ]


def test_corrupt_frame_ends_prefix_not_file(tmp_path):
    wal = _wal_with_records(tmp_path, n=2)
    wal.close()
    path = wal.pending_files()[0][1]
    stable1, _ = repl.stable_wal_prefix(path)
    # flip one byte inside the SECOND frame's payload
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size - 2)
        b = f.read(1)
        f.seek(size - 2)
        f.write(bytes([b[0] ^ 0xFF]))
    stable, records = repl.stable_wal_prefix(path)
    assert records == 1
    assert 0 < stable < stable1


def test_empty_sealed_file_counts_zero(tmp_path):
    wal = _wal_with_records(tmp_path, n=1)
    sealed = wal.rotate()  # the new active file is header-only
    wal.close()
    paths = dict(wal.pending_files())
    active = paths[sealed + 1]
    assert count_records(active) == 0
    stable, records = repl.stable_wal_prefix(active)
    assert records == 0
    assert stable == os.path.getsize(active)  # header ships, no frames


def test_alien_and_missing_files_are_empty_prefix(tmp_path):
    alien = str(tmp_path / "serve-w0.000001.wal")
    with open(alien, "w") as f:
        f.write("this is not a wal header\n")
    assert repl.stable_wal_prefix(alien) == (0, 0)
    assert count_records(alien) == 0
    assert repl.stable_wal_prefix(str(tmp_path / "nope.wal")) == (0, 0)


def test_rotation_race_reader_sees_stable_prefix(tmp_path):
    """Reader vs appender race: every concurrently captured prefix must
    parse to whole records (the ship surface's no-torn-frame contract)."""
    wal = WriteAheadLog(str(tmp_path), name="serve-w0", log=lambda m: None)
    wal.append({"rows": [{"id": "3:1:A:G"}]})
    path = wal.pending_files()[0][1]
    stop = threading.Event()
    seen = []

    def reader():
        while not stop.is_set():
            p = path  # capture: rotation swaps the module-level name
            stable, records = repl.stable_wal_prefix(p)
            recs = repl.read_wal_records(p, 0, stable)
            seen.append((stable, records, len(recs)))

    t = threading.Thread(target=reader)
    t.start()
    for i in range(60):
        wal.append({"rows": [{"id": f"3:{i + 2}:A:G"}]})
        if i % 20 == 19:
            wal.rotate()
            path = wal.pending_files()[-1][1]
    stop.set()
    t.join()
    wal.close()
    assert seen
    for stable, records, parsed in seen:
        assert parsed == records  # every stable byte range parses fully


# -- ship surface ------------------------------------------------------------


def test_ship_manifest_document_shape(leader):
    leader.upsert([{"id": "3:15:A:G"}])
    doc = repl.ship_manifest(leader.store_dir)
    assert doc["repl"] == 1
    assert doc["epoch"] == 0
    assert isinstance(doc["manifest"], dict) and "shards" in doc["manifest"]
    assert len(doc["fingerprint"]) == 3
    (entry,) = doc["wal"]
    assert entry["records"] == 1
    assert entry["bytes"] == repl.stable_wal_prefix(
        os.path.join(leader.store_dir, entry["file"])
    )[0]


def test_ship_manifest_refuses_non_store(tmp_path):
    with pytest.raises(repl.ReplError):
        repl.ship_manifest(str(tmp_path))
    os.makedirs(tmp_path / "x")
    with open(tmp_path / "x" / "manifest.json", "w") as f:
        f.write("{\"not\": \"a store\"}")
    with pytest.raises(repl.ReplError):
        repl.ship_manifest(str(tmp_path / "x"))


def test_ship_file_range_namespace_and_clamps(leader):
    leader.upsert([{"id": "3:15:A:G"}])
    d = leader.store_dir
    # segments ship raw
    seg = sorted(f for f in os.listdir(d) if f.endswith(".npz"))[0]
    blob = repl.ship_file_range(d, seg, 0, 1 << 30)
    assert blob == open(os.path.join(d, seg), "rb").read()
    # offset/limit honored
    assert repl.ship_file_range(d, seg, 2, 3) == blob[2:5]
    # WAL clamps to the stable prefix even when the file is longer
    wname = repl.wal_files(d)[0]
    wpath = os.path.join(d, wname)
    stable, _ = repl.stable_wal_prefix(wpath)
    with open(wpath, "ab") as f:
        f.write(b"\x99" * 9)  # a torn tail beyond the stable prefix
    assert repl.ship_file_range(d, wname, 0, 1 << 30) == \
        open(wpath, "rb").read()[:stable]
    assert repl.ship_file_range(d, wname, stable, 100) == b""
    # outside the namespace: refused, not read
    for name in ("manifest.json", "../etc/passwd", ".hidden",
                 "repl.cursor.json", "serve-w0.000001.wal.tmp"):
        assert repl.ship_file_range(d, name, 0, 10) is None


def test_repl_routes_404_without_store_dir():
    """A StaticSnapshots front end (no on-disk store) has no ship
    surface: /repl/* answer 404, not a crash."""
    from annotatedvdb_tpu.serve import StaticSnapshots

    httpd = start_server(manager=StaticSnapshots(_seed_store()))
    try:
        port = httpd.server_address[1]
        for path in ("/repl/manifest", "/repl/wal?name=x", "/repl/segment"):
            status, body = _request(port, "GET", path)
            assert status == 404, (path, body)
    finally:
        stop_server(httpd)


# -- bootstrap + tail --------------------------------------------------------


def test_bootstrap_then_tail_byte_identical_reads(leader, tmp_path):
    leader.upsert([
        {"id": "3:15:A:G", "ref_snp": 42,
         "annotations": {"cadd_scores": {"CADD_phred": 31.0}}},
        {"id": "3:25:AT:A"},
    ])
    fdir = str(tmp_path / "follower")
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    applied = []
    tailer.apply_rows = applied.extend
    tailer.bootstrap()
    # the mirror is a loadable store from the first bootstrap on
    assert VariantStore.load(fdir, readonly=True).n == 3
    out = tailer.sync_once()
    assert out["applied"] == 1 and not out["resynced"]  # 1 record, 2 rows
    assert [r["pos"] for r in applied] == [15, 25]
    # WAL mirror is byte-identical to the leader's stable prefix
    wname = repl.wal_files(fdir)[0]
    assert open(os.path.join(fdir, wname), "rb").read() == \
        open(os.path.join(leader.store_dir, wname), "rb").read()
    # cursor ledger persisted (resumable)
    cur = json.load(open(os.path.join(fdir, repl.CURSOR_FILE)))
    assert cur["repl_cursor"] == 1 and cur["offsets"]

    # serve the mirror through the follower front end: every read is
    # byte-identical to the leader at the applied LSN
    httpd, _manager, mem, _mgr = _follower_server(fdir, tailer)
    try:
        for rec in tailer.local_records():
            mem.upsert(_mgr_store(_manager), rec["rows"], durable=False)
        fport = httpd.server_address[1]
        for path in ("/variant/3:15:A:G", "/variant/3:25:AT:A",
                     "/variant/3:20:A:C", "/region/3:1-1000"):
            ls, lb = _request(leader.port, "GET", path)
            fs, fb = _request(fport, "GET", path)
            assert (ls, lb) == (fs, fb), path
    finally:
        stop_server(httpd)


def _mgr_store(manager):
    return manager.base.current().store


def test_tail_is_incremental_and_idempotent(leader, tmp_path):
    fdir = str(tmp_path / "follower")
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    applied = []
    tailer.apply_rows = applied.extend
    tailer.bootstrap()
    leader.upsert([{"id": "3:15:A:G"}])
    assert tailer.sync_once()["applied"] == 1
    assert tailer.sync_once()["applied"] == 0  # nothing new: no re-apply
    leader.upsert([{"id": "3:25:AT:A"}])
    assert tailer.sync_once()["applied"] == 1
    assert [r["pos"] for r in applied] == [15, 25]


def test_leader_flush_resyncs_cut_and_resets_overlay(leader, tmp_path):
    """A leader memtable flush commits a new manifest generation and
    discards sealed WAL files; the follower must re-sync the cut, drop
    vanished mirrors, and fire on_resync exactly once."""
    fdir = str(tmp_path / "follower")
    resyncs = []
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None,
                                on_resync=lambda: resyncs.append(1))
    tailer.bootstrap()
    leader.upsert([{"id": "3:15:A:G"}])
    tailer.sync_once()
    assert repl.wal_files(fdir)

    assert leader.mem.flush()["status"] == "flushed"
    leader.mgr.refresh()
    out = tailer.sync_once()
    assert out["resynced"] and resyncs == [1]
    # the flushed row is in the mirrored base cut now; the discarded
    # leader WAL vanished from the mirror too
    assert VariantStore.load(fdir, readonly=True).n == 4
    assert repl.wal_files(fdir) == repl.wal_files(leader.store_dir)


def test_restart_resume_recovers_lsn_and_records(leader, tmp_path):
    leader.upsert([{"id": "3:15:A:G"}, {"id": "3:25:AT:A"}])
    fdir = str(tmp_path / "follower")
    t1 = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    t1.bootstrap()
    t1.sync_once()
    offsets = dict(t1._offsets)

    # a fresh incarnation adopts the cursor and re-derives the LSN
    # vector from the mirrored bytes alone
    t2 = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    recovered = t2.resume()
    assert recovered == 1  # one record (two rows) durable locally
    assert t2._offsets == offsets
    rows = [r["pos"] for rec in t2.local_records() for r in rec["rows"]]
    assert rows == [15, 25]
    assert t2.sync_once()["applied"] == 0  # nothing re-applied


def test_restart_truncates_torn_mirror_tail(leader, tmp_path):
    """A kill mid-mirror leaves a torn tail; resume truncates back to
    the local stable prefix and the next cycle re-ships the difference —
    the follower lands on a consistent applied-LSN prefix, never a
    hybrid."""
    leader.upsert([{"id": "3:15:A:G"}])
    fdir = str(tmp_path / "follower")
    t1 = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    t1.bootstrap()
    t1.sync_once()
    wname = repl.wal_files(fdir)[0]
    wpath = os.path.join(fdir, wname)
    with open(wpath, "ab") as f:
        f.write(b"\x01\x02\x03")  # torn mid-frame tail

    leader.upsert([{"id": "3:25:AT:A"}])
    t2 = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    applied = []
    t2.apply_rows = applied.extend
    assert t2.resume() == 1
    t2.sync_once()
    # only the NEW record applies; the mirror is whole again
    assert [r["pos"] for r in applied] == [25]
    assert open(wpath, "rb").read() == \
        open(os.path.join(leader.store_dir, wname), "rb").read()


def test_nonpersist_worker_applies_without_touching_disk(leader, tmp_path):
    """Fleet follower workers 1..N (persist=False) apply shipped frames
    straight from memory: same applied rows, zero files mirrored."""
    leader.upsert([{"id": "3:15:A:G"}])
    fdir = str(tmp_path / "follower-w1")
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None,
                                persist=False)
    applied = []
    tailer.apply_rows = applied.extend
    tailer.bootstrap()
    tailer.sync_once()
    assert [r["pos"] for r in applied] == [15]
    assert not os.path.exists(fdir) or not os.listdir(fdir)


def test_deposed_leader_epoch_refused(leader, tmp_path):
    fdir = str(tmp_path / "follower")
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    tailer.bootstrap()
    tailer._epoch = 7  # as if this follower already saw epoch 7
    with pytest.raises(repl.ReplError, match="deposed"):
        tailer.sync_once()


# -- staleness contract ------------------------------------------------------


def test_lag_gauge_readyz_and_follower_403(leader, tmp_path):
    leader.upsert([{"id": "3:15:A:G"}])
    fdir = str(tmp_path / "follower")
    registry = MetricsRegistry()
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None,
                                registry=registry, max_lag_s=0.2)
    tailer.bootstrap()
    tailer.sync_once()
    assert tailer.lag_s() < 0.2 and not tailer.lag_exceeded()

    httpd, _manager, _mem, _mgr = _follower_server(fdir, tailer)
    try:
        fport = httpd.server_address[1]
        status, _ = _request(fport, "GET", "/readyz")
        assert status == 200
        # upserts on a follower: 403 with the leader's location
        status, body = _request(fport, "POST", "/variants/upsert",
                                {"variants": [{"id": "3:77:A:G"}]})
        assert status == 403
        assert json.loads(body)["leader"] == leader.url
        # stall the ship stream: lag grows past the declared bound
        tailer._caught_up_t -= 10.0
        assert tailer.lag_exceeded()
        status, body = _request(fport, "GET", "/readyz")
        assert status == 503 and b"replication lag" in body
        # catch-up clears the gate
        tailer.sync_once()
        status, _ = _request(fport, "GET", "/readyz")
        assert status == 200
    finally:
        stop_server(httpd)


def test_background_tail_thread_tracks_leader(leader, tmp_path):
    fdir = str(tmp_path / "follower")
    registry = MetricsRegistry()
    applied = []
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None,
                                registry=registry, poll_s=0.05,
                                apply_rows=applied.extend)
    tailer.bootstrap()
    tailer.start()
    try:
        leader.upsert([{"id": "3:15:A:G"}])
        deadline = time.monotonic() + 10
        while not applied and time.monotonic() < deadline:
            time.sleep(0.02)
        assert [r["pos"] for r in applied] == [15]
    finally:
        tailer.stop()
    rendered = registry.render_prometheus()
    assert "avdb_replication_lag_seconds" in rendered
    assert "avdb_repl_records_applied_total" in rendered
    assert "avdb_repl_ship_bytes_total" in rendered


# -- env knobs ---------------------------------------------------------------


def test_repl_env_knobs(monkeypatch):
    assert repl.repl_max_lag_from_env() == 5.0
    assert repl.repl_poll_from_env() == 0.5
    assert repl.repl_chunk_from_env() == 4 << 20
    assert repl.repl_timeout_from_env() == 10.0
    monkeypatch.setenv("AVDB_REPL_MAX_LAG_S", "0")
    assert repl.repl_max_lag_from_env() == 0.0
    monkeypatch.setenv("AVDB_REPL_CHUNK_BYTES", "512k")
    assert repl.repl_chunk_from_env() == 512 << 10
    for var, fn in (
        ("AVDB_REPL_MAX_LAG_S", repl.repl_max_lag_from_env),
        ("AVDB_REPL_POLL_S", repl.repl_poll_from_env),
        ("AVDB_REPL_CHUNK_BYTES", repl.repl_chunk_from_env),
        ("AVDB_REPL_TIMEOUT_S", repl.repl_timeout_from_env),
    ):
        monkeypatch.setenv(var, "bogus")
        with pytest.raises(ValueError, match=var):
            fn()
        monkeypatch.delenv(var)


# -- promote (failover) ------------------------------------------------------


def test_promote_seals_tail_bumps_epoch_and_fences(leader, tmp_path):
    leader.upsert([{"id": "3:15:A:G"},
                   {"id": "3:25:AT:A", "ref_snp": 9}])
    fdir = str(tmp_path / "follower")
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    tailer.bootstrap()
    tailer.sync_once()

    out = repl.promote(fdir, log=lambda m: None)
    assert out == {"status": "promoted", "epoch": 1, "rows": 2}
    # the tailed rows are ordinary committed segments now
    store = VariantStore.load(fdir, readonly=True)
    assert store.n == 5
    assert not repl.wal_files(fdir)
    assert not os.path.exists(os.path.join(fdir, repl.CURSOR_FILE))
    manifest = json.load(open(os.path.join(fdir, "manifest.json")))
    assert manifest["repl_epoch"] == 1

    # promote is idempotent: nothing left to replay, epoch moves on
    again = repl.promote(fdir, log=lambda m: None)
    assert again["status"] == "promoted" and again["rows"] == 0
    assert again["epoch"] == 2

    # fencing: a writer that opened the store before the promote cannot
    # commit a flush over the promoted lineage
    deposed = Memtable(width=WIDTH, store_dir=fdir, wal=None,
                       log=lambda m: None, fence_epoch=0)
    deposed.upsert(store, [{"code": 3, "pos": 99, "ref": "A", "alt": "G"}],
                   durable=False)
    result = deposed.flush()
    assert result["status"] == "aborted"
    assert "fenced" in result["reason"]
    # a writer opened AFTER the promote (fence_epoch = current) commits
    fresh = Memtable(width=WIDTH, store_dir=fdir, wal=None,
                     log=lambda m: None, fence_epoch=2)
    fresh.upsert(store, [{"code": 3, "pos": 99, "ref": "A", "alt": "G"}],
                 durable=False)
    assert fresh.flush()["status"] == "flushed"


def test_promoted_follower_refuses_old_leader(leader, tmp_path):
    """After promote, a tailer re-pointed at the deposed leader refuses
    it (its epoch is behind the promoted store's cursor-free epoch)."""
    fdir = str(tmp_path / "follower")
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    tailer.bootstrap()
    tailer.sync_once()
    repl.promote(fdir, log=lambda m: None)
    t2 = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    t2._epoch = 1  # the promoted epoch
    with pytest.raises(repl.ReplError, match="deposed"):
        t2.sync_once()


def test_doctor_promote_cli(leader, tmp_path, capsys):
    from annotatedvdb_tpu.cli.doctor import main as doctor_main

    leader.upsert([{"id": "3:15:A:G"}])
    fdir = str(tmp_path / "follower")
    tailer = repl.ReplicaTailer(fdir, leader.url, log=lambda m: None)
    tailer.bootstrap()
    tailer.sync_once()
    rc = doctor_main(["promote", "--storeDir", fdir, "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["status"] == "promoted" and out["rows"] == 1
    assert VariantStore.load(fdir, readonly=True).n == 4
    # not a store: exit 2
    assert doctor_main(
        ["promote", "--storeDir", str(tmp_path / "nope")]
    ) == 2
