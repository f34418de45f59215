"""YCSB workload D on the live store against the benchmark's plain
reference (``benchmarks/reference/ycsb_d_check.py``), on the CPU.

A 20,000-record store, loaded and compacted through the program's own
entry points, is served in-process with upserts on; four client threads
run a seeded D mix (reads by YCSB's ``latest`` rule over the acknowledged
sequence, inserts of new records from ``benchmarks/traffic/inserts.py``)
and every read is compared with the reference model.  A memtable flush is
forced mid-run (``AVDB_MEMTABLE_FLUSH_S`` low).  These tests hold that

- every read is exact, an acknowledged insert included, before, across
  and after the flush;
- the flush's refresh keeps the base ``Segment`` objects it did not write
  (by identity), ``residency.uploads`` does not count them again, and no
  program is compiled after they are resident;
- the committed store plus the WAL's records hold every acknowledged
  insert, read back by a fresh process's replay;
- a compaction after the flush is read anew, whole.

The device path is forced as ``test_point_cobatch`` forces it; the
memtable's small segments stay below the candidates' floor, as a few
thousand upserted rows stay below ``DEVICE_SEGMENT_MIN``.
"""

from __future__ import annotations

import contextlib
import http.client
import io
import json
import os
import shutil
import sys
import threading
import time

import numpy as np
import pytest

from annotatedvdb_tpu.io.synth import write_synth_vcf
from annotatedvdb_tpu.serve import (
    MemtableSnapshots,
    QueryEngine,
    ResidencyManager,
    SnapshotManager,
    StaticSnapshots,
)
from annotatedvdb_tpu.serve.aio import build_aio_server
from annotatedvdb_tpu.store import VariantStore, variant_store
from annotatedvdb_tpu.store.compact import compact_store
from annotatedvdb_tpu.store.memtable import Memtable
from annotatedvdb_tpu.store.wal import WriteAheadLog
from annotatedvdb_tpu.utils import runtime

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.append(BENCH)

from reference import ycsb_d_check  # noqa: E402
from traffic import inserts as traffic_inserts  # noqa: E402
from traffic.vcf import Expected, synth_vcf_rows  # noqa: E402

CHROMOSOMES = ("1", "2", "22")
RECORDS = 20_000
SEED = 2147483711
CLIENTS = 4
#: base segments hold ~6,700 rows each; the memtable's and the flush's
#: a few dozen
MIN_ROWS = 1024


@pytest.fixture(scope="module")
def forced_device():
    patch = pytest.MonkeyPatch()
    patch.setattr(variant_store, "_DEVICE_LOOKUP_OK", True)
    runtime._watch_compiles()
    yield
    patch.undo()


@pytest.fixture(scope="module")
def pristine(tmp_path_factory, forced_device):
    """The generated store, loaded and compacted (one segment a
    chromosome), and what the generator says it holds."""
    from annotatedvdb_tpu.cli import doctor, load_vcf

    tmp = tmp_path_factory.mktemp("ycsb_d")
    vcf = tmp / "in.vcf"
    write_synth_vcf(str(vcf), RECORDS, SEED, CHROMOSOMES)
    rc = load_vcf.main(["--fileName", str(vcf), "--storeDir",
                        str(tmp / "vdb"), "--commit", "--commitAfter", "4096",
                        "--logFilePath", str(tmp / "load.log")])
    assert rc == 0
    with contextlib.redirect_stdout(io.StringIO()):
        assert doctor.main(["compact", "--storeDir", str(tmp / "vdb"),
                            "--json"]) == 0
    exp = Expected(synth_vcf_rows(RECORDS, SEED, CHROMOSOMES), CHROMOSOMES)
    return str(tmp / "vdb"), exp


class Acked:
    """The acknowledged counter across the client threads (the driver's
    ``Sequence``, in one process)."""

    def __init__(self, n: int):
        self.lock = threading.Lock()
        self.next = 0
        self.limit = 0
        self.flags = np.zeros(n, bool)

    def take(self) -> int:
        with self.lock:
            self.next += 1
            return self.next - 1

    def acknowledge(self, j: int) -> None:
        with self.lock:
            self.flags[j] = True
            while self.limit < len(self.flags) and self.flags[self.limit]:
                self.limit += 1


def _client(port, exp, new, draws, acked, reads):
    """One closed-loop client until its draws run out: ``reads`` gets
    (key, status, body) of every read."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    kinds, ranks = draws
    for kind, rank in zip(kinds.tolist(), ranks.tolist()):
        if kind:
            j = acked.take()
            conn.request("POST", "/variants/upsert",
                         body=traffic_inserts.upsert_body(
                             exp.chromosomes, new, j),
                         headers={"Content-Type": "application/json"})
            reply = conn.getresponse()
            body = reply.read()
            if reply.status == 200 and b'"accepted":1' in body:
                acked.acknowledge(j)
            else:  # seen by the test, not lost in this thread
                reads.refused.append((reply.status, body))
        else:
            key = exp.n_rows + acked.limit - 1 - rank
            cols, at = (reads.order_cols, key) if key < exp.n_rows \
                else (new, key - exp.n_rows)
            conn.request("GET", "/variant/" + traffic_inserts.ident(
                exp.chromosomes, cols, at))
            reply = conn.getresponse()
            reads.append((key, reply.status, reply.read()))
    conn.close()


class Reads(list):
    """The reads, the inserts not acknowledged, and the stored rows'
    columns in recency order."""

    def __init__(self, exp, order):
        super().__init__()
        self.refused = []
        self.order_cols = {name: exp.kept[name][order]
                           for name in ("chrom", "pos", "ref", "alt")}


def _run_clients(port, exp, new, specs, acked, reads):
    threads = [threading.Thread(target=_client, args=(
        port, exp, new, draws, acked, reads)) for draws in specs]
    for thread in threads:
        thread.start()
    return threads


def _segments(mgr) -> dict:
    return {id(seg): seg for shard in mgr.current().store.shards.values()
            for seg in shard.segments}


@pytest.mark.parametrize("seed,insert_proportion", [
    (7, 0.05), (2147483659, 0.05), (3000000019, 0.3)])
def test_d_mix_against_the_reference_across_a_flush(
        pristine, tmp_path, monkeypatch, seed, insert_proportion):
    src, exp = pristine
    store_dir = str(tmp_path / "vdb")
    shutil.copytree(src, store_dir)
    monkeypatch.setenv("AVDB_MEMTABLE_FLUSH_S", "0.5")
    monkeypatch.setenv("AVDB_MEMTABLE_BYTES", "0")
    ops = 160
    order = traffic_inserts.recency(exp.n_rows, seed)
    new = traffic_inserts.new_records(exp, CLIENTS * ops, seed)
    model = ycsb_d_check.Model(exp, order, new)
    cdf = traffic_inserts.zipf_cdf(exp.n_rows, 0.99)
    specs = [traffic_inserts.client_draws(cdf, ops, insert_proportion, seed,
                                          c) for c in range(CLIENTS)]

    mgr = SnapshotManager(store_dir)
    mem = Memtable(width=mgr.current().store.width, store_dir=store_dir,
                   wal=WriteAheadLog(store_dir, "serve-w0"))
    residency = ResidencyManager(budget_bytes=1 << 30, upload=True,
                                 min_rows=MIN_ROWS, async_upload=False,
                                 plan_interval_s=0.0)
    server = build_aio_server(manager=MemtableSnapshots(mgr, mem), port=0,
                              residency=residency, memtable=mem)
    server.start_background()
    port = server.server_address[1]
    base = _segments(mgr)
    assert len(base) == len(CHROMOSOMES)
    try:
        acked, reads = Acked(len(new["pos"])), Reads(exp, order)
        # warm: every base segment resident before the mix starts
        warm = (np.zeros(64, np.int8), np.arange(64))
        deadline = time.monotonic() + 30
        while residency.stats()["resident"] < len(CHROMOSOMES):
            assert time.monotonic() < deadline, residency.stats()
            for thread in _run_clients(port, exp, new, [warm] * CLIENTS,
                                       acked, reads):
                thread.join()
        uploads = residency.stats()["uploads"]
        assert uploads == len(CHROMOSOMES)
        programs = runtime.compile_summary()["programs"]

        # the mix, with a flush in the middle of it
        half = [(k[: ops // 2], r[: ops // 2]) for k, r in specs]
        for thread in _run_clients(port, exp, new, half, acked, reads):
            thread.join()
        deadline = time.monotonic() + 30
        while mem.stats()["flushes"] == 0:
            assert time.monotonic() < deadline, mem.stats()
            time.sleep(0.05)
        rest = [(k[ops // 2:], r[ops // 2:]) for k, r in specs]
        for thread in _run_clients(port, exp, new, rest, acked, reads):
            thread.join()
    finally:
        server.shutdown()
        server.ctx.batcher.close()
    deadline = time.monotonic() + 30
    while mem._flushing:  # a flush the last upsert started
        assert time.monotonic() < deadline
        time.sleep(0.05)

    assert reads.refused == []
    for j in np.flatnonzero(acked.flags).tolist():
        assert model.insert(j)
    # every acknowledged insert's frame was fsync'd (the cell's
    # ``fsync_short``)
    assert mem.stats()["wal_fsyncs"] == len(model.acked)
    got = ycsb_d_check.compare_reads(model, reads)
    assert got["reads_wrong"] == 0 and got["responses_malformed"] == 0, got
    assert got["reads_compared"] == len(reads) >= CLIENTS * ops // 2
    assert sum(key >= exp.n_rows for key, _s, _b in reads) > 0
    # the flush kept every base segment, by identity, and uploaded none
    after = _segments(mgr)
    assert all(after.get(key) is seg for key, seg in base.items())
    assert len(after) > len(base)
    assert mgr.stats()["segments_reused"] >= len(CHROMOSOMES)
    assert residency.stats()["uploads"] == uploads
    assert runtime.compile_summary()["programs"] == programs

    # durable: the committed store and the WAL, read by a fresh replay
    store = VariantStore.load(store_dir, readonly=True)
    fresh = Memtable(width=store.width, store_dir=store_dir,
                     wal=WriteAheadLog(store_dir, "serve-w0"))
    fresh.replay(store)
    engine = QueryEngine(MemtableSnapshots(StaticSnapshots(store), fresh),
                         region_cache_size=0)
    ids = [model.ident(exp.n_rows + j) for j in model.acked]
    records = engine.lookup_many(ids)
    assert [json.loads(r) if r else None for r in records] == [
        ycsb_d_check.insert_record(exp.chromosomes, new, j)
        for j in model.acked]

    # a compaction rewrites the groups: a refresh reads them anew, whole
    report = compact_store(store_dir)
    assert report["status"] == "compacted", report
    assert mgr.refresh()
    compacted = _segments(mgr)
    assert not set(compacted) & set(after)
    assert mgr.current().store.n == exp.n_rows + mem.stats()["flush_rows"]
