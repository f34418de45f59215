"""Concurrent point readers, co-batched onto the device probe.

The deployment is YCSB workload C on the variant store: N closed-loop
readers, one id a request, Zipf 0.99.  The batcher coalesces them into
microbatches of whatever size the moment gives; these tests hold that

- every reply is its own request's record however it was co-batched
  (against the scalar oracle, ``engine.render_variant``), with co-batching,
  render-cache hits, columnar misses and a group's lone miss all seen;
- once the segments are reported resident no microbatch size compiles a
  program (``compile.programs`` constant), and the padding is counted;
- the padded device probe and the host twin agree on ``(found, index)``;
- a segment is reported resident only after its upload and its probe
  programs have landed;
- the spans and counters the benchmark reads are there: ``queue`` and
  ``kind="point"`` on ``/metrics``, ``avdb.serve.batch`` on the profiler's
  clock around a drain of either batcher.

The device probe is forced the way ``test_serve_residency`` forces it: the
latch that a CPU backend turns off is set, and the residency manager
uploads at any segment size.
"""

from __future__ import annotations

import contextlib
import glob
import http.client
import io
import json
import threading

import numpy as np
import pytest

from annotatedvdb_tpu.io.synth import write_synth_vcf
from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.serve import (
    QueryEngine,
    ResidencyManager,
    StaticSnapshots,
)
from annotatedvdb_tpu.serve.aio import build_aio_server
from annotatedvdb_tpu.serve.engine import render_variant
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store import variant_store
from annotatedvdb_tpu.store.variant_store import (
    DEVICE_QUERY_FLOOR,
    combined_key,
    probe_query_capacity,
    probe_stats,
)
from annotatedvdb_tpu.types import encode_allele_array
from annotatedvdb_tpu.utils import runtime
from conftest import BatcherOnLoop

CHROMOSOMES = ("1", "2", "22")
RECORDS = 3000
SEED = 2147483693
THETA = 0.99
MAX_BATCH = 256


@pytest.fixture(scope="module")
def forced_device():
    """Resident segments really ride ``_probe_device`` on the CPU backend."""
    patch = pytest.MonkeyPatch()
    patch.setattr(variant_store, "_DEVICE_LOOKUP_OK", True)
    runtime._watch_compiles()
    yield
    patch.undo()


@pytest.fixture(scope="module")
def store_dir(tmp_path_factory, forced_device):
    """A few thousand generated rows over three chromosomes, loaded and
    compacted through the program's own entry points, as a deployment does
    before it serves: one segment a chromosome."""
    from annotatedvdb_tpu.cli import doctor, load_vcf

    tmp = tmp_path_factory.mktemp("cobatch")
    vcf = tmp / "in.vcf"
    write_synth_vcf(str(vcf), RECORDS, SEED, CHROMOSOMES)
    rc = load_vcf.main(["--fileName", str(vcf), "--storeDir",
                        str(tmp / "vdb"), "--commit", "--commitAfter", "512",
                        "--logFilePath", str(tmp / "load.log")])
    assert rc == 0
    with contextlib.redirect_stdout(io.StringIO()) as out:
        rc = doctor.main(["compact", "--storeDir", str(tmp / "vdb"),
                          "--json"])
    report = json.loads(out.getvalue())
    assert rc == 0 and report["rows_dropped"] == 0, report
    return str(tmp / "vdb")


@pytest.fixture(scope="module")
def oracle(store_dir):
    """{id: the scalar renderer's text}, by walking every row of the store
    (no probe, no batch, no cache), and the ids by popularity rank."""
    store = VariantStore.load(store_dir, readonly=True)
    records = {}
    for code, shard in store.shards.items():
        for gid in range(shard.n):
            text = render_variant(shard, code, gid)
            records[json.loads(text)["metaseq_id"]] = text
    assert len(records) == store.n >= RECORDS
    assert {i.split(":")[0] for i in records} == set(CHROMOSOMES)
    ranked = np.random.default_rng(SEED).permutation(sorted(records))
    weights = np.arange(1, len(ranked) + 1, dtype=np.float64) ** -THETA
    return records, ranked, np.cumsum(weights / weights.sum())


def _zipf_ids(oracle, seed: int, n: int) -> list:
    _records, ranked, cdf = oracle
    rng = np.random.default_rng([SEED, seed])
    return ranked[np.searchsorted(cdf, rng.random(n))].tolist()


def _manager(**kw) -> ResidencyManager:
    kw.setdefault("async_upload", False)
    return ResidencyManager(budget_bytes=1 << 30, upload=True, min_rows=1,
                            plan_interval_s=0.0, **kw)


@pytest.fixture
def server(store_dir):
    """A fresh aio server a test (an empty render cache each time); the
    compiled programs are the process's and carry over."""
    srv = build_aio_server(store_dir=store_dir, port=0,
                           residency=_manager(), max_batch=MAX_BATCH)
    srv.start_background()
    try:
        yield srv
    finally:
        srv.shutdown()
        srv.ctx.batcher.close()


def _get(conn, path: str):
    conn.request("GET", path)
    response = conn.getresponse()
    return response.status, response.read().decode()


def _stats(port: int) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        return json.loads(_get(conn, "/stats")[1])
    finally:
        conn.close()


def _make_resident(port: int, oracle) -> dict:
    """One lone read a chromosome until every candidate is resident."""
    records = oracle[0]
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        for label in CHROMOSOMES:
            ident = next(i for i in records if i.startswith(f"{label}:"))
            assert _get(conn, f"/variant/{ident}") == (200, records[ident])
    finally:
        conn.close()
    stats = _stats(port)
    residency = stats["residency"]
    assert residency["resident"] == residency["candidates"] \
        == len(CHROMOSOMES)
    return stats


# ---------------------------------------------------------------------------
# every reply its own, however it was co-batched


@pytest.mark.parametrize("readers", [2, 8, 32])
def test_every_reply_is_its_own_under_cobatching(server, oracle, readers):
    records = oracle[0]
    port = server.server_address[1]
    before = _make_resident(port, oracle)
    per_reader = max(1280 // readers, 60)
    wrong: list = []
    start = threading.Barrier(readers)

    def reader(k: int):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            start.wait()
            for ident in _zipf_ids(oracle, k, per_reader):
                status, body = _get(conn, f"/variant/{ident}")
                if status != 200 or body != records[ident]:
                    wrong.append((ident, status, body))
        except Exception as err:  # a dead reader must fail the test
            wrong.append((k, "reader died", repr(err)))
        finally:
            conn.close()

    threads = [threading.Thread(target=reader, args=(k,))
               for k in range(readers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not wrong, wrong[:3]

    after = _stats(port)

    def grew(path: str) -> int:
        a, b = after, before
        for key in path.split("."):
            a, b = a[key], b[key]
        return a - b

    sent = readers * per_reader
    assert grew("batcher.queries") == sent
    # co-batching happened: fewer drains than reads
    assert grew("batcher.queries") / grew("batcher.batches") > 1
    # hits, columnar misses and a group's lone miss all occurred
    assert grew("render_cache.hits") > 0
    assert grew("render_cache.misses") > 0
    assert grew("render_batch.rows") > 0
    assert grew("render_batch.scalar_rows") > 0
    assert grew("render_cache.hits") + grew("render_cache.misses") == sent
    # every id went through the device probe, padding counted, no compile
    assert grew("device_lookup.device_queries") == sent
    assert grew("device_lookup.padded_queries") \
        >= grew("device_lookup.device_queries")
    assert grew("compile.programs") == 0
    assert after["residency"]["resident"] == before["residency"]["resident"]


# ---------------------------------------------------------------------------
# no program compiled once resident, at any microbatch size


@pytest.fixture(scope="module")
def resident_engine(store_dir, oracle):
    store = VariantStore.load(store_dir, readonly=True)
    manager = _manager(max_batch=MAX_BATCH)
    engine = QueryEngine(StaticSnapshots(store), registry=MetricsRegistry(),
                         residency=manager)
    records = oracle[0]
    for label in CHROMOSOMES:  # touch -> plan -> upload -> warm, in line
        ident = next(i for i in records if i.startswith(f"{label}:"))
        assert engine.lookup_many([ident]) == [records[ident]]
    stats = manager.stats()
    assert stats["resident"] == stats["candidates"] == len(CHROMOSOMES)
    return engine


@pytest.mark.parametrize("sizes", [range(1, 9), range(9, 33),
                                   range(33, 129), range(129, 257)],
                         ids=["1-8", "9-32", "33-128", "129-256"])
@pytest.mark.parametrize("spread", ["one_chromosome", "all_chromosomes"])
def test_no_microbatch_size_compiles_once_resident(resident_engine, oracle,
                                                   sizes, spread):
    """A drain is one ``lookup_many`` of the ids that happened to arrive:
    every size 1..max_batch, in one chromosome group or spread over all."""
    records = oracle[0]
    pool = [i for i in sorted(records)
            if spread == "all_chromosomes" or i.startswith("2:")]
    rng = np.random.default_rng([SEED, sizes.start])
    programs = runtime.compile_summary()["programs"]
    stats0 = dict(probe_stats)
    asked = 0
    for size in sizes:
        ids = rng.choice(pool, size=size, replace=False).tolist()
        assert resident_engine.lookup_many(ids) == [records[i] for i in ids]
        asked += size
    assert runtime.compile_summary()["programs"] == programs
    queries = probe_stats["device_queries"] - stats0["device_queries"]
    padded = probe_stats["padded_queries"] - stats0["padded_queries"]
    probes = probe_stats["device_probes"] - stats0["device_probes"]
    assert queries == asked  # one segment a chromosome: one probe a group
    assert padded >= queries and padded % DEVICE_QUERY_FLOOR == 0
    assert padded <= probes * probe_query_capacity(sizes[-1])


def test_probe_capacities_are_decided_in_one_place():
    floor = DEVICE_QUERY_FLOOR
    assert [probe_query_capacity(n) for n in (0, 1, 2, floor - 1, floor)] \
        == [floor] * 5
    # above the floor, and every bulk probe: the power-of-two buckets
    assert [probe_query_capacity(n) for n in (33, 64, 255, 256, 257, 1536,
                                              2048, 2049)] \
        == [64, 64, 256, 256, 512, 2048, 2048, 4096]


@pytest.mark.parametrize("top", [1, 32, 33, 256, 257])
def test_warm_runs_every_capacity_a_microbatch_can_take(big_segment,
                                                        monkeypatch, top):
    _width, seg = big_segment
    ran = []
    monkeypatch.setattr(
        variant_store.Segment, "_probe_device",
        lambda self, pos, *rest, dev=None: ran.append((pos.shape[0], dev)))
    seg.warm_device_probe(top, seg._device)
    assert [cap for cap, _dev in ran] \
        == sorted({probe_query_capacity(n) for n in range(1, top + 1)})
    assert all(dev is seg._device for _cap, dev in ran)


# ---------------------------------------------------------------------------
# the padded probe equals the host twin


@pytest.fixture(scope="module")
def big_segment(store_dir, forced_device):
    store = VariantStore.load(store_dir, readonly=True)
    code, shard = max(store.shards.items(), key=lambda kv: kv[1].n)
    seg = max(shard.segments, key=lambda s: s.n)
    seg._ensure_device_cache()
    return store.width, seg


@pytest.mark.parametrize("size", [1, 2, 3, 31, 32, 33, 255, 256])
def test_padded_probe_equals_host_twin(big_segment, size):
    width, seg = big_segment
    rng = np.random.default_rng([SEED, size])
    rows = rng.choice(seg.n, size=size, replace=size > seg.n)
    pos = seg.cols["pos"][rows].copy()
    refs, alts = [], []
    for j in rows.tolist():
        refs.append(bytes(seg.ref[j][: seg.cols["ref_len"][j]]).decode())
        alts.append(bytes(seg.alt[j][: seg.cols["alt_len"][j]]).decode())
    # every third query names a row that is not there: a real position
    # with alleles the store cannot hold beside it, or a position past
    # the segment's last
    for k in range(0, size, 3):
        if k % 2:
            pos[k] = int(seg.cols["pos"][-1]) + 1 + k
        else:
            refs[k], alts[k] = "ACGTN", "NTGCA"
    ref, ref_len = encode_allele_array(refs, width)
    alt, alt_len = encode_allele_array(alts, width)
    h = identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts)
    qkey = combined_key(pos, h)
    probes = probe_stats["device_probes"]
    found_d, index_d = seg.probe(qkey, pos, h, ref, alt, ref_len, alt_len)
    assert probe_stats["device_probes"] == probes + 1  # the device answered
    found_h, index_h = seg.probe(qkey, pos, h, ref, alt, ref_len, alt_len,
                                 host_only=True)
    assert probe_stats["device_probes"] == probes + 1
    assert found_d.dtype == found_h.dtype and index_d.dtype == index_h.dtype
    assert found_d.tolist() == found_h.tolist()
    assert index_d.tolist() == index_h.tolist()
    absent = set(range(0, size, 3))
    assert [k for k in range(size) if not found_h[k]] == sorted(absent)
    present = [k for k in range(size) if k not in absent]
    assert index_h[present].tolist() == rows[present].tolist()


# ---------------------------------------------------------------------------
# resident means landed: uploaded, and its probe programs compiled


def test_resident_is_reported_after_upload_and_warm(store_dir, oracle,
                                                    monkeypatch):
    store = VariantStore.load(store_dir, readonly=True)
    manager = _manager(async_upload=True, max_batch=MAX_BATCH)
    engine = QueryEngine(StaticSnapshots(store), residency=manager)
    entered, release = threading.Event(), threading.Event()
    warmed: list = []
    warm = variant_store.Segment.warm_device_probe

    def slow_warm(seg, max_queries, dev):
        # the upload came first, and no request can see the copy yet
        assert dev is not None and seg._device is None
        entered.set()
        assert release.wait(30)
        warm(seg, max_queries, dev)
        warmed.append(max_queries)

    monkeypatch.setattr(variant_store.Segment, "warm_device_probe", slow_warm)
    records = oracle[0]
    ident = next(i for i in records if i.startswith("22:"))
    assert engine.lookup_many([ident]) == [records[ident]]
    assert entered.wait(30)
    during = manager.stats()
    # the budget already counts the segment; nobody is told it is resident
    assert during["resident"] == 0 and during["resident_bytes"] > 0
    assert engine.lookup_many([ident]) == [records[ident]]  # still answers
    release.set()
    manager._uploader.shutdown(wait=True)
    after = manager.stats()
    assert after["resident"] >= 1
    assert warmed and set(warmed) == {MAX_BATCH}


# ---------------------------------------------------------------------------
# what the benchmark reads is there


def _series(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def test_point_reads_observe_queue_and_handler_on_the_fast_path(server,
                                                                oracle):
    records = oracle[0]
    port = server.server_address[1]
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        before = _series(_get(conn, "/metrics")[1])
        ids = _zipf_ids(oracle, 99, 12)
        for ident in ids:  # http.client's plain GET takes aio's fast path
            assert _get(conn, f"/variant/{ident}") == (200, records[ident])
        after = _series(_get(conn, "/metrics")[1])
    finally:
        conn.close()
    for series in ('avdb_stage_seconds_count{stage="queue"}',
                   'avdb_stage_seconds_count{stage="device"}',
                   'avdb_query_seconds_count{kind="point"}'):
        assert after[series] - before.get(series, 0) == len(ids), series
    for series in ('avdb_stage_seconds_sum{stage="queue"}',
                   'avdb_query_seconds_sum{kind="point"}'):
        assert after[series] > before.get(series, 0), series


def _batch_spans(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    assert len(paths) == 1
    spans = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [dict(e.stats) for e in line.events
                          if e.name == "avdb.serve.batch"]
    return spans


@pytest.mark.parametrize("front", ["server", "filled_batch"])
def test_a_drain_is_a_span_on_the_profilers_clock(store_dir, oracle, front,
                                                  tmp_path):
    import jax

    records = oracle[0]
    ids = [next(i for i in records if i.startswith(f"{label}:"))
           for label in CHROMOSOMES]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    if front == "server":
        srv = build_aio_server(store_dir=store_dir, port=0)
        srv.start_background()
        conn = http.client.HTTPConnection(
            "127.0.0.1", srv.server_address[1], timeout=30)
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for ident in ids:
                assert _get(conn, f"/variant/{ident}") \
                    == (200, records[ident])
        finally:
            jax.profiler.stop_trace()
            conn.close()
            srv.shutdown()
            srv.ctx.batcher.close()
        spans = _batch_spans(str(tmp_path))
        assert len(spans) == len(ids)
        assert all((s["n"], s["groups"]) == (1, 1) for s in spans)
    else:
        engine = QueryEngine(StaticSnapshots(
            VariantStore.load(store_dir, readonly=True)))
        batcher = BatcherOnLoop(engine, max_batch=len(ids), max_wait_s=5.0)

        async def one_turn(b):
            import asyncio

            return await asyncio.gather(*[b.submit_future(i) for i in ids])

        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            assert batcher.run(one_turn) == [records[i] for i in ids]
        finally:
            jax.profiler.stop_trace()
            batcher.close()
        # the batch filled, so the three ids left in one drain
        assert _batch_spans(str(tmp_path)) \
            == [{"n": len(ids), "groups": len(CHROMOSOMES)}]
