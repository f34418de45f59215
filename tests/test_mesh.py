"""Mesh-native AVDB battery: the multiprocess-CPU mesh suite.

``tests/conftest.py`` forces ``--xla_force_host_platform_device_count=8``,
so every test here runs against a REAL 8-device host mesh — the same
device topology a v5e-8 slice presents, minus the silicon.  The contract
under test is byte-identity: the mesh-sharded answers (load, point, bulk,
region, regions, the annotate kernel) must equal the single-device
answers bit for bit, because the mesh only moves WHERE rows compute —
never what they compute.  Placement, knob grammar, per-device residency
budgets, the manifest's advisory placement block, and the doctor/status
surfaces ride along.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.parallel import mesh as meshlib
from annotatedvdb_tpu.serve import (
    DeviceBreaker,
    MeshExecutor,
    QueryEngine,
    SnapshotManager,
    StaticSnapshots,
    serve_mesh_executor,
)
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.variant_store import RawJson
from annotatedvdb_tpu.types import (
    NUM_CHROMOSOMES,
    chromosome_label,
    encode_allele_array,
)
from conftest import bulk_envelope, start_server, stop_server

WIDTH = 8
CHROMS = (1, 8, 23)
BASES = ("A", "C", "G", "T")


@pytest.fixture(autouse=True)
def _fresh_mesh_cache():
    meshlib.reset_global_mesh()
    yield
    meshlib.reset_global_mesh()


# ---------------------------------------------------------------------------
# synthetic multi-chromosome store (shadowed duplicate + long-allele tail)


def _append(shard, rows):
    refs = [r["ref"] for r in rows]
    alts = [r["alt"] for r in rows]
    ref, ref_len = encode_allele_array(refs, WIDTH)
    alt, alt_len = encode_allele_array(alts, WIDTH)
    h = identity_hashes(WIDTH, ref, alt, ref_len, alt_len, refs, alts)
    cols = {
        "pos": np.asarray([r["pos"] for r in rows], np.int32),
        "h": h, "ref_len": ref_len, "alt_len": alt_len,
    }
    ann = {
        "cadd_scores": [
            {"CADD_phred": float(3 + (r["pos"] % 17))}
            if r["pos"] % 3 == 0 else None for r in rows
        ],
        "vep_output": [
            RawJson(f'{{"p":{r["pos"]}}}') if r["pos"] % 5 == 0 else None
            for r in rows
        ],
    }
    long_alleles = [
        (r["ref"], r["alt"])
        if len(r["ref"]) > WIDTH or len(r["alt"]) > WIDTH else None
        for r in rows
    ]
    shard.append(cols, ref, alt, annotations=ann,
                 long_alleles=long_alleles)


def _build_store():
    store = VariantStore(width=WIDTH)
    truth = []
    for code in CHROMS:
        shard = store.shard(code)
        for run, base in enumerate((500, 60_000)):
            rows = []
            for i in range(25):
                pos = base + 977 * i
                k = (i + run) % 4
                ref = BASES[k]
                alt = BASES[(k + 1) % 4] if i % 4 else ref + "TTG"
                if i == 20:  # long-allele tail: full-string identity
                    ref = "A" * (WIDTH + 4)
                    alt = "G"
                rows.append({"chrom": code, "pos": pos, "ref": ref,
                             "alt": alt})
            _append(shard, rows)
            truth.extend(rows)
    # a shadowed duplicate: same identity in a NEWER chr8 segment —
    # first-wins must keep the older row on every path
    dup = dict(truth[0], chrom=8)
    dup = next(r for r in truth if r["chrom"] == 8)
    _append(store.shard(8), [dict(dup)])
    return store, truth


def _ids(truth):
    ids = [
        f"{chromosome_label(r['chrom'])}:{r['pos']}:{r['ref']}:{r['alt']}"
        for r in truth
    ]
    ids += ["1:999999999:A:T", "11:50:G:C", "8:505:T:G"]  # misses
    return ids


SPECS = ["8:1-100000", "1:400-2000", "X:59000-90000", "11:1-5000",
         "8:490-600", "1:1-60000000", "8:60000-60000"]


@pytest.fixture(scope="module")
def served():
    store, truth = _build_store()
    snaps = StaticSnapshots(store)
    plain = QueryEngine(snaps, region_cache_size=0)
    breaker = DeviceBreaker()
    meshed = QueryEngine(
        snaps, region_cache_size=0, breaker=breaker,
        mesh=MeshExecutor(meshlib.global_mesh(), breaker=breaker,
                          bulk_min=0),
    )
    return store, truth, plain, meshed


# ---------------------------------------------------------------------------
# mesh authority: shape grammar, sizing, placement


def test_mesh_shape_env_grammar(monkeypatch):
    monkeypatch.setenv("AVDB_MESH_SHAPE", "2x4")
    with pytest.raises(ValueError, match="device count"):
        meshlib.mesh_shape_from_env()
    monkeypatch.setenv("AVDB_MESH_SHAPE", "0")
    with pytest.raises(ValueError, match=">= 1"):
        meshlib.mesh_shape_from_env()
    monkeypatch.setenv("AVDB_MESH_SHAPE", "64")
    with pytest.raises(ValueError, match="exceeds"):
        meshlib.global_mesh()
    monkeypatch.delenv("AVDB_MESH_SHAPE")
    assert meshlib.mesh_shape_from_env() is None


def test_global_mesh_sizing(monkeypatch):
    import jax

    mesh = meshlib.global_mesh()
    assert mesh is not None and mesh.devices.size == len(jax.devices())
    monkeypatch.setenv("AVDB_MESH_SHAPE", "4")
    meshlib.reset_global_mesh()
    assert meshlib.global_mesh().devices.size == 4
    # --maxWorkers-style limit clamps further
    assert meshlib.global_mesh(limit=2).devices.size == 2
    monkeypatch.setenv("AVDB_MESH_SHAPE", "1")
    meshlib.reset_global_mesh()
    assert meshlib.global_mesh() is None  # single device = no mesh


def test_chromosome_placement_covers_every_code():
    from annotatedvdb_tpu.parallel.distributed import chromosome_owner_table

    placement = meshlib.chromosome_placement(8)
    assert set(placement) == set(range(1, NUM_CHROMOSOMES + 1))
    assert set(placement.values()) == set(range(8))
    # serving placement and loader routing MUST be the same table
    table = chromosome_owner_table(8)
    for code, dev in placement.items():
        assert table[code] == dev
    per_dev = meshlib.groups_per_device(placement, placement.keys())
    assert sum(len(v) for v in per_dev.values()) == NUM_CHROMOSOMES


def test_placement_hint_single_device_is_none(monkeypatch):
    monkeypatch.delenv("AVDB_MESH_SHAPE", raising=False)
    assert meshlib.placement_hint() is None
    monkeypatch.setenv("AVDB_MESH_SHAPE", "1")
    assert meshlib.placement_hint() is None
    monkeypatch.setenv("AVDB_MESH_SHAPE", "4")
    hint = meshlib.placement_hint()
    assert hint["devices"] == 4
    assert set(hint["groups"].values()) <= set(range(4))


# ---------------------------------------------------------------------------
# manifest placement block + snapshot + doctor status


def test_manifest_placement_roundtrip(tmp_path, monkeypatch):
    store, _truth = _build_store()
    plain_dir = str(tmp_path / "plain")
    store.save(plain_dir)
    with open(plain_dir + "/manifest.json") as f:
        assert "mesh_placement" not in json.load(f)

    monkeypatch.setenv("AVDB_MESH_SHAPE", "4")
    mesh_dir = str(tmp_path / "meshed")
    store.save(mesh_dir)
    with open(mesh_dir + "/manifest.json") as f:
        block = json.load(f)["mesh_placement"]
    assert block["devices"] == 4
    assert set(block["groups"]) == {
        chromosome_label(c) for c in range(1, NUM_CHROMOSOMES + 1)
    }
    loaded = VariantStore.load(mesh_dir, readonly=True)
    assert loaded.mesh_placement == block
    # the snapshot carries the placement map
    manager = SnapshotManager(mesh_dir)
    assert manager.current().placement == block
    # and the single-device store's snapshot carries none
    assert SnapshotManager(plain_dir).current().placement is None


def test_doctor_status_mesh_block(tmp_path, monkeypatch):
    from annotatedvdb_tpu.store.maintenance import store_status

    store, _truth = _build_store()
    monkeypatch.setenv("AVDB_MESH_SHAPE", "4")
    monkeypatch.setenv("AVDB_SERVE_HBM_BUDGET", "64m")
    store_dir = str(tmp_path / "status_store")
    store.save(store_dir)
    report = store_status(store_dir)
    mesh = report["mesh"]
    assert mesh["devices"] == 4
    assert sum(mesh["groups_per_device"].values()) == len(CHROMS)
    assert mesh["per_device_budget_bytes"] == (64 << 20) // 4
    assert all(v > 0 for v in
               mesh["est_resident_bytes_per_device"].values())
    # single-device resolution: no mesh block
    monkeypatch.delenv("AVDB_MESH_SHAPE")
    plain_dir = str(tmp_path / "status_plain")
    store.save(plain_dir)
    assert store_status(plain_dir)["mesh"] is None


# ---------------------------------------------------------------------------
# knob grammar + executor gating


def test_serve_mesh_knob_grammar(monkeypatch):
    from annotatedvdb_tpu.serve import mesh_exec

    monkeypatch.setenv("AVDB_SERVE_MESH", "yes")
    with pytest.raises(ValueError, match="AVDB_SERVE_MESH"):
        mesh_exec.resolve_serve_mesh()
    monkeypatch.setenv("AVDB_MESH_BULK_MIN", "many")
    with pytest.raises(ValueError, match="AVDB_MESH_BULK_MIN"):
        mesh_exec.resolve_mesh_bulk_min()
    monkeypatch.setenv("AVDB_SERVE_MESH", "0")
    assert serve_mesh_executor() is None
    # auto on a CPU backend: the per-segment host path stays production
    monkeypatch.setenv("AVDB_SERVE_MESH", "auto")
    assert serve_mesh_executor() is None
    # forced: the executor engages on the virtual mesh
    monkeypatch.setenv("AVDB_SERVE_MESH", "1")
    monkeypatch.setenv("AVDB_MESH_BULK_MIN", "16")
    ex = serve_mesh_executor()
    assert ex is not None and ex.n_devices == 8 and ex.bulk_min == 16


# ---------------------------------------------------------------------------
# byte-identity: point / bulk


def test_bulk_and_point_parity(served):
    _store, truth, plain, meshed = served
    ids = _ids(truth)
    want = plain.lookup_many(ids)
    got = meshed.lookup_many(ids)
    assert got == want
    assert sum(1 for v in want if v is not None) == len(truth)
    # the sharded call actually ran (not a silent fallback)
    assert meshed.mesh._bulk is not None
    # single point rides the same path
    assert meshed.lookup(ids[0]) == plain.lookup(ids[0])
    assert meshed.lookup("11:50:G:C") is None


def test_bulk_min_gates_small_batches(served):
    store, truth, plain, _meshed = served
    breaker = DeviceBreaker()
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, breaker=breaker,
        mesh=MeshExecutor(meshlib.global_mesh(), breaker=breaker,
                          bulk_min=10_000),
    )
    ids = _ids(truth)[:8]
    assert engine.lookup_many(ids) == plain.lookup_many(ids)
    assert engine.mesh._bulk is None  # never dispatched


def test_budget_tombstone_falls_back(served):
    store, truth, plain, _meshed = served
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0,
        mesh=MeshExecutor(meshlib.global_mesh(), bulk_min=0,
                          budget_bytes=16),  # nothing fits
    )
    ids = _ids(truth)
    assert engine.lookup_many(ids) == plain.lookup_many(ids)
    assert engine.mesh._bulk.store is None  # tombstoned, not resident


# ---------------------------------------------------------------------------
# byte-identity: region / regions


def test_regions_parity(served):
    _store, _truth, plain, meshed = served
    for kwargs in (
        {},
        {"min_cadd": 5.0},
        {"limit": 3},
        {"limit": 0},                      # count-only
        {"tokenize": True},
        {"min_cadd": 4.0, "limit": 2, "tokenize": True},
    ):
        want = plain.regions_serve(SPECS, **kwargs).assemble()
        got = meshed.regions_serve(SPECS, **kwargs).assemble()
        assert got == want, kwargs
    for spec in SPECS:
        assert meshed.region(spec) == plain.region(spec)


def test_parity_across_generation_swap(tmp_path, monkeypatch):
    """The mesh state is generation-keyed: a loader commit must rebuild
    it, and post-swap answers stay byte-identical to the single-device
    path (stale resident state would serve pre-commit bytes)."""
    store, truth = _build_store()
    store_dir = str(tmp_path / "swap_store")
    store.save(store_dir)
    manager = SnapshotManager(store_dir)
    plain = QueryEngine(manager, region_cache_size=0)
    meshed = QueryEngine(
        manager, region_cache_size=0,
        mesh=MeshExecutor(meshlib.global_mesh(), bulk_min=0,
                          rebuild_min_s=0.0),
    )
    ids = _ids(truth) + ["8:777777:T:A"]
    assert meshed.lookup_many(ids) == plain.lookup_many(ids)
    gen1 = meshed.mesh._bulk.generation

    # a loader commit adds a row
    writer = VariantStore.load(store_dir)
    _append(writer.shard(8), [{"chrom": 8, "pos": 777_777, "ref": "T",
                               "alt": "A"}])
    writer.save(store_dir)
    assert manager.refresh() is True

    want = plain.lookup_many(ids)
    got = meshed.lookup_many(ids)
    assert got == want
    assert want[-1] is not None  # the new row resolved on both paths
    assert meshed.mesh._bulk.generation > gen1
    assert plain.regions_serve(SPECS).assemble() \
        == meshed.regions_serve(SPECS).assemble()


def test_rebuild_rate_limit_declines_churning_generations(served):
    """A generation churning faster than ``rebuild_min_s`` (the live
    write path mints one per memtable epoch) must NOT re-sort and
    re-upload the store per epoch: the executor declines and the
    byte-identical single-device path serves until the window lapses."""
    store, truth, plain, _m = served
    snaps = StaticSnapshots(store)
    engine = QueryEngine(
        snaps, region_cache_size=0,
        mesh=MeshExecutor(meshlib.global_mesh(), bulk_min=0,
                          rebuild_min_s=3600.0),
    )
    ids = _ids(truth)
    want = plain.lookup_many(ids)
    assert engine.lookup_many(ids) == want
    built = engine.mesh._bulk
    assert built is not None and built.generation == 1
    # the "commit": a new generation over the same rows
    engine.snapshots = StaticSnapshots(store, generation=2)
    assert engine.lookup_many(ids) == want  # correct bytes, no rebuild
    assert engine.mesh._bulk is built       # state untouched (declined)


def test_builders_hand_mesh_the_per_device_budget(tmp_path, monkeypatch):
    """The mesh state budget rides the residency manager's already-split
    per-device share — never the raw AVDB_SERVE_HBM_BUDGET env (a fleet
    worker reading the env whole would overcommit HBM N-fold)."""
    from annotatedvdb_tpu.serve import ResidencyManager

    store, _truth = _build_store()
    store_dir = str(tmp_path / "budget_store")
    store.save(store_dir)
    monkeypatch.setenv("AVDB_SERVE_MESH", "1")
    monkeypatch.setenv("AVDB_SERVE_HBM_BUDGET", "8g")  # must be ignored
    residency = ResidencyManager(1 << 20)  # the worker's split share
    httpd = start_server(store_dir=store_dir, residency=residency)
    try:
        assert httpd.ctx.engine.mesh is not None
        assert httpd.ctx.engine.mesh.budget == 1 << 20
    finally:
        stop_server(httpd)
    # no residency manager = unmanaged mesh state, not env-budgeted
    httpd = start_server(store_dir=store_dir)
    try:
        assert httpd.ctx.engine.mesh.budget == 0
    finally:
        stop_server(httpd)


def test_mesh_bulk_keeps_residency_warm(served):
    """Mesh bulk traffic must keep feeding residency heat scores — the
    per-segment caches are what the single-device FALLBACK serves from
    (a decayed plan would evict them exactly when a tripped mesh needs
    them)."""
    from annotatedvdb_tpu.serve import ResidencyManager

    store, truth, _plain, _m = served
    residency = ResidencyManager(
        1 << 30, upload=False, min_rows=0, plan_interval_s=0.0,
    )
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, residency=residency,
        mesh=MeshExecutor(meshlib.global_mesh(), bulk_min=0),
    )
    engine.lookup_many(_ids(truth))
    assert engine.mesh._bulk is not None  # the mesh path really ran
    stats = residency.stats()
    assert stats["resident"] > 0  # touches fed the plan


# ---------------------------------------------------------------------------
# byte-identity over HTTP


def _get(port, path):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


def _post(port, path, payload):
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


def test_front_end_parity_mesh_vs_single_device(tmp_path, monkeypatch):
    """The server with the mesh FORCED answers byte-identically to
    itself without the mesh, across point/bulk/region/regions — the
    serving acceptance gate.  Oracle: a mesh-less ``QueryEngine`` over
    the same store called directly (``lookup_many``, ``region``,
    ``regions_serve(...).assemble()``)."""
    store, truth = _build_store()
    store_dir = str(tmp_path / "http_store")
    store.save(store_dir)
    ids = _ids(truth)[:40]
    paths = (
        [f"/variant/{ids[0]}", f"/variant/{ids[-1]}"]
        + [f"/region/{s}" for s in SPECS[:4]]
        + ["/region/8:490-600?minCadd=4.0&limit=3"]
    )
    plain = QueryEngine(
        StaticSnapshots(VariantStore.load(store_dir, readonly=True)),
        region_cache_size=0,
    )
    records = plain.lookup_many(ids)
    want = (
        [records[0], records[-1]]
        + [plain.region(s) for s in SPECS[:4]]
        + [plain.region("8:490-600", min_cadd=4.0, limit=3)]
        + [bulk_envelope(records),
           plain.regions_serve(SPECS, limit=5).assemble()]
    )
    bodies = {}
    for mesh_mode in ("0", "1"):
        monkeypatch.setenv("AVDB_SERVE_MESH", mesh_mode)
        monkeypatch.setenv("AVDB_MESH_BULK_MIN", "0")
        server = start_server(store_dir=store_dir)
        try:
            assert (server.ctx.engine.mesh is not None) \
                == (mesh_mode == "1")
            port = server.server_address[1]
            got = [_get(port, p) for p in paths]
            assert all(st == 200 for st, _body in got)
            out = [body for _s, body in got]
            st, bulk = _post(port, "/variants", {"ids": ids})
            assert st == 200
            out.append(bulk)
            st, regions = _post(port, "/regions",
                                {"regions": SPECS, "limit": 5})
            assert st == 200
            out.append(regions)
            bodies[mesh_mode] = out
        finally:
            stop_server(server)
    assert bodies["1"] == bodies["0"]
    # and the mesh path answers with the engine's own bytes
    assert bodies["1"] == want


# ---------------------------------------------------------------------------
# sharded load == single-device load (the mesh authority wired through
# the loader path; the deep parity battery lives in test_distributed_load)


def test_load_parity_via_global_mesh(tmp_path, monkeypatch):
    from annotatedvdb_tpu.loaders.vcf_loader import TpuVcfLoader
    from annotatedvdb_tpu.store import AlgorithmLedger

    lines = ["##fileformat=VCFv4.2",
             "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO"]
    rng = np.random.default_rng(5)
    pos = 1000
    for i in range(300):
        pos += int(rng.integers(1, 40))
        ref = BASES[int(rng.integers(0, 4))]
        alt = BASES[(BASES.index(ref) + 1 + int(rng.integers(0, 3))) % 4]
        if alt == ref:
            alt = BASES[(BASES.index(ref) + 1) % 4]
        lines.append(f"7\t{pos}\trs{i}\t{ref}\t{alt}\t.\t.\tRS={i}")
    vcf = tmp_path / "chr7.vcf"
    vcf.write_text("\n".join(lines) + "\n")

    def load(tag, mesh):
        store = VariantStore(width=16)
        ledger = AlgorithmLedger(str(tmp_path / f"ledger_{tag}.jsonl"))
        loader = TpuVcfLoader(store, ledger, mesh=mesh, batch_size=128,
                              log=lambda *a: None)
        loader.load_file(str(vcf), commit=True)
        return store

    s1 = load("single", mesh=None)
    monkeypatch.setenv("AVDB_MESH_SHAPE", "4")
    meshlib.reset_global_mesh()
    mesh = meshlib.global_mesh()
    assert mesh is not None and mesh.devices.size == 4
    s4 = load("mesh", mesh=mesh)
    sh1, sh4 = s1.shard(7), s4.shard(7)
    sh1.compact(), sh4.compact()
    assert sh1.n == sh4.n > 0
    for col in ("pos", "h", "ref_len", "alt_len", "bin_level", "leaf_bin"):
        np.testing.assert_array_equal(sh1.cols[col], sh4.cols[col],
                                      err_msg=col)
    np.testing.assert_array_equal(sh1.ref, sh4.ref)
    np.testing.assert_array_equal(sh1.alt, sh4.alt)


# ---------------------------------------------------------------------------
# residency: per-device budgets + placed uploads


def test_residency_places_uploads_per_device_budget():
    import jax

    from annotatedvdb_tpu.serve import ResidencyManager

    store, _truth = _build_store()
    snaps = StaticSnapshots(store)
    placement = meshlib.chromosome_placement(8)
    from annotatedvdb_tpu.serve.residency import device_cache_bytes

    seg_bytes = max(
        device_cache_bytes(seg, WIDTH)
        for shard in store.shards.values() for seg in shard.segments
    )
    manager = ResidencyManager(
        seg_bytes,  # per-device: exactly ONE segment fits per device
        upload=True, async_upload=False, min_rows=0, plan_interval_s=0.0,
        placement=placement, devices=jax.devices(),
    )
    manager.govern(snaps.current())
    # touch every chromosome: each group's hottest segment becomes
    # resident ON ITS PLACED DEVICE; per-device bytes never exceed budget
    for code, shard in store.shards.items():
        key = shard.segments[0].key
        manager.touch_window(shard, key[0], key[-1], 100)
    stats = manager.stats()
    assert stats["resident"] >= len(CHROMS) - 1
    per_dev = stats["per_device_bytes"]
    assert per_dev and all(v <= seg_bytes for v in per_dev.values())
    for code, shard in store.shards.items():
        for seg in shard.segments:
            if seg._device is not None:
                dev = next(iter(seg._device[0].devices()))
                assert dev == jax.devices()[placement[code]], code


# ---------------------------------------------------------------------------
# metrics + stats surfaces


def test_mesh_metrics_registered(served):
    from annotatedvdb_tpu.obs.metrics import MetricsRegistry

    store, truth, plain, _m = served
    registry = MetricsRegistry()
    breaker = DeviceBreaker(registry=registry)
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, breaker=breaker,
        mesh=MeshExecutor(meshlib.global_mesh(), registry=registry,
                          breaker=breaker, bulk_min=0),
    )
    ids = _ids(truth)
    assert engine.lookup_many(ids) == plain.lookup_many(ids)
    engine.regions_serve(SPECS)
    text = registry.render_prometheus()
    assert 'avdb_mesh_devices 8' in text
    assert 'avdb_mesh_dispatch_total{kind="bulk"} 1' in text
    assert 'avdb_mesh_dispatch_total{kind="spans"} 1' in text
    assert "avdb_mesh_resident_bytes" in text
    assert "avdb_mesh_groups_placed" in text
    stats = engine.mesh.stats()
    assert stats["devices"] == 8
    assert stats["resident_bytes"] > 0
    assert sum(stats["groups_per_device"].values()) == NUM_CHROMOSOMES
