"""Crash-safe online compaction (`store/compact.py` + `doctor compact`).

The byte-parity gate: a compacted store must answer point / bulk / region /
`/regions` BYTE-identically to the fragmented pre-compaction store — via
the engine, a brute-force per-row reference scan, and BOTH HTTP front ends
— while legacy (pre-compaction-era) stores keep loading unchanged.  Plus:
first-wins dedup, the v2 container (dictionary-coded alleles, compressed
JSONB sidecar), the out-of-core spill tier, online generation-swap
publication, cooperative preemption/cancellation, `doctor compact` CLI
contract (dry-run / --group / --maxBytes), fsck's compact-tmp handling,
and the compaction metrics registrations.
"""

import json
import os
import shutil
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import test_serve as ts
from annotatedvdb_tpu.store import (
    AlgorithmLedger,
    VariantStore,
    compact_store,
    plan_compaction,
)
from annotatedvdb_tpu.store.compact import _metrics, segment_spans
from annotatedvdb_tpu.store.fsck import fsck
from annotatedvdb_tpu.store.variant_store import Segment
from annotatedvdb_tpu.serve import QueryEngine, SnapshotManager
from annotatedvdb_tpu.utils import faults
from conftest import bulk_envelope, start_server, stop_server


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.reset("")


def _fragmented(store_dir: str):
    """The test_serve store (chr1/chr8/chrX): 3 disjoint segments each, one
    OVERLAPPING chr8 segment with a shadowed duplicate + an over-width
    long-allele row.  Saved segment-per-append, so the directory is a
    genuinely fragmented many-file store."""
    return ts._build_store(store_dir)


def _files(store_dir: str):
    return sorted(
        f for f in os.listdir(store_dir)
        if f.endswith(".npz") or f.endswith(".ann.jsonl")
    )


def _query_bytes(store_dir: str, truth: list) -> dict:
    """Every read surface's bytes from a FRESH engine on ``store_dir``:
    point (every truth row + misses), bulk, region (filters/limit), and a
    batched /regions panel."""
    manager = SnapshotManager(store_dir)
    engine = QueryEngine(manager, region_cache_size=0)
    out = {}
    out["points"] = [engine.lookup(ts._vid(r)) for r in truth]
    out["misses"] = [engine.lookup("8:499:A:G"), engine.lookup("9:1:A:C")]
    out["bulk"] = engine.lookup_many([ts._vid(r) for r in truth])
    out["regions_single"] = [
        engine.region(spec, min_cadd=mc, max_conseq_rank=mr, limit=lim)
        for spec, mc, mr, lim in (
            ("8:1-10000", None, None, None),
            ("8:1-3000000", 5.0, None, 64),
            ("1:100000-2500000", None, 10, None),
            ("X:1-999", None, None, 0),
        )
    ]
    batch = engine.regions_serve(
        ["8:1-10000", "8:400-700", "1:1-3000000"], limit=16
    )
    out["regions_batch"] = [p.assemble() for p in batch.pages]
    return out


def test_compaction_byte_parity_engine_and_brute(tmp_path):
    store_dir = str(tmp_path / "vdb")
    truth = _fragmented(store_dir)
    assert len(_files(store_dir)) > 6  # genuinely fragmented

    pre = _query_bytes(store_dir, truth)
    # brute-force reference scan of the PRE store (region text rebuilt row
    # by row, first-wins dedup applied by hand)
    pre_store = VariantStore.load(store_dir)
    brute_pre = ts._brute_region_text(pre_store, 1, 8, 1, 10000)

    report = compact_store(store_dir)
    assert report["status"] == "compacted"
    assert report["rows_dropped"] == 1  # the shadowed chr8 duplicate
    assert report["files_after"] == len(report["labels"]) == 3
    assert report["bytes_after"] < report["bytes_before"]

    post = _query_bytes(store_dir, truth)
    assert post == pre
    # the brute scan of the POST store reproduces the same region text
    post_store = VariantStore.load(store_dir)
    assert ts._brute_region_text(post_store, 1, 8, 1, 10000) == brute_pre
    # and the store is observably compact: one segment file pair per shard
    assert segment_spans(store_dir) == {"1": 1, "8": 1, "X": 1}
    assert fsck(store_dir, deep=True, log=lambda m: None)["exit_code"] == 0


def _collect_http(port: int, truth: list) -> list:
    """One response-bytes sample across every read route."""
    out = []
    for r in truth[:25] + [truth[-1]]:
        out.append(ts._get(port, f"/variant/{ts._vid(r)}")[:2])
    out.append(ts._get(port, "/variant/8:499:A:G")[:2])
    out.append(ts._get(port, "/region/8:1-10000?minCadd=5&limit=8")[:2])
    out.append(ts._get(port, "/region/1:100000-2500000?limit=0")[:2])
    ids = [ts._vid(r) for r in truth[:40]] + ["8:499:A:G"]
    for path, payload in (
        ("/variants", {"ids": ids}),
        ("/regions", {"regions": ["8:1-10000", "8:400-700"], "limit": 8}),
    ):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(payload).encode(), method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            out.append((resp.status, resp.read().decode()))
    return out


def _collect_engine(engine, truth: list) -> list:
    """What ``_collect_http`` must read, from the engine called directly."""
    def point(vid):
        record = engine.lookup(vid)
        if record is None:
            return 404, json.dumps(
                {"error": f"variant {vid!r} not in store"})
        return 200, record

    out = [point(ts._vid(r)) for r in truth[:25] + [truth[-1]]]
    out.append(point("8:499:A:G"))
    out.append((200, engine.region("8:1-10000", min_cadd=5.0, limit=8)))
    out.append((200, engine.region("1:100000-2500000", limit=0)))
    ids = [ts._vid(r) for r in truth[:40]] + ["8:499:A:G"]
    records = engine.lookup_many(ids)
    out.append((200, bulk_envelope(records)))
    out.append((200, engine.regions_serve(
        ["8:1-10000", "8:400-700"], limit=8).assemble()))
    return out


def test_compaction_byte_parity_over_http(tmp_path):
    """Pre- vs post-compaction responses of the server (fresh managers
    each side, so generation numbers agree).  Oracle: a ``QueryEngine``
    over the uncompacted store called directly (``_collect_engine``)."""
    pre_dir = str(tmp_path / "pre")
    truth = _fragmented(pre_dir)
    post_dir = str(tmp_path / "post")
    shutil.copytree(pre_dir, post_dir)
    assert compact_store(post_dir)["status"] == "compacted"

    def sample(store_dir):
        server = start_server(store_dir=store_dir)
        try:
            return _collect_http(server.server_address[1], truth)
        finally:
            stop_server(server)

    pre = sample(pre_dir)
    assert sample(post_dir) == pre
    # and the server agrees with the engine over the uncompacted store
    assert pre == _collect_engine(
        QueryEngine(SnapshotManager(pre_dir), region_cache_size=0), truth)


def test_legacy_fragmented_store_loads_unchanged(tmp_path):
    """A store that is never compacted keeps its exact multi-segment
    layout and content across load/save round trips — compaction support
    must not disturb the v1 path."""
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    files = _files(store_dir)
    store = VariantStore.load(store_dir)
    n = store.n
    segs = {c: len(s.segments) for c, s in store.shards.items()}
    assert max(segs.values()) > 1
    again = VariantStore.load(store_dir)
    assert again.n == n
    assert {c: len(s.segments) for c, s in again.shards.items()} == segs
    assert _files(store_dir) == files  # loading never rewrites


def test_compacted_sidecar_is_compressed_and_alleles_dict_coded(tmp_path):
    """The v2 container: zlib sidecar (0x78 lead byte) and dictionary-coded
    allele matrices when that shrinks them — verified by content parity
    plus the on-disk artifacts."""
    store_dir = str(tmp_path / "vdb")
    store = VariantStore(width=8)
    sh = store.shard(5)
    n = 600
    for k in range(3):
        cols = {
            "pos": np.arange(1000 + 50_000 * k, 1000 + 50_000 * k + n,
                             dtype=np.int32),
            "h": np.arange(n, dtype=np.uint32) + 11,
            "ref_len": np.full(n, 4, np.int32),
            "alt_len": np.full(n, 4, np.int32),
        }
        ref = np.zeros((n, 8), np.uint8)
        alt = np.zeros((n, 8), np.uint8)
        ref[:, :4] = [65, 67, 71, 84]  # ACGT — 1 unique row
        alt[:, :4] = [84, 71, 67, 65]
        sh.append_segment(Segment.build(
            cols, ref, alt,
            annotations={"other_annotation":
                         [{"k": int(i)} for i in range(n)]},
        ))
        sh._starts_cache = None
        store.save(store_dir)
    pre = VariantStore.load(store_dir)
    pre.shard(5).compact()
    pre_sig = (pre.shard(5).cols["pos"].tobytes(), pre.shard(5).ref.tobytes(),
               [pre.shard(5).get_ann("other_annotation", i)
                for i in range(0, 3 * n, 97)])

    report = compact_store(store_dir)
    assert report["status"] == "compacted"
    npz = [f for f in _files(store_dir) if f.endswith(".npz")]
    jsonl = [f for f in _files(store_dir) if f.endswith(".ann.jsonl")]
    assert len(npz) == 1 and len(jsonl) == 1
    with open(os.path.join(store_dir, npz[0]), "rb") as f:
        hdr = json.loads(f.readline())
    assert hdr["seg"] == 2
    assert "ref_dict" in hdr["names"] and "alt_dict" in hdr["names"]
    with open(os.path.join(store_dir, jsonl[0]), "rb") as f:
        assert f.read(1) == b"\x78"  # zlib magic, not '{'

    post = VariantStore.load(store_dir)
    post.shard(5).compact()
    post_sig = (post.shard(5).cols["pos"].tobytes(),
                post.shard(5).ref.tobytes(),
                [post.shard(5).get_ann("other_annotation", i)
                 for i in range(0, 3 * n, 97)])
    assert post_sig == pre_sig
    # deep-verify agrees with the compressed/coded integrity records
    assert fsck(store_dir, deep=True, log=lambda m: None)["exit_code"] == 0


def test_online_publication_through_snapshot_swap(tmp_path):
    """Compaction against a LIVE pinned generation: the pre-compaction
    snapshot keeps answering (its segment set is in memory; GC'd files
    don't matter), the swap publishes the compacted generation, and
    point/bulk answers are byte-identical across the swap."""
    store_dir = str(tmp_path / "vdb")
    truth = _fragmented(store_dir)
    manager = SnapshotManager(store_dir)
    engine = QueryEngine(manager, region_cache_size=0)
    vids = [ts._vid(r) for r in truth]
    pre_points = [engine.lookup(v) for v in vids]
    pre_gen = manager.current().generation

    report = compact_store(store_dir)
    assert report["status"] == "compacted"
    # the pinned (pre-compaction) generation still answers: its files are
    # gone from disk but the loaded segment set is immune to the GC
    assert [engine.lookup(v) for v in vids] == pre_points
    assert manager.current().generation == pre_gen

    assert manager.refresh() is True
    assert manager.current().generation == pre_gen + 1
    assert [engine.lookup(v) for v in vids] == pre_points


def test_cancel_aborts_cleanly(tmp_path):
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    files = _files(store_dir)
    report = compact_store(store_dir, cancel=lambda: True)
    assert report["status"] == "aborted"
    assert "cancel" in report["reason"]
    assert _files(store_dir) == files
    assert not [f for f in os.listdir(store_dir) if ".compact.tmp" in f]


def test_loader_commit_mid_pass_preempts(tmp_path, monkeypatch):
    """A loader commit between merge and swap must abort the pass (temps
    removed, the LOADER's generation intact) — the cooperative-preemption
    half of the online contract."""
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    committed = {"n": 0}
    real_fire = faults.fire

    def commit_at_swap(point, *args, **kwargs):
        if point == "compact.swap" and not committed["n"]:
            committed["n"] = 1
            store = VariantStore.load(store_dir)
            ts._append(store.shard(8), [
                {"chrom": 8, "pos": 7_777_777, "ref": "A", "alt": "G",
                 "rs": -1, "cadd": None, "rank": None, "vep": False},
            ])
            store.save(store_dir)
        return real_fire(point, *args, **kwargs)

    monkeypatch.setattr(
        "annotatedvdb_tpu.store.compact.faults.fire", commit_at_swap
    )
    report = compact_store(store_dir)
    assert report["status"] == "aborted"
    assert "loader committed" in report["reason"]
    assert not [f for f in os.listdir(store_dir) if ".compact.tmp" in f]
    store = VariantStore.load(store_dir)  # loader's row survived the abort
    found, _ = store.shard(8).lookup(
        *_identity_arrays("A", "G", 7_777_777)
    )
    assert bool(found[0])
    # an unarmed retry compacts to a clean store that keeps the row
    monkeypatch.setattr("annotatedvdb_tpu.store.compact.faults.fire",
                        real_fire)
    assert compact_store(store_dir)["status"] == "compacted"
    store = VariantStore.load(store_dir)
    found, _ = store.shard(8).lookup(
        *_identity_arrays("A", "G", 7_777_777)
    )
    assert bool(found[0])
    assert fsck(store_dir, deep=True, log=lambda m: None)["exit_code"] == 0


def _identity_arrays(ref: str, alt: str, pos: int):
    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.types import encode_allele_array

    r, rl = encode_allele_array([ref], ts.WIDTH)
    a, al = encode_allele_array([alt], ts.WIDTH)
    h = identity_hashes(ts.WIDTH, r, a, rl, al, [ref], [alt])
    return np.asarray([pos], np.int32), h, r, a, rl, al


# ---------------------------------------------------------------------------
# out-of-core spill tier


def test_spill_tier_loads_memmapped_and_byte_identical(tmp_path, monkeypatch):
    store_dir = str(tmp_path / "vdb")
    truth = _fragmented(store_dir)
    pre = _query_bytes(store_dir, truth)

    monkeypatch.setenv("AVDB_STORE_SPILL_BYTES", "1")  # spill everything
    store = VariantStore.load(store_dir)
    assert any(
        isinstance(seg.cols["pos"], np.memmap)
        for s in store.shards.values() for seg in s.segments
    )
    assert _query_bytes(store_dir, truth) == pre  # engine over spilled store

    # mutation lands in copy-on-write pages (update loaders keep working)
    sh = store.shard(8)
    sh.set_col("ref_snp", [0], [424242])
    assert int(sh.get_col("ref_snp", [0])[0]) == 424242

    # and a compaction pass over a spilled store still round-trips
    assert compact_store(store_dir)["status"] == "compacted"
    monkeypatch.delenv("AVDB_STORE_SPILL_BYTES")
    assert _query_bytes(store_dir, truth) == pre


def test_spill_threshold_gates_by_file_size(tmp_path, monkeypatch):
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    monkeypatch.setenv("AVDB_STORE_SPILL_BYTES", "1g")  # nothing that big
    store = VariantStore.load(store_dir)
    assert not any(
        isinstance(seg.cols["pos"], np.memmap)
        for s in store.shards.values() for seg in s.segments
    )


# ---------------------------------------------------------------------------
# doctor compact CLI contract


def _doctor(args):
    from annotatedvdb_tpu.cli import doctor

    return doctor.main(args)


def test_dry_run_prints_plan_without_touching(tmp_path, capsys):
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    before = {
        f: os.path.getmtime(os.path.join(store_dir, f))
        for f in os.listdir(store_dir)
    }
    rc = _doctor(["compact", "--storeDir", store_dir, "--dry-run", "--json"])
    assert rc == 0
    plan = json.loads(capsys.readouterr().out)
    assert {e["label"] for e in plan["eligible"]} == {"1", "8", "X"}
    for e in plan["eligible"]:
        assert e["stems"] >= 3 and e["bytes_before"] > 0
    after = {
        f: os.path.getmtime(os.path.join(store_dir, f))
        for f in os.listdir(store_dir)
    }
    assert after == before  # nothing touched, nothing created


def test_group_and_max_bytes_scoping(tmp_path, capsys):
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    # --group compacts exactly that chromosome
    rc = _doctor(["compact", "--storeDir", store_dir,
                  "--group", "chrX", "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["status"] == "compacted" and report["labels"] == ["X"]
    spans = segment_spans(store_dir)
    assert spans["X"] == 1 and spans["8"] > 1 and spans["1"] > 1
    # --maxBytes 0: every remaining group is over budget -> noop
    rc = _doctor(["compact", "--storeDir", store_dir,
                  "--maxBytes", "0", "--json"])
    assert rc == 0
    assert json.loads(capsys.readouterr().out)["status"] == "noop"
    # unscoped pass finishes the rest
    rc = _doctor(["compact", "--storeDir", store_dir, "--json"])
    assert rc == 0
    assert set(segment_spans(store_dir).values()) == {1}


def test_cli_missing_store_is_exit_2(tmp_path, capsys):
    rc = _doctor(["compact", "--storeDir", str(tmp_path / "nope")])
    assert rc == 2


def test_cli_hard_failure_is_exit_2(tmp_path):
    """A real I/O failure mid-merge (injected EIO) is the documented exit
    2 — never the benign 'aborted cleanly' 1 an ops retry loop would
    treat as preemption and spin on."""
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               AVDB_FAULT="compact.merge:1:eio")
    p = subprocess.run(
        [sys.executable, "-m", "annotatedvdb_tpu", "doctor", "compact",
         "--storeDir", store_dir],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert p.returncode == 2, (p.returncode, p.stderr[-500:])
    assert "EIO" in p.stderr
    store = VariantStore.load(store_dir)  # store untouched
    assert store.n > 0


def test_compact_metrics_registered_and_counted(tmp_path):
    from annotatedvdb_tpu.obs import MetricsRegistry

    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    reg = MetricsRegistry()
    compact_store(store_dir, registry=reg, cancel=lambda: True)  # abort
    compact_store(store_dir, registry=reg)                       # pass
    snap = reg.snapshot()
    assert snap["avdb_compact_passes_total"][0]["value"] == 1
    assert snap["avdb_compact_aborts_total"][0]["value"] == 1
    assert snap["avdb_compact_segments_merged_total"][0]["value"] > 0
    assert snap["avdb_compact_bytes_reclaimed_total"][0]["value"] > 0
    assert snap["avdb_compact_seconds"][0]["count"] == 1
    # the module default registry exists and exposes the same names
    handles = _metrics(None)
    assert set(handles) == {
        "passes", "segments_merged", "bytes_reclaimed", "aborts", "seconds"
    }


def test_compact_ledger_record(tmp_path):
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    compact_store(store_dir)
    led = AlgorithmLedger(os.path.join(store_dir, "ledger.jsonl"),
                          log=lambda m: None)
    recs = led.compactions()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["type"] == "compact"
    assert set(rec) >= {"labels", "files_before", "files_after",
                        "bytes_before", "bytes_after", "bytes_reclaimed",
                        "rows", "rows_dropped", "seconds", "ts"}
    # compact records are invisible to resume/undo logic
    assert led.last_checkpoint("whatever.vcf") == 0
    assert led.pending_undo_intents() == []


# ---------------------------------------------------------------------------
# fsck: abandoned compaction temps


def test_fsck_flags_and_prunes_compact_tmp(tmp_path):
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    stray_npz = os.path.join(store_dir, "chr8.000042.compact.tmp.npz")
    stray_jsonl = os.path.join(store_dir,
                               "chr8.000042.compact.tmp.ann.jsonl")
    open(stray_npz, "wb").write(b"half-written garbage")
    open(stray_jsonl, "wb").write(b"\x78\x9cxx")
    report = fsck(store_dir, log=lambda m: None)
    codes = [f["code"] for f in report["findings"]]
    assert codes.count("compact-tmp") == 2
    assert "foreign-file" not in codes  # the satellite bug: was foreign
    assert report["exit_code"] == 1
    report = fsck(store_dir, repair=True, log=lambda m: None)
    assert not os.path.exists(stray_npz)
    assert not os.path.exists(stray_jsonl)
    assert fsck(store_dir, log=lambda m: None)["status"] == "clean"


def test_stale_plan_label_preempts_instead_of_keyerror(tmp_path, monkeypatch):
    """A plan naming a label the (separately read, fingerprinted) manifest
    no longer carries must preempt cleanly, never KeyError mid-pass."""
    import annotatedvdb_tpu.store.compact as C

    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    real_plan = C.plan_compaction

    def stale_plan(*args, **kwargs):
        plan = real_plan(*args, **kwargs)
        plan["eligible"].append({
            "label": "22", "stems": 3, "groups": 3, "rows": 0,
            "bytes_before": 10, "est_bytes_after": 10,
        })
        return plan

    monkeypatch.setattr(C, "plan_compaction", stale_plan)
    report = compact_store(store_dir)
    assert report["status"] == "aborted"
    assert "no longer present" in report["reason"]
    assert not [f for f in os.listdir(store_dir) if ".compact.tmp" in f]
    VariantStore.load(store_dir)  # untouched


def test_corrupt_compressed_sidecar_is_store_corrupt_error(tmp_path):
    """A same-size bit flip in a compacted (zlib) sidecar passes the free
    size check but must still surface as StoreCorruptError naming the
    doctor — never a bare zlib.error."""
    from annotatedvdb_tpu.store import StoreCorruptError

    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    compact_store(store_dir)
    victim = [f for f in _files(store_dir)
              if f.startswith("chr8.") and f.endswith(".ann.jsonl")][0]
    fp = os.path.join(store_dir, victim)
    blob = bytearray(open(fp, "rb").read())
    assert blob[0] == 0x78  # the compressed format is what's under test
    blob[len(blob) // 2] ^= 0xFF
    open(fp, "wb").write(bytes(blob))
    with pytest.raises(StoreCorruptError, match="store_fsck"):
        VariantStore.load(store_dir)


def test_malformed_spill_knob_raises(tmp_path, monkeypatch):
    """A typo'd AVDB_STORE_SPILL_BYTES errors loudly (shared parse_bytes
    grammar) instead of silently disabling the out-of-core tier."""
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    monkeypatch.setenv("AVDB_STORE_SPILL_BYTES", "512mb")
    with pytest.raises(ValueError, match="AVDB_STORE_SPILL_BYTES"):
        VariantStore.load(store_dir)


def test_plan_skips_damaged_groups(tmp_path):
    """A group with a missing segment file is skipped (doctor --repair
    first), never half-compacted."""
    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    victim = [f for f in _files(store_dir)
              if f.startswith("chr1.") and f.endswith(".npz")][0]
    os.remove(os.path.join(store_dir, victim))
    plan = plan_compaction(store_dir)
    assert "1" not in {e["label"] for e in plan["eligible"]}
    assert any(e["label"] == "1" and "missing" in e["reason"]
               for e in plan["skipped"])


def test_compact_survives_sigterm_via_cli(tmp_path):
    """SIGTERM mid-pass aborts cleanly: rc=1, temps pruned, store intact
    (the cooperative shutdown half of the preemption contract)."""
    import signal
    import time

    store_dir = str(tmp_path / "vdb")
    _fragmented(store_dir)
    files = _files(store_dir)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               AVDB_COMPACT_CHUNK_ROWS="1024",
               # park the pass long enough to land the signal mid-merge
               AVDB_FAULT="compact.plan:1:delay:8000")
    proc = subprocess.Popen(
        [sys.executable, "-m", "annotatedvdb_tpu", "doctor", "compact",
         "--storeDir", store_dir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    # wait for the handler-is-live announcement — signaling during
    # interpreter startup would hit the DEFAULT handler and just die
    line = proc.stderr.readline()
    assert "pass starting" in line, line
    time.sleep(0.5)
    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=60)
    assert rc == 1, proc.stderr.read()[-1000:]
    assert _files(store_dir) == files
    assert not [f for f in os.listdir(store_dir) if ".compact.tmp" in f]
    store = VariantStore.load(store_dir)
    assert store.n > 0
