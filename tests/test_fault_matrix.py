"""Crash/recovery matrix: for every fault-injection kill point in the VCF
load path, abort a committing load mid-flight, then require that

1. the on-disk store loads cleanly, at most one checkpoint behind, OR is
   restored by ``store_fsck --repair``; and
2. ledger-driven resume completes the load to a store whose CONTENT is
   identical to an uninterrupted run (provenance columns — seg ids,
   ``row_algorithm_id`` — necessarily differ: they encode how many
   invocations it took, which is the one thing a crash changes).

The in-process matrix uses the ``raise`` action: an exception abandons the
in-memory store exactly where a crash would, and the durable state is
whatever the persist path had already renamed into place — the same
atomic-swap guarantees a SIGKILL exercises, minus page-cache effects no
in-tree test can simulate.  ``test_sigkill_*`` drives two points through a
real subprocess SIGKILL for the no-finally-runs guarantee.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from annotatedvdb_tpu.config import StoreConfig
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.fsck import fsck
from annotatedvdb_tpu.utils import faults
from conftest import BatcherOnLoop, start_server, stop_server

N_ROWS = 2600
BATCH = 512  # ~6 chunks => ~6 checkpoints per committed load


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.reset("")


def _write_vcf(path, n=N_ROWS):
    with open(path, "w") as f:
        f.write("##fileformat=VCFv4.2\n"
                "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
        for i in range(n):
            f.write(f"8\t{1000 + 3 * i}\trs{i}\tA\tG\t.\t.\tRS={i}\n")


def _run_load(store_dir, vcf, fault=""):
    """One committing CLI-shaped load (persist-before-checkpoint).  Returns
    (counters, exception): with a fault armed, the in-memory store is
    abandoned like a crashed process's heap and only disk state survives."""
    from annotatedvdb_tpu.loaders import TpuVcfLoader

    faults.reset(fault)
    store, ledger = StoreConfig(store_dir).open()
    loader = TpuVcfLoader(
        store, ledger, batch_size=BATCH, log=lambda *a: None,
    )
    try:
        counters = loader.load_file(
            vcf, commit=True, resume=True,
            persist=lambda: store.save(store_dir),
        )
        loader.close()
        store.save(store_dir)
        return counters, None
    except BaseException as exc:
        # a real crash stops every thread instantly: cancel the "dead"
        # loader's queued writer jobs so it cannot keep committing into
        # the directory while the recovery run is underway (an artifact
        # only an in-process crash simulation has)
        try:
            if loader._writer_pool is not None:
                loader._writer_pool.shutdown(wait=True, cancel_futures=True)
            if loader._prefetch_pool is not None:
                loader._prefetch_pool.shutdown(wait=False)
        except Exception:  # avdb: noqa[AVDB602] -- best-effort teardown of a simulated-dead loader; the armed fault is the exception under test
            pass
        return None, exc
    finally:
        faults.reset("")


def _content(store_dir):
    """Content signature: every column except provenance (alg ids)."""
    store = VariantStore.load(store_dir)
    shard = store.shard(8)
    shard.compact()
    cols = {
        c: shard.cols[c]
        for c in ("pos", "h", "ref_snp", "ref_len", "alt_len",
                  "bin_level", "leaf_bin")
    }
    return cols, shard.ref.copy(), shard.alt.copy(), store.n


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """One uninterrupted load: the content every recovery must reproduce."""
    d = tmp_path_factory.mktemp("ref")
    vcf = str(d / "d.vcf")
    _write_vcf(vcf)
    ref_store = str(d / "store")
    counters, exc = _run_load(ref_store, vcf)
    assert exc is None, exc
    assert counters["variant"] == N_ROWS
    return vcf, _content(ref_store)


# every kill point of the load path; nth chosen so at least one checkpoint
# is durable before the fault lands (the "<= 1 checkpoint behind" clause)
MATRIX = [
    ("store.save.pre_manifest:2:raise", False),
    ("store.save.pre_manifest:2:raise", True),   # + fsck --repair pass
    ("store.save.mid_segment:3:raise", False),
    ("ledger.append:4:raise", False),
    ("ingest.chunk:4:raise", False),
    # the prefetch spine (io/prefetch.py): death ON the prefetch thread —
    # the stage envelope must surface it on the consumer, and the durable
    # store stays <= 1 checkpoint behind like any other ingest death
    ("ingest.prefetch:3:raise", False),
    ("ingest.prefetch:2:eio", False),
]


@pytest.mark.parametrize("fault,run_fsck", MATRIX)
def test_crash_matrix(tmp_path, reference, fault, run_fsck):
    vcf, want = reference
    store_dir = str(tmp_path / "crash")

    counters, exc = _run_load(store_dir, vcf, fault=fault)
    assert exc is not None, f"{fault}: fault never fired"

    # 1. the durable store must load cleanly (possibly behind) ...
    partial = VariantStore.load(store_dir)
    assert partial.n <= N_ROWS
    # ... at most one checkpoint behind the ledger cursor: resume replays
    # idempotently, so the cursor may lag the store but never lead it
    from annotatedvdb_tpu.store import AlgorithmLedger

    cursor = AlgorithmLedger(
        os.path.join(store_dir, "ledger.jsonl")
    ).last_checkpoint(vcf)
    committed_rows = partial.n
    assert cursor <= 2 + committed_rows  # lines = header(2) + one per row

    if run_fsck:  # repair between crash and resume must stay recoverable
        report = fsck(store_dir, repair=True, log=lambda m: None)
        assert report["exit_code"] in (0, 1), report
        VariantStore.load(store_dir)

    # 2. resume completes to reference content
    counters, exc = _run_load(store_dir, vcf)
    assert exc is None, f"{fault}: resume failed: {exc}"
    got = _content(store_dir)
    want_cols, want_ref, want_alt, want_n = want
    got_cols, got_ref, got_alt, got_n = got
    assert got_n == want_n == N_ROWS
    for c, arr in want_cols.items():
        np.testing.assert_array_equal(got_cols[c], arr, err_msg=f"{fault}:{c}")
    np.testing.assert_array_equal(got_ref, want_ref)
    np.testing.assert_array_equal(got_alt, want_alt)

    # 3. post-recovery store passes fsck cleanly (orphans at worst)
    report = fsck(store_dir, deep=True, repair=True, log=lambda m: None)
    assert report["exit_code"] in (0, 1), report


def _cli(vcf, store, extra=()):
    return [sys.executable, "-m", "annotatedvdb_tpu.cli.load_vcf",
            "--fileName", vcf, "--storeDir", store,
            "--commitAfter", str(BATCH), "--commit", *extra]


@pytest.mark.parametrize("fault", [
    "store.save.pre_manifest:2:kill",
    "ledger.append:4:torn_write",
    # SIGKILL delivered ON the ingest-prefetch thread, mid-scan: the whole
    # process dies with chunks queued ahead of the consumer, and resume
    # must still land exactly on the reference content
    "ingest.prefetch:3:kill",
])
def test_sigkill_matrix(tmp_path, reference, fault):
    """True process death (no finally/atexit) at the juiciest points:
    before a manifest swap, tearing a ledger append in half, and mid-scan
    on the prefetch thread."""
    vcf, want = reference
    store_dir = str(tmp_path / "crash")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        AVDB_FAULT=fault,
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache"),
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
    )
    p = subprocess.run(_cli(vcf, store_dir), env=env,
                       capture_output=True, text=True, timeout=480)
    assert p.returncode == -signal.SIGKILL, (
        f"expected SIGKILL death, got rc={p.returncode}\n{p.stderr[-2000:]}"
    )

    # store loads (possibly behind); fsck prunes crash debris
    try:
        n_partial = VariantStore.load(store_dir).n
    except FileNotFoundError:
        # the prefetch thread runs AHEAD of the consumer: its kill can
        # land before the very first checkpoint persisted, leaving no
        # manifest at all — "zero checkpoints behind nothing" is a legal
        # durable state for that point, and resume starts from scratch
        assert fault.startswith("ingest.prefetch"), fault
        n_partial = 0
    assert n_partial <= N_ROWS
    if n_partial:
        report = fsck(store_dir, repair=True, log=lambda m: None)
        assert report["exit_code"] in (0, 1), report

    # resume (no fault armed) completes to reference content
    env.pop("AVDB_FAULT")
    p = subprocess.run(_cli(vcf, store_dir), env=env,
                       capture_output=True, text=True, timeout=480)
    assert p.returncode == 0, p.stderr[-2000:]
    got_cols, got_ref, got_alt, got_n = _content(store_dir)
    want_cols, want_ref, want_alt, want_n = want
    assert got_n == want_n
    for c, arr in want_cols.items():
        np.testing.assert_array_equal(got_cols[c], arr, err_msg=c)
    np.testing.assert_array_equal(got_ref, want_ref)
    np.testing.assert_array_equal(got_alt, want_alt)


# ---------------------------------------------------------------------------
# egress.flush — the export leg's injection point.  Not part of the VCF
# load matrix above (egress runs offline), but every faults.POINTS entry
# must be crash-tested here (static rule AVDB302): a raise mid-export must
# abort without leaving a torn COPY tmp, and a rerun must complete.


def _tiny_store(width=8):
    """Three chr3 A->C SNVs with REAL identity hashes (the serve legs probe
    them back by ``chr:pos:ref:alt``, so the stored hash must match what
    the engine computes)."""
    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.types import encode_allele_array

    store = VariantStore(width=width)
    ref, ref_len = encode_allele_array(["A"] * 3, width)
    alt, alt_len = encode_allele_array(["C"] * 3, width)
    store.shard(3).append(
        {"pos": np.asarray([10, 20, 30], np.int32),
         "h": identity_hashes(width, ref, alt, ref_len, alt_len),
         "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
    )
    return store


def test_egress_flush_raise_aborts_clean_and_rerun_completes(tmp_path):
    from annotatedvdb_tpu.io.pg_egress import export_store
    from annotatedvdb_tpu.utils.faults import InjectedFault

    store = _tiny_store()
    out = str(tmp_path / "export")
    faults.reset("egress.flush:1:raise")
    with pytest.raises(InjectedFault):
        export_store(store, out)
    # the aborted export left no torn half-written COPY tmp behind
    data_dir = os.path.join(out, "data")
    if os.path.isdir(data_dir):
        assert [f for f in os.listdir(data_dir) if ".tmp" in f] == []
    # rerun unarmed completes to full content
    faults.reset("")
    counts = export_store(store, out)
    assert counts == {"3": 3}
    data = open(os.path.join(data_dir, "variant_chr3.copy")).read()
    assert data.count("\n") == 3


# ---------------------------------------------------------------------------
# serve.batch / snapshot.swap — the serving subsystem's injection points
# (AVDB302: every faults.POINTS entry must be crash-tested in this file).
# Both use the raise action: serving is in-memory, so the contract is
# fail-the-unit-of-work-and-keep-running, not crash-and-recover-from-disk.


def test_serve_batch_raise_fails_only_that_batch_and_recovers():
    """An injected fault mid-drain (serve.batch:1:raise) must surface the
    root cause to every caller of THAT microbatch and leave the loop
    serving the next one."""
    from annotatedvdb_tpu.serve import QueryEngine, StaticSnapshots
    from annotatedvdb_tpu.utils.faults import InjectedFault

    engine = QueryEngine(StaticSnapshots(_tiny_store()), region_cache_size=0)
    batcher = BatcherOnLoop(engine, max_batch=4, max_wait_s=0.001)
    try:
        faults.reset("serve.batch:1:raise")
        with pytest.raises(InjectedFault):
            batcher.submit("3:10:A:C")
        faults.reset("")
        # the batcher survived its failed drain: same query now answers
        assert batcher.submit("3:10:A:C") is not None
        stats = batcher.batcher.drain_stats()
        assert stats["batches"] == 1  # only the clean drain counted
    finally:
        faults.reset("")
        batcher.close()


def test_serve_regions_raise_fails_only_that_batch_and_recovers():
    """An injected fault in the batch-region drain (serve.regions:1:raise)
    must fail exactly that batch's caller — the front ends map it to one
    500 — and leave the engine answering the next batch byte-identically
    to the untouched single-region path."""
    from annotatedvdb_tpu.serve import QueryEngine, StaticSnapshots
    from annotatedvdb_tpu.utils.faults import InjectedFault

    engine = QueryEngine(StaticSnapshots(_tiny_store()), region_cache_size=0)
    specs = ["3:1-100", "3:5-25"]
    want = [engine.region(s) for s in specs]
    faults.reset("serve.regions:1:raise")
    with pytest.raises(InjectedFault):
        engine.regions_serve(specs)
    # the engine survived its failed batch: the same panel now answers,
    # byte-identical per interval to the single-region calls
    got = engine.regions_serve(specs)
    assert [p.assemble() for p in got.pages] == want


def test_serve_stats_raise_and_eio_fail_only_that_request_and_recover():
    """An injected fault in the analytics drain (serve.stats raise/eio)
    must fail exactly that panel's caller — the front ends map it to one
    500 — and leave the engine answering the next panel byte-identically
    (incl. after an EIO, the transient-device shape the stats breaker
    fallback also absorbs)."""
    from annotatedvdb_tpu.serve import QueryEngine, StaticSnapshots
    from annotatedvdb_tpu.utils.faults import InjectedFault

    engine = QueryEngine(StaticSnapshots(_tiny_store()), region_cache_size=0)
    specs = ["3:1-100", "3:5-25"]
    want = engine.stats_serve(specs).assemble()
    try:
        faults.reset("serve.stats:1:raise")
        with pytest.raises(InjectedFault):
            engine.stats_serve(specs)
        faults.reset("serve.stats:1:eio")
        with pytest.raises(OSError):
            engine.stats_serve(specs)
    finally:
        faults.reset("")
    # the engine survived both failed panels: same panel, same bytes
    assert engine.stats_serve(specs).assemble() == want


def test_snapshot_swap_raise_keeps_old_generation_serving(tmp_path):
    """A fault between loading the new generation and swapping the pin
    (snapshot.swap:1:raise) must leave the OLD generation serving; an
    unarmed retry completes the swap."""
    from annotatedvdb_tpu.serve import SnapshotManager
    from annotatedvdb_tpu.utils.faults import InjectedFault

    store_dir = str(tmp_path / "store")
    _tiny_store().save(store_dir)
    manager = SnapshotManager(store_dir)
    rows_v1 = manager.current().store.n

    # a loader commit lands a second generation on disk
    store = VariantStore.load(store_dir)
    store.shard(3).append(
        {"pos": np.asarray([40], np.int32),
         "h": np.asarray([11], np.uint32),
         "ref_len": np.full(1, 1, np.int32),
         "alt_len": np.full(1, 1, np.int32)},
        np.full((1, 8), 65, np.uint8), np.full((1, 8), 71, np.uint8),
    )
    store.save(store_dir)

    faults.reset("snapshot.swap:1:raise")
    with pytest.raises(InjectedFault):
        manager.refresh()
    # the pin never moved: generation 1, old row count
    snap = manager.current()
    assert snap.generation == 1 and snap.store.n == rows_v1

    faults.reset("")
    assert manager.refresh() is True
    snap = manager.current()
    assert snap.generation == 2 and snap.store.n == rows_v1 + 1


# ---------------------------------------------------------------------------
# serve.accept / serve.worker — the fleet's injection points.  An accept
# fault must cost exactly one connection (raise) while the server keeps
# serving; a killed worker must be restarted by the supervisor with the
# fleet serving cleanly after the restart window.


def test_serve_accept_raise_fails_only_that_connection():
    import urllib.error
    import urllib.request

    from annotatedvdb_tpu.serve import StaticSnapshots
    from annotatedvdb_tpu.serve.aio import build_aio_server

    server = build_aio_server(
        manager=StaticSnapshots(_tiny_store()), port=0
    )
    server.start_background()
    try:
        port = server.server_address[1]

        def get():
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/variant/3:10:A:C", timeout=30
            ) as r:
                return r.status

        assert get() == 200
        # arm: the NEXT accepted connection dies before parsing anything
        # (the client sees a reset/empty response, never a served reply)
        faults.reset("serve.accept:1:raise")
        with pytest.raises((urllib.error.URLError, ConnectionResetError)):
            get()
        # exactly that connection failed; the server keeps serving
        faults.reset("")
        assert get() == 200
    finally:
        faults.reset("")
        server.shutdown()
        server.ctx.batcher.close()


def test_engine_device_probe_eio_trips_breaker_then_half_open_recloses():
    """engine.device_probe (eio): repeated injected device-probe failures
    must (1) never change answer bytes — the breaker retries the
    byte-identical host path — and (2) trip the per-group breaker after
    the threshold, then re-close it through a half-open probe once the
    cooldown lapses and the fault is gone."""
    from annotatedvdb_tpu.serve import (
        DeviceBreaker,
        QueryEngine,
        StaticSnapshots,
    )

    clock = {"t": 0.0}
    breaker = DeviceBreaker(cooldown_s=5.0, clock=lambda: clock["t"])
    engine = QueryEngine(
        StaticSnapshots(_tiny_store()), region_cache_size=0,
        breaker=breaker,
    )
    want = engine.lookup("3:10:A:C")
    assert want is not None
    faults.reset("engine.device_probe:prob:1.0:eio")
    for _ in range(breaker.failure_threshold):
        # every failing probe still answers, byte-identical (host retry)
        assert engine.lookup("3:10:A:C") == want
    assert breaker.state(3) == "open"
    # while tripped the device path is never attempted: the armed fault
    # cannot fire (host-only path), answers stay correct
    fired_before = faults.fired().get("engine.device_probe", 0)
    assert engine.lookup("3:10:A:C") == want
    assert faults.fired().get("engine.device_probe", 0) == fired_before
    # cooldown lapses, fault cleared: ONE half-open probe re-closes
    faults.reset("")
    clock["t"] = 10.0
    assert engine.lookup("3:10:A:C") == want
    assert breaker.state(3) == "closed"


def test_serve_wedge_watchdog_kills_and_respawns(tmp_path):
    """serve.wedge (delay): a long delay on the event-loop maintenance
    tick parks the LOOP — the worker process stays alive but stops
    heartbeating and serving.  The fleet watchdog must SIGKILL it
    (logged as wedged) and the respawned workers (fault stripped) must
    bring the fleet back to clean serving."""
    import re
    import subprocess
    import threading
    import time
    import urllib.request

    store_dir = str(tmp_path / "wedge_store")
    _tiny_store().save(store_dir)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # 3rd tick (~0.5s after accept starts): both workers come up,
        # serve briefly, then park their loops for 60s
        AVDB_FAULT="serve.wedge:3:delay:60000",
        AVDB_SERVE_WEDGE_TIMEOUT_S="2",
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "annotatedvdb_tpu", "serve",
         "--storeDir", store_dir, "--port", "0", "--workers", "2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    lines: list[str] = []
    try:
        first = proc.stdout.readline()
        lines.append(first)
        reader = threading.Thread(
            target=lambda: lines.extend(proc.stdout), daemon=True
        )
        reader.start()
        m = re.search(r"http://([\d.]+):(\d+)", first)
        assert m, f"no fleet address line: {first!r}"
        host, port = m.group(1), int(m.group(2))

        def get(path):
            with urllib.request.urlopen(
                f"http://{host}:{port}{path}", timeout=5
            ) as r:
                return r.status

        # the watchdog must detect the parked loops and the respawned
        # (clean) workers must serve again
        deadline = time.monotonic() + 120
        recovered = False
        while time.monotonic() < deadline:
            if any("wedged" in ln for ln in lines):
                try:
                    if get("/variant/3:10:A:C") == 200:
                        recovered = True
                        break
                except OSError:
                    pass
            time.sleep(0.3)
        assert any("wedged" in ln for ln in lines), (
            "watchdog never detected the wedged workers:\n"
            + "".join(lines)[-2000:]
        )
        assert recovered, (
            "fleet never recovered after the wedge kills:\n"
            + "".join(lines)[-2000:]
        )
        # recovered means RELIABLY serving, not one lucky hit
        failures = sum(
            1 for _ in range(20)
            if _get_status_or_none(get) != 200
        )
        assert failures == 0
    finally:
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    assert rc == 0, "".join(lines)[-2000:]


def _get_status_or_none(get):
    try:
        return get("/variant/3:10:A:C")
    except OSError:
        return None


# ---------------------------------------------------------------------------
# compact.plan / compact.merge / compact.swap / compact.gc — the online
# compactor's kill points (store/compact.py).  Contract: a death at ANY of
# them leaves a store byte-identical to either the PRE- or the
# POST-compaction reference — never a third state — and fsck --repair
# prunes whatever debris (compact temps, orphaned segments) the death left.


def _fragmented_store(store_dir: str) -> None:
    """Four disjoint chr6 segments saved one checkpoint apart, with sparse
    annotations — enough files that every compact kill point has real work
    in flight when it fires."""
    store = VariantStore(width=8)
    shard = store.shard(6)
    from annotatedvdb_tpu.store.variant_store import Segment

    for k in range(4):
        n = 250
        cols = {
            "pos": np.arange(500 + 20_000 * k, 500 + 20_000 * k + n,
                             dtype=np.int32),
            "h": np.arange(n, dtype=np.uint32) + 1,
            "ref_len": np.full(n, 1, np.int32),
            "alt_len": np.full(n, 1, np.int32),
        }
        shard.append_segment(Segment.build(
            cols, np.full((n, 8), 65, np.uint8),
            np.full((n, 8), 71, np.uint8),
            annotations={"other_annotation":
                         [{"k": int(i)} if i % 3 else None
                          for i in range(n)]},
        ))
        shard._starts_cache = None
        store.save(store_dir)


def _store_signature(store_dir: str):
    """Full content signature: every numeric column + alleles + a sample of
    annotations, in position-sorted order (compaction-invariant)."""
    from annotatedvdb_tpu.store.variant_store import _NUMERIC_COLUMNS

    store = VariantStore.load(store_dir)
    shard = store.shard(6)
    shard.compact()
    return (
        tuple(shard.cols[c].tobytes() for c, _ in _NUMERIC_COLUMNS),
        shard.ref.tobytes(), shard.alt.tobytes(),
        tuple(json.dumps(shard.get_ann("other_annotation", i))
              for i in range(0, store.n, 83)),
        store.n,
    )


@pytest.fixture()
def compact_refs(tmp_path):
    """(store_dir, pre signature, post signature): the two states every
    crashed compact pass must land on."""
    import shutil

    store_dir = str(tmp_path / "cstore")
    _fragmented_store(store_dir)
    pre = _store_signature(store_dir)
    ref_dir = str(tmp_path / "cref")
    shutil.copytree(store_dir, ref_dir)
    from annotatedvdb_tpu.store import compact_store

    report = compact_store(ref_dir)
    assert report["status"] == "compacted"
    post = _store_signature(ref_dir)
    assert post == pre  # no duplicates here: content identical either way
    return store_dir, pre, post


@pytest.mark.parametrize("fault,expect_state", [
    ("compact.plan:1:raise", "pre"),
    ("compact.plan:1:eio", "pre"),
    ("compact.merge:1:raise", "pre"),
    ("compact.merge:1:eio", "pre"),
    ("compact.swap:1:raise", "pre"),
    ("compact.gc:1:eio", "post"),   # gc absorbs eio: committed, orphans
])
def test_compact_crash_matrix_in_process(compact_refs, fault, expect_state):
    from annotatedvdb_tpu.store import compact_store
    from annotatedvdb_tpu.store.fsck import fsck as run_fsck

    store_dir, pre, post = compact_refs
    faults.reset(fault)
    try:
        report = compact_store(store_dir)
        fired = faults.fired()
        assert expect_state == "post", f"{fault}: fault never surfaced"
        assert report["status"] == "compacted" and fired
    except (faults.InjectedFault, OSError):
        assert expect_state == "pre"
    finally:
        faults.reset("")

    got = _store_signature(store_dir)
    assert got == (pre if expect_state == "pre" else post)
    # in-process aborts clean their own temps; repair handles the rest
    report = run_fsck(store_dir, repair=True, log=lambda m: None)
    assert report["exit_code"] in (0, 1), report
    assert _store_signature(store_dir) == got
    # an unarmed pass completes to the post state
    final = compact_store(store_dir)
    assert final["status"] in ("compacted", "noop")
    assert _store_signature(store_dir) == post


@pytest.mark.parametrize("fault", [
    "compact.merge:1:kill",
    "compact.merge:1:torn_write",
    "compact.swap:1:kill",
    "compact.gc:1:kill",
])
def test_compact_sigkill_matrix(compact_refs, fault):
    """True process death through the CLI (`doctor compact` subprocess):
    the durable store must equal pre OR post — never a hybrid — and the
    repair + rerun path must converge on post."""
    from annotatedvdb_tpu.store import compact_store
    from annotatedvdb_tpu.store.fsck import fsck as run_fsck

    store_dir, pre, post = compact_refs
    env = dict(os.environ, JAX_PLATFORMS="cpu", AVDB_FAULT=fault)
    p = subprocess.run(
        [sys.executable, "-m", "annotatedvdb_tpu", "doctor", "compact",
         "--storeDir", store_dir],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert p.returncode == -signal.SIGKILL, (
        f"{fault}: expected SIGKILL death, rc={p.returncode}\n"
        f"{p.stderr[-2000:]}"
    )
    got = _store_signature(store_dir)
    assert got in (pre, post), f"{fault}: store is a third state"
    # gc kill dies AFTER the commit point; everything earlier dies before
    expect_committed = fault.startswith("compact.gc")
    spans = json.load(open(os.path.join(store_dir, "manifest.json")))
    n_stems = sum(len(g) for g in spans["shards"]["6"])
    assert (n_stems == 1) == expect_committed

    report = run_fsck(store_dir, repair=True, log=lambda m: None)
    assert report["exit_code"] in (0, 1), report
    assert not [f for f in os.listdir(store_dir) if ".compact.tmp" in f]
    assert _store_signature(store_dir) == got

    final = compact_store(store_dir)
    assert final["status"] in ("compacted", "noop")
    assert _store_signature(store_dir) == post
    assert run_fsck(store_dir, deep=True,
                    log=lambda m: None)["exit_code"] == 0


def test_serve_worker_kill_fleet_restarts_and_keeps_serving(tmp_path):
    """SIGKILLed workers (serve.worker:1:kill fires in each initial worker
    right after it starts accepting) are restarted by the supervisor —
    with the serve-side fault stripped from the respawn env — and after
    the restart window the fleet serves with zero failed responses."""
    import re
    import subprocess
    import time
    import urllib.request

    store_dir = str(tmp_path / "fleet_store")
    _tiny_store().save(store_dir)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        AVDB_FAULT="serve.worker:1:kill",
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "annotatedvdb_tpu", "serve",
         "--storeDir", store_dir, "--port", "0", "--workers", "2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        line = proc.stdout.readline()
        m = re.search(r"http://([\d.]+):(\d+)", line)
        assert m, f"no fleet address line: {line!r}"
        host, port = m.group(1), int(m.group(2))

        def get(path):
            with urllib.request.urlopen(
                f"http://{host}:{port}{path}", timeout=5
            ) as r:
                return r.status

        # both initial workers die at the fire point; the supervisor
        # respawns them clean — wait out the restart window
        deadline = time.monotonic() + 120
        up = False
        while time.monotonic() < deadline:
            try:
                if get("/healthz") == 200:
                    up = True
                    break
            except OSError:
                time.sleep(0.3)
        assert up, "fleet never recovered from the injected worker kills"
        # zero failed responses after the restart window
        failures = 0
        for _ in range(30):
            try:
                if get("/variant/3:10:A:C") != 200:
                    failures += 1
            except OSError:
                failures += 1
        assert failures == 0
    finally:
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    assert rc == 0, proc.stdout.read()[-2000:]


# ---------------------------------------------------------------------------
# wal.append / wal.fsync / wal.replay / memtable.flush — the live write
# path's kill points (store/wal.py + store/memtable.py).  Contract: an
# ACKNOWLEDGED upsert (Memtable.upsert returned) is present after
# recovery; an unacknowledged one is applied in full or not at all —
# never a hybrid, never a torn store.


_UPSERT_ROW = {
    "code": 3, "pos": 15, "ref": "A", "alt": "G", "ref_snp": 7,
    "ann": {"other_annotation": {"k": 1}},
}


def _upsert_env(tmp_path):
    """(store_dir, base readonly store, memtable-with-wal) over the tiny
    chr3 store — the in-process write-path fixture."""
    from annotatedvdb_tpu.store.memtable import Memtable
    from annotatedvdb_tpu.store.wal import WriteAheadLog

    store_dir = str(tmp_path / "ustore")
    _tiny_store().save(store_dir)
    base = VariantStore.load(store_dir, readonly=True)
    wal = WriteAheadLog(store_dir, "serve-w0", log=lambda m: None)
    mem = Memtable(width=8, store_dir=store_dir, wal=wal,
                   log=lambda m: None)
    return store_dir, base, mem


def _fresh_replayed(store_dir, base):
    """A brand-new memtable rebuilt from the on-disk WAL — the respawned
    worker's view."""
    from annotatedvdb_tpu.store.memtable import Memtable
    from annotatedvdb_tpu.store.wal import WriteAheadLog

    mem = Memtable(width=8, store_dir=store_dir,
                   wal=WriteAheadLog(store_dir, "serve-w0",
                                     log=lambda m: None),
                   log=lambda m: None)
    applied = mem.replay(base)
    return mem, applied


@pytest.mark.parametrize("fault", [
    "wal.append:1:raise",
    "wal.append:1:eio",
])
def test_wal_append_fault_leaves_prestate(tmp_path, fault):
    """A failure BEFORE the WAL frame lands must fail the request with
    nothing visible, nothing durable, and nothing to replay — the
    consistent pre-state (the request was never acknowledged)."""
    store_dir, base, mem = _upsert_env(tmp_path)
    faults.reset(fault)
    try:
        with pytest.raises((faults.InjectedFault, OSError)):
            mem.upsert(base, [dict(_UPSERT_ROW)])
    finally:
        faults.reset("")
    assert mem.rows == 0
    replayed, applied = _fresh_replayed(store_dir, base)
    assert applied == 0 and replayed.rows == 0
    # unarmed retry succeeds and IS durable
    accepted, shadowed, _b = mem.upsert(base, [dict(_UPSERT_ROW)])
    assert (accepted, shadowed) == (1, 0)
    _, applied = _fresh_replayed(store_dir, base)
    assert applied == 1


def test_wal_fsync_fault_is_all_or_nothing(tmp_path):
    """A failure between the frame write and its fsync: the request was
    NOT acknowledged, but the frame is complete — replay applies it in
    full (never a torn half-row), which the contract allows for un-acked
    writes.  The failing request itself left nothing visible."""
    store_dir, base, mem = _upsert_env(tmp_path)
    faults.reset("wal.fsync:1:raise")
    try:
        with pytest.raises(faults.InjectedFault):
            mem.upsert(base, [dict(_UPSERT_ROW)])
    finally:
        faults.reset("")
    assert mem.rows == 0  # nothing became visible in the failing worker
    replayed, applied = _fresh_replayed(store_dir, base)
    assert applied in (0, 1)
    if applied:
        # applied IN FULL: the row answers with its exact content
        from annotatedvdb_tpu.serve import QueryEngine, StaticSnapshots
        from annotatedvdb_tpu.serve.snapshot import MemtableSnapshots

        engine = QueryEngine(
            MemtableSnapshots(StaticSnapshots(base), replayed),
            region_cache_size=0,
        )
        rec = engine.lookup("3:15:A:G")
        assert rec is not None and '"rs7"' in rec \
            and '"other_annotation":{"k": 1}' in rec


def test_wal_replay_fault_then_retry_recovers(tmp_path):
    """A death mid-replay (wal.replay) is recovered by replaying again on
    the next respawn — replay mutates nothing durable, and the first-wins
    check makes double-application impossible."""
    store_dir, base, mem = _upsert_env(tmp_path)
    accepted, _s, _b = mem.upsert(base, [dict(_UPSERT_ROW)])
    assert accepted == 1
    faults.reset("wal.replay:1:raise")
    try:
        with pytest.raises(faults.InjectedFault):
            _fresh_replayed(store_dir, base)
    finally:
        faults.reset("")
    # the respawn replays clean; a second replay pass over the same WAL
    # (the crash-during-replay recovery) changes nothing
    replayed, applied = _fresh_replayed(store_dir, base)
    assert applied == 1 and replayed.rows == 1
    accepted, shadowed, _b = replayed.upsert(
        base, [dict(_UPSERT_ROW)], durable=False
    )
    assert (accepted, shadowed) == (0, 1)


@pytest.mark.parametrize("fault", [
    "memtable.flush:1:raise",   # before anything is written
    "memtable.flush:1:eio",
    "memtable.flush:2:raise",   # mid-manifest-commit (segments renamed)
    "memtable.flush:2:eio",
])
def test_memtable_flush_crash_matrix_in_process(tmp_path, fault):
    """A flush failure at either kill point leaves the on-disk store
    byte-identical to its pre-flush state, the memtable + WAL keeping
    every acknowledged row (reads unaffected); fsck prunes any debris
    and an unarmed retry completes."""
    from annotatedvdb_tpu.store.fsck import fsck as run_fsck

    store_dir, base, mem = _upsert_env(tmp_path)
    pre = _store_signature_chr3(store_dir)
    accepted, _s, _b = mem.upsert(base, [dict(_UPSERT_ROW)])
    assert accepted == 1
    faults.reset(fault)
    try:
        with pytest.raises((faults.InjectedFault, OSError)):
            mem.flush(base_manager=None)
    finally:
        faults.reset("")
    # store untouched; the acknowledged row is still served (memtable)
    assert _store_signature_chr3(store_dir) == pre
    assert mem.rows == 1
    report = run_fsck(store_dir, repair=True, log=lambda m: None)
    assert report["exit_code"] in (0, 1), report
    # repair prunes WAL debris too in this mode — but the MEMTABLE still
    # holds the row, so the retry flush makes it durable regardless
    result = mem.flush(base_manager=None)
    assert result["status"] == "flushed" and result["rows"] == 1
    assert mem.rows == 0
    store = VariantStore.load(store_dir)
    assert store.shard(3).n == 4
    final = run_fsck(store_dir, repair=True, log=lambda m: None)
    assert final["exit_code"] in (0, 1), final


def _store_signature_chr3(store_dir: str):
    store = VariantStore.load(store_dir)
    shard = store.shard(3)
    shard.compact()
    return (
        shard.cols["pos"].tobytes(), shard.cols["h"].tobytes(),
        shard.ref.tobytes(), shard.alt.tobytes(), store.n,
    )


def test_memtable_flush_preempted_by_loader_commit(tmp_path):
    """The three-writer coordination contract: a loader committing a new
    generation between the flush's plan and its commit point PREEMPTS the
    flush (status aborted, temps cleaned, memtable untouched) — and the
    retry lands the rows on top of the loader's generation."""
    store_dir, base, mem = _upsert_env(tmp_path)
    accepted, _s, _b = mem.upsert(base, [dict(_UPSERT_ROW)])
    assert accepted == 1

    from annotatedvdb_tpu.store import memtable as memtable_mod

    real_write = VariantStore._write_segment
    fired = {"n": 0}

    def racing_write(path, stem, seg):
        rec = real_write(path, stem, seg)
        if fired["n"] == 0:
            fired["n"] = 1
            # a loader commits a new generation AFTER our temp is written,
            # BEFORE the flush's rename step re-checks the fingerprint
            loader = VariantStore.load(store_dir)
            loader.shard(3).append(
                {"pos": np.asarray([40], np.int32),
                 "h": np.asarray([99], np.uint32),
                 "ref_len": np.full(1, 1, np.int32),
                 "alt_len": np.full(1, 1, np.int32)},
                np.full((1, 8), 65, np.uint8),
                np.full((1, 8), 71, np.uint8),
            )
            loader.save(store_dir)
        return rec

    import unittest.mock as mock

    with mock.patch.object(VariantStore, "_write_segment",
                           staticmethod(racing_write)):
        result = mem.flush(base_manager=None)
    assert result["status"] == "aborted", result
    assert mem.rows == 1  # nothing acknowledged was lost
    assert not [f for f in os.listdir(store_dir) if ".flush.tmp" in f]
    # the retry flushes onto the loader's generation
    result = mem.flush(base_manager=None)
    assert result["status"] == "flushed"
    store = VariantStore.load(store_dir)
    assert store.shard(3).n == 5  # 3 loaded + 1 loader row + 1 upsert


def _spawn_upsert_server(store_dir, env_extra=None, timeout=60):
    """One real `serve --upserts` worker process on an ephemeral port;
    returns (proc, host, port) once the address line printed."""
    import re

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               AVDB_MEMTABLE_FLUSH_S="0", AVDB_MEMTABLE_BYTES="0")
    env.pop("AVDB_FAULT", None)
    env.update(env_extra or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "annotatedvdb_tpu", "serve",
         "--storeDir", store_dir, "--port", "0", "--upserts"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    lines = []
    for _ in range(50):  # replay/log lines may precede the address line
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        m = re.search(r"http://([\d.]+):(\d+)", line)
        if m:
            return proc, m.group(1), int(m.group(2))
    raise AssertionError(f"no serve address line: {lines!r}")


def _post_upsert(host, port, vid, timeout=10):
    import urllib.request

    body = json.dumps({"variants": [{"id": vid}]}).encode()
    req = urllib.request.Request(
        f"http://{host}:{port}/variants/upsert", data=body, method="POST"
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def _get_variant(host, port, vid, timeout=10):
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(
            f"http://{host}:{port}/variant/{vid}", timeout=timeout
        ) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as err:
        return err.code, err.read()


def test_upsert_sigkill_unacked_never_appears_acked_survives(tmp_path):
    """The ack contract through the REAL serve CLI:

    1. a worker armed ``wal.append:1:torn_write`` dies mid-frame on the
       first upsert — the client never got a 200, and after a clean
       respawn the row is ABSENT (the torn tail was dropped);
    2. the respawned worker ACKs the same upsert (200) and is then
       SIGKILLed outright — after another respawn the acknowledged row
       is PRESENT, byte-identical, served from the replayed WAL."""
    import urllib.error

    store_dir = str(tmp_path / "sstore")
    _tiny_store().save(store_dir)

    # -- stage 1: death mid-WAL-append => un-acked, absent ---------------
    proc, host, port = _spawn_upsert_server(
        store_dir, env_extra={"AVDB_FAULT": "wal.append:1:torn_write"}
    )
    try:
        with pytest.raises((urllib.error.URLError, ConnectionError,
                            TimeoutError)):
            _post_upsert(host, port, "3:15:A:G")
    finally:
        rc = proc.wait(timeout=60)
    assert rc == -signal.SIGKILL, f"expected SIGKILL death, rc={rc}"

    proc, host, port = _spawn_upsert_server(store_dir)
    try:
        status, _body = _get_variant(host, port, "3:15:A:G")
        assert status == 404, "un-acked upsert must not appear"

        # -- stage 2: acked upsert survives a SIGKILL --------------------
        status, body = _post_upsert(host, port, "3:15:A:G")
        assert status == 200 and b'"accepted":1' in body
        status, want = _get_variant(host, port, "3:15:A:G")
        assert status == 200
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    proc, host, port = _spawn_upsert_server(store_dir)
    try:
        status, got = _get_variant(host, port, "3:15:A:G")
        assert status == 200 and got == want, \
            "acknowledged upsert lost or changed across SIGKILL"
    finally:
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0


def test_memtable_flush_sigkill_through_cli_recovers_to_post(tmp_path):
    """memtable.flush:2:kill through the REAL serve CLI: the worker acks
    an upsert, its flush dies AT THE MANIFEST COMMIT POINT (segments
    renamed, manifest not swapped) — the durable store is byte-identical
    pre-state with fsck-attributable debris, the acknowledged row
    survives in the WAL, and a clean respawn replays it, flushes it, and
    converges on the post state."""
    import shutil as _shutil
    import time

    store_dir = str(tmp_path / "fstore")
    _tiny_store().save(store_dir)

    pre_manifest = json.load(open(os.path.join(store_dir,
                                               "manifest.json")))

    # stage 1: ack a row with flush triggers off, drain cleanly (the
    # WAL keeps the row: the memtable never flushed)
    proc, host, port = _spawn_upsert_server(store_dir)
    try:
        status, body = _post_upsert(host, port, "3:15:A:G")
        assert status == 200 and b'"accepted":1' in body
        status, want = _get_variant(host, port, "3:15:A:G")
        assert status == 200
    finally:
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0

    # stage 2: respawn with the commit-point kill armed and a 1-byte
    # bound — replay crosses the bound, the maintenance tick fires the
    # flush, and the armed kill lands at the manifest commit (no request
    # in flight: the ack already happened, a restart ago)
    proc, host, port = _spawn_upsert_server(
        store_dir,
        env_extra={"AVDB_FAULT": "memtable.flush:2:kill",
                   "AVDB_MEMTABLE_BYTES": "1"},
    )
    try:
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    assert rc == -signal.SIGKILL, f"expected flush kill, rc={rc}"

    # pre-state: the manifest never swapped (same shard groups), the
    # renamed segments are orphan debris, the WAL survives
    now_manifest = json.load(open(os.path.join(store_dir,
                                               "manifest.json")))
    assert now_manifest["shards"] == pre_manifest["shards"]
    assert any(f.endswith(".wal") for f in os.listdir(store_dir))
    from annotatedvdb_tpu.store.fsck import fsck as run_fsck

    # repair on a COPY first: pruning must yield a clean pre-state store
    audit = str(tmp_path / "audit")
    _shutil.copytree(store_dir, audit)
    report = run_fsck(audit, repair=True, log=lambda m: None)
    assert report["exit_code"] in (0, 1), report

    # a clean respawn replays the acked row and completes the flush
    proc, host, port = _spawn_upsert_server(
        store_dir, env_extra={"AVDB_MEMTABLE_BYTES": "1"}
    )
    try:
        status, got = _get_variant(host, port, "3:15:A:G")
        assert status == 200 and got == want
        deadline = time.time() + 60
        flushed = False
        while time.time() < deadline:
            rows = json.load(open(os.path.join(
                store_dir, "manifest.json"
            ))).get("stats", {}).get("rows", {})
            if int(rows.get("3", 0)) >= 4:
                flushed = True
                break
            time.sleep(0.25)
        assert flushed, "respawned worker never completed the flush"
        status, got = _get_variant(host, port, "3:15:A:G")
        assert status == 200 and got == want
    finally:
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    store = VariantStore.load(store_dir)
    assert store.shard(3).n == 4
    # the dead flush's stale .manifest.tmp is the one prescribed repair
    # (the per-kill-point table); after it the store deep-fscks clean
    report = run_fsck(store_dir, repair=True, log=lambda m: None)
    assert report["exit_code"] in (0, 1), report
    assert run_fsck(store_dir, deep=True,
                    log=lambda m: None)["exit_code"] == 0
    assert VariantStore.load(store_dir).shard(3).n == 4


# ---------------------------------------------------------------------------
# maintain.tick / maintain.disk_guard — the autonomy layer's fault points
# (store/maintenance.py).  Contract: a dying daemon tick is absorbed
# (logged + backed off) and never propagates to the hosting fleet
# supervisor; an injected low-disk reading flips upserts to 507 on both
# front ends (through the ONE shared upsert_execute gate) and clears
# cleanly on the next reading.


@pytest.mark.parametrize("fault", [
    "maintain.tick:1:raise",
    "maintain.tick:1:eio",
])
def test_maintain_tick_fault_absorbed_next_tick_compacts(tmp_path, fault):
    """A dying tick must never kill the daemon (and therefore never the
    supervisor or the fleet hosting it): the fault is logged, the daemon
    backs off, and the NEXT tick runs the watermark evaluation normally
    — the fragmented store still gets compacted."""
    from annotatedvdb_tpu.store.maintenance import MaintenanceDaemon

    store_dir = str(tmp_path / "mstore")
    _fragmented_store(store_dir)
    pre = _store_signature(store_dir)
    logs: list = []
    daemon = MaintenanceDaemon(
        store_dir, high=4, low=2, tick_s=0.05, cooldown_s=0.0,
        log=logs.append,
    )
    faults.reset(fault)
    assert daemon.tick() == "error"  # absorbed, not raised
    assert any("tick failed" in m for m in logs), logs
    # nth=1 consumed: the next tick trips the watermark and compacts
    assert daemon.tick() == "pass"
    assert max(daemon.read_amp().values()) == 1
    assert _store_signature(store_dir) == pre
    assert daemon.stats()["disabled"] is False


def test_maintain_tick_fault_daemon_thread_survives(tmp_path):
    """Same point through the REAL daemon thread (what the supervisor
    hosts): with the fault armed the thread keeps ticking — it neither
    dies nor wedges, which is exactly what keeps the fleet alive."""
    from annotatedvdb_tpu.store.maintenance import MaintenanceDaemon

    store_dir = str(tmp_path / "mstore2")
    _fragmented_store(store_dir)
    daemon = MaintenanceDaemon(
        store_dir, high=4, low=2, tick_s=0.05, cooldown_s=0.0,
        log=lambda m: None,
    )
    faults.reset("maintain.tick:1:raise")
    daemon.start()
    try:
        deadline = time.time() + 20
        while time.time() < deadline:
            if daemon.stats()["passes"] >= 1:
                break
            time.sleep(0.05)
        stats = daemon.stats()
        assert daemon._thread.is_alive()
        assert stats["passes"] >= 1, stats
        assert stats["ticks"] >= 2, stats
    finally:
        daemon.stop()


def test_maintain_disk_guard_fault_flips_507_and_clears(tmp_path):
    """maintain.disk_guard (raise/eio): an injected free-space reading
    failure IS a low-disk observation — the guard reports breached, the
    upsert gate answers 507 with the single-source body, nothing becomes
    durable, and the next (clean) reading
    clears the degradation."""
    from annotatedvdb_tpu.obs.metrics import MetricsRegistry
    from annotatedvdb_tpu.serve.http import MSG_DISK_RESERVE
    from annotatedvdb_tpu.serve.snapshot import (
        MemtableSnapshots,
        SnapshotManager,
    )
    from annotatedvdb_tpu.store.maintenance import DiskReserveGuard
    from annotatedvdb_tpu.store.memtable import Memtable
    from annotatedvdb_tpu.store.wal import WriteAheadLog

    store_dir = str(tmp_path / "dstore")
    _fragmented_store(store_dir)

    # guard level: injected failure = breached; clean reading = clear
    guard = DiskReserveGuard(store_dir, reserve=1, ttl_s=0.0,
                             log=lambda m: None)
    faults.reset("maintain.disk_guard:1:eio")
    breached, free = guard.state(force=True)
    assert breached is True and free == -1
    breached, free = guard.state(force=True)  # nth=1 consumed: clean now
    assert breached is False and free > 0
    faults.reset("")

    # route level: the ONE gate (ServeContext.upsert_execute) renders
    # the 507 (the HTTP-level battery lives in tests/test_maintenance.py)
    registry = MetricsRegistry()
    mgr = SnapshotManager(store_dir, log=lambda m: None)
    mem = Memtable(
        width=8, store_dir=store_dir,
        wal=WriteAheadLog(store_dir, "serve-dg", log=lambda m: None),
        registry=registry, log=lambda m: None,
    )
    httpd = start_server(manager=MemtableSnapshots(mgr, mem),
                         memtable=mem, registry=registry)
    ctx = httpd.ctx
    try:
        ctx.disk_guard = DiskReserveGuard(store_dir, reserve=1,
                                          ttl_s=0.0, log=lambda m: None)
        body = json.dumps(
            {"variants": [{"id": "6:999999:A:G"}]}
        ).encode()
        faults.reset("maintain.disk_guard:1:raise")
        status, text, _rows = ctx.upsert_execute(body)
        assert status == 507
        assert json.loads(text)["error"] == MSG_DISK_RESERVE
        assert mem.rows == 0  # nothing durable, nothing visible
        # the degraded window clears on the next clean reading: the
        # SAME request now acks durably
        status, text, _rows = ctx.upsert_execute(body)
        assert status == 200
        assert json.loads(text)["accepted"] == 1
        assert mem.rows == 1
    finally:
        faults.reset("")
        stop_server(httpd)
        mem.wal.close(remove_if_empty=True)


# ---------------------------------------------------------------------------
# mesh.dispatch — a device failure inside the sharded mesh gather
# (serve/mesh_exec).  The contract: the mesh breaker group absorbs it on
# the byte-identical single-device path (never wrong bytes), repeated
# failures trip the group open so the sharded attempt stops being paid,
# and a half-open probe re-closes it once the device heals.


def test_mesh_dispatch_raise_bulk_falls_back_byte_identical():
    """mesh.dispatch (raise) during a bulk lookup: the answer bytes are
    the single-device path's, the breaker's mesh group trips after the
    threshold (no further sharded attempt fires while open), and the
    cooled-down half-open probe re-closes it."""
    from annotatedvdb_tpu.parallel.mesh import global_mesh
    from annotatedvdb_tpu.serve import (
        DeviceBreaker,
        MeshExecutor,
        QueryEngine,
        StaticSnapshots,
    )
    from annotatedvdb_tpu.serve.mesh_exec import MESH_GROUP

    mesh = global_mesh()
    assert mesh is not None  # conftest forces the 8-device host platform
    snaps = StaticSnapshots(_tiny_store())
    plain = QueryEngine(snaps, region_cache_size=0)
    clock = {"t": 0.0}
    breaker = DeviceBreaker(cooldown_s=5.0, clock=lambda: clock["t"])
    engine = QueryEngine(
        snaps, region_cache_size=0, breaker=breaker,
        mesh=MeshExecutor(mesh, breaker=breaker, bulk_min=0),
    )
    ids = ["3:10:A:C", "3:20:A:C", "3:30:A:C", "3:99:A:C"]
    want = plain.lookup_many(ids)
    assert engine.lookup_many(ids) == want  # mesh path agrees unarmed
    faults.reset("mesh.dispatch:prob:1.0:raise")
    try:
        for _ in range(breaker.failure_threshold):
            # every failing dispatch still answers, byte-identical
            # (single-device fallback)
            assert engine.lookup_many(ids) == want
        assert breaker.state(MESH_GROUP) == "open"
        # while tripped the sharded call is never attempted: the armed
        # fault cannot fire
        fired_before = faults.fired().get("mesh.dispatch", 0)
        assert engine.lookup_many(ids) == want
        assert faults.fired().get("mesh.dispatch", 0) == fired_before
    finally:
        faults.reset("")
    # cooldown lapses, fault cleared: the half-open probe re-closes
    clock["t"] = 10.0
    assert engine.lookup_many(ids) == want
    assert breaker.state(MESH_GROUP) == "closed"


def test_mesh_dispatch_eio_panel_falls_back_byte_identical():
    """mesh.dispatch (eio) during a region panel: the batch answers
    byte-identically through the single-device spans path, and the
    engine keeps serving mesh panels once the fault clears."""
    from annotatedvdb_tpu.parallel.mesh import global_mesh
    from annotatedvdb_tpu.serve import (
        DeviceBreaker,
        MeshExecutor,
        QueryEngine,
        StaticSnapshots,
    )

    mesh = global_mesh()
    assert mesh is not None
    snaps = StaticSnapshots(_tiny_store())
    plain = QueryEngine(snaps, region_cache_size=0)
    breaker = DeviceBreaker(cooldown_s=0.0)
    engine = QueryEngine(
        snaps, region_cache_size=0, breaker=breaker,
        mesh=MeshExecutor(mesh, breaker=breaker, bulk_min=0),
    )
    specs = ["3:1-100", "3:5-25", "7:1-50"]
    want = plain.regions_serve(specs).assemble()
    assert engine.regions_serve(specs).assemble() == want
    faults.reset("mesh.dispatch:1:eio")
    try:
        assert engine.regions_serve(specs).assemble() == want
    finally:
        faults.reset("")
    # unarmed: the mesh panel path serves again, same bytes
    assert engine.regions_serve(specs).assemble() == want


# ---------------------------------------------------------------------------
# obs.flight — the crash flight recorder (obs/flight.py).  Contract:
# observability must NEVER take down serving — an injected failure inside
# a ring write costs exactly that record, a failure inside the
# supervisor's harvest costs exactly that harvest, and a REAL SIGKILL
# through the serve CLI leaves a harvested black box holding the killed
# worker's final requests.


def test_obs_flight_ring_write_failure_absorbed_while_serving(tmp_path):
    """obs.flight (raise) inside a request-summary write: the request
    still answers 200, the failure is counted, recording continues."""
    import urllib.request

    from annotatedvdb_tpu.obs.flight import FlightRecorder, decode_ring

    store_dir = str(tmp_path / "fstore")
    _tiny_store().save(store_dir)
    ring = str(tmp_path / "w0.ring")
    flight = FlightRecorder(ring, slots=16, log=lambda m: None)
    httpd = start_server(store_dir=store_dir, flight=flight)
    try:
        port = httpd.server_address[1]

        def get(path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10
            ) as r:
                return r.status

        faults.reset("obs.flight:1:raise")
        assert get("/variant/3:10:A:C") == 200  # the write failure is silent
        faults.reset("")
        assert get("/variant/3:20:A:C") == 200
        assert flight.errors == 1
        flight.flush()
        reqs = [e for e in decode_ring(ring)["events"]
                if e["type"] == "request"]
        # exactly the injected record is missing; recording resumed
        assert len(reqs) == 1
    finally:
        faults.reset("")
        stop_server(httpd)
        flight.close()


def test_obs_flight_harvest_failure_absorbed_by_supervisor(tmp_path):
    """obs.flight (eio) inside the supervisor's harvest: the fleet's
    absorb wrapper logs and continues — a broken black box must never
    stall the respawn loop."""
    from annotatedvdb_tpu.obs import flight as flight_mod
    from annotatedvdb_tpu.serve.fleet import ServeFleet

    store_dir = str(tmp_path / "hstore")
    _tiny_store().save(store_dir)
    ring = flight_mod.ring_path(store_dir, 0)
    fr = flight_mod.FlightRecorder(ring, slots=8)
    fr.request("abc", "point", 200, 0.001, [])
    fr.close()
    fleet = ServeFleet(store_dir, port=0, workers=1, log=lambda m: None)
    try:
        faults.reset("obs.flight:1:eio")
        fleet._harvest_flight(0, "died rc=-9")  # absorbed, never raises
        faults.reset("")
        assert flight_mod.list_blackboxes(store_dir)["harvested"] == []
        # unarmed: the same harvest lands
        fleet._harvest_flight(0, "died rc=-9")
        assert len(
            flight_mod.list_blackboxes(store_dir)["harvested"]
        ) == 1
    finally:
        faults.reset("")
        fleet._reserve.close()
        if fleet._sup_flight is not None:
            fleet._sup_flight.close()
        import shutil

        from annotatedvdb_tpu.obs import reqtrace as _rt

        _rt.set_background_sink(None, None)
        shutil.rmtree(fleet._telemetry_dir, ignore_errors=True)
        fleet._hb_mm.close()
        os.unlink(fleet._hb_path)


def test_obs_flight_sigkill_harvest_holds_final_requests(tmp_path):
    """A REAL worker SIGKILL through the serve CLI: requests land on the
    worker's mmap'd ring, the chaos route kills it mid-accept, and the
    supervisor's harvest under <store>/flight/ holds the killed worker's
    final request summaries — the black-box acceptance contract."""
    import re
    import subprocess
    import urllib.request

    from annotatedvdb_tpu.obs import flight as flight_mod

    store_dir = str(tmp_path / "kstore")
    _tiny_store().save(store_dir)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        AVDB_SERVE_CHAOS="1",
    )
    env.pop("AVDB_FAULT", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "annotatedvdb_tpu", "serve",
         "--storeDir", store_dir, "--port", "0", "--workers", "2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        line = proc.stdout.readline()
        m = re.search(r"http://([\d.]+):(\d+)", line)
        assert m, f"no fleet address line: {line!r}"
        host, port = m.group(1), int(m.group(2))

        def get(path, timeout=5):
            with urllib.request.urlopen(
                f"http://{host}:{port}{path}", timeout=timeout
            ) as r:
                return r.status, r.read()

        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                if get("/healthz")[0] == 200:
                    break
            except OSError:
                time.sleep(0.3)
        # traffic until BOTH workers' rings hold request summaries (the
        # kernel round-robins accepts, but /healthz answers as soon as one
        # worker listens: a worker that came up after the traffic, or was
        # killed inside the recorder's flush cadence, harvests nothing)
        def recorded(worker):
            ring = flight_mod.ring_path(store_dir, worker)
            return os.path.isfile(ring) and any(
                e["type"] == "request"
                for e in flight_mod.decode_ring(ring)["events"]
            )

        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            for i in range(40):
                try:
                    get(f"/variant/3:{(i % 3 + 1) * 10}:A:C")
                except OSError:
                    pass
            if recorded(0) and recorded(1):
                break
            time.sleep(flight_mod.FlightRecorder.FLUSH_S)
        # arm a kill in whichever worker answers: it dies mid-accept
        body = json.dumps({"spec": "serve.accept:1:kill"}).encode()
        req = urllib.request.Request(
            f"http://{host}:{port}/_chaos", data=body, method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=5):
            pass
        # trip it + wait for the supervisor to harvest and respawn
        for _ in range(10):
            try:
                get("/variant/3:10:A:C", timeout=2)
            except OSError:
                pass
            time.sleep(0.2)
        harvested = []
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            harvested = flight_mod.list_blackboxes(store_dir)["harvested"]
            if harvested:
                break
            time.sleep(0.5)
        assert harvested, "the supervisor never harvested the killed " \
                          "worker's flight ring"
        data = flight_mod.load_harvest(harvested[0])
        assert "died rc=-9" in data["meta"]["reason"]
        reqs = [e for e in data["events"] if e["type"] == "request"]
        assert reqs, "the harvested black box holds no request summaries"
        assert any(e["kind"] == "point" and e["status"] == 200
                   and e.get("stages") for e in reqs)
        # the fleet telemetry plane on the REAL fleet: any worker's
        # ?fleet=1 answers for the whole fleet, incl. the supervisor's
        # respawn counter the kill just incremented (workers publish
        # snapshots ~1 Hz; give the plane a moment to converge)
        deadline = time.monotonic() + 30
        fleet_ok = False
        while time.monotonic() < deadline and not fleet_ok:
            try:
                _s, body = get("/metrics?fleet=1")
                text = body.decode()
                fleet_ok = ("avdb_fleet_workers_live 2" in text
                            and "avdb_fleet_respawns_total 1" in text)
            except OSError:
                pass
            if not fleet_ok:
                time.sleep(0.5)
        assert fleet_ok, "?fleet=1 never showed 2 live workers and the " \
                         "respawn the kill caused"
    finally:
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    assert rc == 0, proc.stdout.read()[-2000:]


# ---------------------------------------------------------------------------
# obs.tick — the health plane's fault point (obs/timeseries.py,
# obs/slo.py).  Same contract as obs.flight: observability must NEVER
# take down serving — a failing snapshot costs one tick, a failing
# mirror write costs one persist (the previous file survives tmp+rename),
# a failing supervisor harvest costs exactly that harvest.


@pytest.mark.parametrize("fault", [
    "obs.tick:1:raise",
    "obs.tick:1:eio",
])
def test_obs_tick_sample_fault_absorbed_ring_continues(tmp_path, fault):
    """An injected failure inside the snapshot costs one tick: absorbed,
    logged once, counted — and the NEXT tick samples normally."""
    from annotatedvdb_tpu.obs.metrics import MetricsRegistry
    from annotatedvdb_tpu.obs.timeseries import TimeSeriesRing

    logs: list = []
    ring = TimeSeriesRing(
        MetricsRegistry(), worker=0,
        path=str(tmp_path / "w0.ts.json"),
        tick_s=0.01, history_s=60.0, log=logs.append,
    )
    faults.reset(fault)
    try:
        assert ring.tick() is False  # absorbed, not raised
        assert ring.errors == 1
        assert any("tick failed" in m for m in logs), logs
        assert ring.samples() == []
        # nth=1 consumed: the next tick runs normally
        assert ring.tick() is True
        assert len(ring.samples()) == 1
    finally:
        faults.reset("")


def test_obs_tick_persist_fault_keeps_previous_mirror(tmp_path):
    """A failing mirror write costs one persist: the sample still lands
    in the ring and the previously persisted file stays readable (the
    write is tmp+rename)."""
    from annotatedvdb_tpu.obs.metrics import MetricsRegistry
    from annotatedvdb_tpu.obs.timeseries import (
        TimeSeriesRing,
        load_history,
    )

    ring = TimeSeriesRing(
        MetricsRegistry(), worker=0,
        path=str(tmp_path / "w0.ts.json"),
        tick_s=0.01, history_s=60.0, log=lambda m: None,
    )
    ring.sample()
    ring.persist(force=True)
    assert len(load_history(ring.path)["samples"]) == 1
    # fire #1 passes the sample, fire #2 dies inside the persist
    # (re-open the PERSIST_S gate so the tick actually attempts it)
    ring._last_persist = -1e9
    faults.reset("obs.tick:2:eio")
    try:
        assert ring.tick() is False
        assert ring.errors == 1
        assert len(ring.samples()) == 2  # the sample half landed
        # the previous mirror is intact — no torn document
        assert len(load_history(ring.path)["samples"]) == 1
    finally:
        faults.reset("")
    ring.persist(force=True)  # unarmed: the mirror catches up
    assert len(load_history(ring.path)["samples"]) == 2


def test_obs_tick_fault_while_serving_requests_still_answer(tmp_path):
    """obs.tick (raise) under the server's maintenance tick: requests
    keep answering 200 while a tick dies, the failure is counted, and
    the next due tick samples normally."""
    import urllib.request

    from annotatedvdb_tpu.obs.metrics import MetricsRegistry
    from annotatedvdb_tpu.obs.slo import HealthPlane

    store_dir = str(tmp_path / "hstore")
    _tiny_store().save(store_dir)
    registry = MetricsRegistry()
    health = HealthPlane(registry, store_dir=store_dir, worker=0,
                         tick_s=0.01, history_s=60.0)
    httpd = start_server(store_dir=store_dir, registry=registry,
                         health=health)
    try:
        port = httpd.server_address[1]

        def get(path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10
            ) as r:
                return r.status

        def wait_for(cond):
            deadline = time.monotonic() + 10
            while not cond() and time.monotonic() < deadline:
                assert get("/variant/3:10:A:C") == 200
                time.sleep(0.02)
            return cond()

        faults.reset("obs.tick:1:raise")
        assert wait_for(lambda: health.errors >= 1)  # a tick died silently
        faults.reset("")
        assert health.errors == 1
        n0 = len(health.ring.samples())
        # recording resumed on the next due tick
        assert wait_for(lambda: len(health.ring.samples()) > n0)
    finally:
        faults.reset("")
        stop_server(httpd)


def test_obs_tick_harvest_failure_absorbed_by_supervisor(tmp_path):
    """obs.tick (eio) inside the supervisor's history harvest: the
    fleet's absorb wrapper logs and continues — a broken history file
    must never stall the respawn loop."""
    from annotatedvdb_tpu.obs.metrics import MetricsRegistry
    from annotatedvdb_tpu.obs.timeseries import (
        TimeSeriesRing,
        history_path,
        list_history,
    )
    from annotatedvdb_tpu.serve.fleet import ServeFleet

    store_dir = str(tmp_path / "hstore2")
    _tiny_store().save(store_dir)
    ring = TimeSeriesRing(
        MetricsRegistry(), worker=0, path=history_path(store_dir, 0),
        tick_s=1.0, history_s=60.0,
    )
    ring.sample()
    ring.persist(force=True)
    fleet = ServeFleet(store_dir, port=0, workers=1, log=lambda m: None)
    try:
        faults.reset("obs.tick:1:eio")
        fleet._harvest_history(0, "died rc=-9")  # absorbed, never raises
        faults.reset("")
        assert list_history(store_dir)["harvested"] == []
        # unarmed: the same harvest lands, reason stamped in
        fleet._harvest_history(0, "died rc=-9")
        assert len(list_history(store_dir)["harvested"]) == 1
    finally:
        faults.reset("")
        fleet._reserve.close()
        if fleet._sup_flight is not None:
            fleet._sup_flight.close()
        import shutil

        from annotatedvdb_tpu.obs import reqtrace as _rt

        _rt.set_background_sink(None, None)
        shutil.rmtree(fleet._telemetry_dir, ignore_errors=True)
        fleet._hb_mm.close()
        os.unlink(fleet._hb_path)


# -- replication kill points (store/replication.py) ---------------------------


def _repl_leader(tmp_path, rows):
    """One in-process leader (store + memtable + WAL + server) with
    ``rows`` upserted — the replication matrix's write source."""
    from annotatedvdb_tpu.obs.metrics import MetricsRegistry
    from annotatedvdb_tpu.serve.snapshot import (
        MemtableSnapshots,
        SnapshotManager,
    )
    from annotatedvdb_tpu.store.memtable import Memtable
    from annotatedvdb_tpu.store.wal import WriteAheadLog

    store_dir = str(tmp_path / "repl-leader")
    _tiny_store().save(store_dir)
    mem = Memtable(
        width=8, store_dir=store_dir,
        wal=WriteAheadLog(store_dir, "serve-w0", log=lambda m: None),
        log=lambda m: None,
    )
    store = VariantStore.load(store_dir, readonly=True)
    for row in rows:
        mem.upsert(store, [row], durable=True)
    httpd = start_server(
        manager=MemtableSnapshots(
            SnapshotManager(store_dir, log=lambda m: None), mem
        ),
        memtable=mem,
    )
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    return store_dir, url, httpd


_REPL_ROWS = [
    {"code": 3, "pos": 15, "ref": "A", "alt": "G"},
    {"code": 3, "pos": 25, "ref": "AT", "alt": "A"},
]


@pytest.mark.parametrize("fault", ["repl.ship:1:raise", "repl.ship:1:eio"])
def test_repl_ship_fault_cycle_retries_to_identical_state(tmp_path, fault):
    """repl.ship fires on the leader's ship surface: the poisoned cycle
    fails whole (ReplError — nothing half-applied), and the NEXT cycle
    lands the follower on the leader's exact applied-LSN state."""
    from annotatedvdb_tpu.store import replication as repl

    store_dir, url, httpd = _repl_leader(tmp_path, _REPL_ROWS)
    fdir = str(tmp_path / "repl-follower")
    applied: list = []
    tailer = repl.ReplicaTailer(fdir, url, log=lambda m: None,
                                apply_rows=applied.extend)
    try:
        faults.reset(fault)
        with pytest.raises(repl.ReplError):
            tailer.sync_once()
        assert applied == []  # the failed cycle applied NOTHING
        faults.reset("")
        tailer.sync_once()
        assert [r["pos"] for r in applied] == [15, 25]
        # the mirror is byte-identical to the leader's stable stream
        for fname in repl.wal_files(store_dir):
            with open(os.path.join(store_dir, fname), "rb") as f:
                leader_bytes = f.read()
            with open(os.path.join(fdir, fname), "rb") as f:
                assert f.read() == leader_bytes
    finally:
        faults.reset("")
        stop_server(httpd)


def test_repl_apply_fault_restart_lands_on_applied_lsn_prefix(tmp_path):
    """repl.apply dies AFTER the shipped bytes are durable on the
    follower but BEFORE the overlay applied them: a restarted tailer
    recovers the records from its own mirror and the live stream applies
    each acked row exactly once — a consistent applied-LSN prefix, never
    a hybrid."""
    from annotatedvdb_tpu.store import replication as repl

    store_dir, url, httpd = _repl_leader(tmp_path, _REPL_ROWS)
    fdir = str(tmp_path / "repl-follower")
    try:
        t1 = repl.ReplicaTailer(fdir, url, log=lambda m: None)
        t1.bootstrap()  # cut installed; WAL tail not mirrored yet
        applied: list = []
        t1.apply_rows = applied.extend
        faults.reset("repl.apply:1:raise")
        with pytest.raises(faults.InjectedFault):
            t1.sync_once()
        faults.reset("")
        assert applied == []  # durable locally, applied nowhere

        # restart: a fresh incarnation resumes from the mirror alone
        t2 = repl.ReplicaTailer(fdir, url, log=lambda m: None)
        recovered = t2.resume()
        replayed = [r["pos"] for rec in t2.local_records()
                    for r in rec["rows"]]
        live: list = []
        t2.apply_rows = live.extend
        t2.sync_once()
        total = replayed + [r["pos"] for r in live]
        # every acked row exactly once, in WAL order — no loss, no dupes
        assert sorted(total) == [15, 25]
        assert recovered + len(live) >= 1
    finally:
        faults.reset("")
        stop_server(httpd)


def test_repl_promote_fault_leaves_promotable_follower(tmp_path):
    """repl.promote (raise, hit #1 — before any mutation): the follower
    is byte-untouched and promotes cleanly on re-run; the deposed
    leader's flush is fenced afterwards."""
    from annotatedvdb_tpu.store import replication as repl
    from annotatedvdb_tpu.store.memtable import Memtable

    store_dir, url, httpd = _repl_leader(tmp_path, _REPL_ROWS)
    fdir = str(tmp_path / "repl-follower")
    try:
        tailer = repl.ReplicaTailer(fdir, url, log=lambda m: None)
        tailer.bootstrap()
        tailer.sync_once()
        before = sorted(os.listdir(fdir))

        faults.reset("repl.promote:1:raise")
        with pytest.raises(faults.InjectedFault):
            repl.promote(fdir, log=lambda m: None)
        faults.reset("")
        assert sorted(os.listdir(fdir)) == before  # byte-untouched
        with open(os.path.join(fdir, "manifest.json")) as f:
            assert json.load(f).get("repl_epoch", 0) == 0

        out = repl.promote(fdir, log=lambda m: None)
        assert out["status"] == "promoted" and out["epoch"] == 1
        promoted = VariantStore.load(fdir, readonly=True)
        assert promoted.n == 5  # 3 seed + 2 tailed rows sealed

        # deposed-leader write fenced: a writer that opened the store
        # under the old epoch cannot commit a flush over the new lineage
        deposed = Memtable(width=8, store_dir=fdir, wal=None,
                           log=lambda m: None, fence_epoch=0)
        deposed.upsert(
            promoted, [{"code": 3, "pos": 99, "ref": "A", "alt": "G"}],
            durable=False,
        )
        result = deposed.flush()
        assert result["status"] == "aborted"
        assert "fenced" in result["reason"]
    finally:
        faults.reset("")
        stop_server(httpd)


# ---------------------------------------------------------------------------
# fsck.repair — the repair pass's own manifest commit is a crash point too


@pytest.mark.parametrize("fault", [
    "fsck.repair:1:raise",
    "fsck.repair:1:eio",
])
def test_fsck_repair_commit_fault_leaves_diagnosable_store(tmp_path, fault):
    """``fsck.repair`` fires while the rolled-back manifest is staged (tmp
    written, atomic replace not yet done): a death there must leave the
    damaged-but-diagnosed store byte-identical — the OLD manifest still
    serving — so the next repair run diagnoses the same damage and
    converges.  Repair is idempotent; its commit is one atomic replace."""
    vcf = str(tmp_path / "d.vcf")
    _write_vcf(vcf, n=300)
    store_dir = str(tmp_path / "store")
    counters, exc = _run_load(store_dir, vcf)
    assert exc is None, exc
    # tear one referenced segment: size mismatch vs its integrity record
    seg = next(f for f in sorted(os.listdir(store_dir))
               if f.endswith(".npz"))
    with open(os.path.join(store_dir, seg), "r+b") as f:
        f.truncate(16)
    mpath = os.path.join(store_dir, "manifest.json")
    with open(mpath, "rb") as f:
        manifest_before = f.read()

    faults.reset(fault)
    try:
        with pytest.raises((faults.InjectedFault, OSError)):
            fsck(store_dir, repair=True, log=lambda m: None)
    finally:
        faults.reset("")
    # the commit never happened: the old manifest is byte-identical and
    # the damage is still on disk for the next run to diagnose
    with open(mpath, "rb") as f:
        assert f.read() == manifest_before

    # unarmed re-run converges: the damaged group rolls back, debris is
    # pruned, and the store then deep-fscks clean
    report = fsck(store_dir, repair=True, log=lambda m: None)
    assert report["exit_code"] in (0, 1), report
    assert fsck(store_dir, deep=True,
                log=lambda m: None)["exit_code"] == 0


# ---------------------------------------------------------------------------
# export.plan / export.pack / export.commit — the training-corpus export
# subsystem's kill points (export/core.py + export/writer.py).  Contract:
# a death at ANY of them leaves the output directory a committed-part
# PREFIX of the reference corpus (possibly empty, possibly plus prunable
# ``*.export.tmp*`` debris — never a torn part), and ``--resume``
# completes to bytes IDENTICAL to the uninterrupted run.


def _corpus_bytes(out_dir):
    if not os.path.isdir(out_dir):
        return {}
    out = {}
    for fname in sorted(os.listdir(out_dir)):
        if fname.endswith(".npz") or fname == "corpus.manifest.json":
            with open(os.path.join(out_dir, fname), "rb") as f:
                out[fname] = f.read()
    return out


@pytest.fixture()
def export_refs(tmp_path):
    """(store, ledger, store_dir, reference corpus bytes): a tiny store
    whose whole-store export makes 2 one-batch parts — enough that every
    export kill point has a real committed prefix to land on."""
    from annotatedvdb_tpu.export.core import run_export

    store_dir = str(tmp_path / "estore")
    _tiny_store().save(store_dir)
    store, ledger = StoreConfig(store_dir).open(create=False,
                                                readonly=True)
    ref_dir = str(tmp_path / "eref")
    summary = run_export(store, ledger, store_dir, ref_dir, seed=5,
                         batch_rows=2, part_bytes=1)
    assert summary["parts_written"] == 2 and summary["complete"]
    return store, ledger, store_dir, _corpus_bytes(ref_dir)


@pytest.mark.parametrize("fault", [
    "export.plan:1:raise",
    "export.plan:1:eio",
])
def test_export_plan_fault_leaves_out_dir_untouched(export_refs, tmp_path,
                                                    fault):
    """export.plan fires after the plan exists in memory, before anything
    touches the output directory: a death there must leave NO output
    directory at all, and an unarmed re-run (no resume needed — nothing
    was committed) produces the reference corpus."""
    from annotatedvdb_tpu.export.core import run_export

    store, ledger, store_dir, want = export_refs
    out_dir = str(tmp_path / "out")
    faults.reset(fault)
    try:
        with pytest.raises((faults.InjectedFault, OSError)):
            run_export(store, ledger, store_dir, out_dir, seed=5,
                       batch_rows=2, part_bytes=1)
    finally:
        faults.reset("")
    assert not os.path.exists(out_dir)  # byte-untouched means ABSENT
    run_export(store, ledger, store_dir, out_dir, seed=5,
               batch_rows=2, part_bytes=1)
    assert _corpus_bytes(out_dir) == want


@pytest.mark.parametrize("fault", [
    "export.pack:2:raise",
    "export.pack:2:eio",
])
def test_export_pack_fault_lands_on_prefix_resume_completes(export_refs,
                                                            tmp_path,
                                                            fault):
    """export.pack fires per tokenized batch, before staging: nth=2 dies
    with part 0 already committed.  The durable state must be exactly the
    reference's part-0 prefix (no manifest — it commits last), and
    ``resume=True`` must complete to reference bytes without repacking
    the committed part."""
    from annotatedvdb_tpu.export.core import run_export

    store, ledger, store_dir, want = export_refs
    out_dir = str(tmp_path / "out")
    faults.reset(fault)
    try:
        with pytest.raises((faults.InjectedFault, OSError)):
            run_export(store, ledger, store_dir, out_dir, seed=5,
                       batch_rows=2, part_bytes=1)
    finally:
        faults.reset("")
    got = _corpus_bytes(out_dir)
    assert set(got) == {"part-000000.npz"}  # committed prefix, no manifest
    assert got["part-000000.npz"] == want["part-000000.npz"]
    summary = run_export(store, ledger, store_dir, out_dir, seed=5,
                         batch_rows=2, part_bytes=1, resume=True)
    assert summary["resumed_parts"] == 1 and summary["parts_written"] == 1
    assert _corpus_bytes(out_dir) == want


@pytest.mark.parametrize("fault,n_committed", [
    ("export.commit:1:raise", 0),   # dies staging part 0
    ("export.commit:2:raise", 1),   # dies staging part 1 (part 0 durable)
    ("export.commit:2:eio", 1),
    ("export.commit:3:raise", 2),   # dies on the manifest temp, parts done
])
def test_export_commit_fault_strands_only_debris_resume_identical(
        export_refs, tmp_path, fault, n_committed):
    """export.commit fires on every staged temp (each part's, then the
    manifest's) after the body is written, before its fsync/rename: a
    death there strands exactly one ``*.export.tmp*`` temp next to the
    committed prefix — never a torn part — and resume prunes the debris
    and completes to reference bytes."""
    from annotatedvdb_tpu.export.core import run_export
    from annotatedvdb_tpu.export.writer import is_export_tmp

    store, ledger, store_dir, want = export_refs
    out_dir = str(tmp_path / "out")
    faults.reset(fault)
    try:
        with pytest.raises((faults.InjectedFault, OSError)):
            run_export(store, ledger, store_dir, out_dir, seed=5,
                       batch_rows=2, part_bytes=1)
    finally:
        faults.reset("")
    debris = [f for f in os.listdir(out_dir) if is_export_tmp(f)]
    assert len(debris) == 1, debris
    got = _corpus_bytes(out_dir)
    assert set(got) == {f"part-{n:06d}.npz" for n in range(n_committed)}
    for fname, body in got.items():
        assert body == want[fname]
    summary = run_export(store, ledger, store_dir, out_dir, seed=5,
                         batch_rows=2, part_bytes=1, resume=True)
    assert summary["resumed_parts"] == n_committed
    assert _corpus_bytes(out_dir) == want
    assert [f for f in os.listdir(out_dir) if is_export_tmp(f)] == []
