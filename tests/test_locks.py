"""Lock-order / deadlock detector battery (``utils.locks`` +
``analysis.lockorder``).

Three layers:

- **wrapper mechanics** — ``make_lock`` is a plain stdlib lock unarmed
  (the production path pays nothing) and a recording ``TracedLock`` under
  ``AVDB_LOCK_TRACE=1``;
- **detector semantics** — an ABBA inversion across two threads is
  reported as a cycle, consistent orderings and reentrant re-acquires are
  not, held durations land in the ``avdb_lock_held_seconds`` histogram;
- **serve battery under trace** — the real serve stack (engine + batcher
  + ServeContext admission + snapshot pin) driven concurrently with
  tracing armed must produce ZERO cycles: the tier-1 half of the
  acceptance gate (``tools/run_checks.sh`` arms the serve smoke the same
  way for the full-HTTP version).
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from annotatedvdb_tpu.analysis.lockorder import RECORDER, LockOrderRecorder
from annotatedvdb_tpu.utils.locks import TracedLock, make_lock
from conftest import BatcherOnLoop


# ---------------------------------------------------------------------------
# wrapper mechanics


def test_make_lock_unarmed_is_plain_stdlib_lock(monkeypatch):
    monkeypatch.delenv("AVDB_LOCK_TRACE", raising=False)
    lock = make_lock("x")
    assert type(lock) is type(threading.Lock())
    rlock = make_lock("x", reentrant=True)
    assert type(rlock) is type(threading.RLock())


def test_make_lock_armed_returns_traced(monkeypatch):
    monkeypatch.setenv("AVDB_LOCK_TRACE", "1")
    lock = make_lock("test.armed")
    assert isinstance(lock, TracedLock)
    with lock:
        assert lock.locked()
    assert not lock.locked()


def test_traced_lock_api_matches_stdlib():
    rec = LockOrderRecorder()
    lock = TracedLock("test.api", recorder=rec)
    assert lock.acquire()
    assert not lock.acquire(blocking=False)  # held: non-blocking fails
    lock.release()
    assert lock.acquire(timeout=1.0)
    lock.release()
    assert rec.held_stats()["test.api"]["count"] == 2


def test_failed_acquire_records_nothing():
    rec = LockOrderRecorder()
    a = TracedLock("test.a", recorder=rec)
    b = TracedLock("test.b", recorder=rec)
    with a:
        done = threading.Event()

        def contender():
            # a is held by the main thread: this acquire must fail and
            # leave no (b -> a) ordering edge behind
            with b:
                assert not a.acquire(blocking=False)
            done.set()

        t = threading.Thread(target=contender)
        t.start()
        assert done.wait(5)
        t.join()
    assert ("test.b", "test.a") not in rec.snapshot_edges()


# ---------------------------------------------------------------------------
# detector semantics


def _run_threads(*fns):
    threads = [threading.Thread(target=fn) for fn in fns]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)


def test_abba_inversion_is_a_cycle():
    rec = LockOrderRecorder()
    a = TracedLock("order.a", recorder=rec)
    b = TracedLock("order.b", recorder=rec)
    gate = threading.Event()

    def t1():
        with a:
            with b:
                pass
        gate.set()

    def t2():
        gate.wait(5)  # sequential: records the inverted ORDER, no hang
        with b:
            with a:
                pass

    _run_threads(t1, t2)
    cycles = rec.cycles()
    assert len(cycles) == 1
    assert set(cycles[0]) == {"order.a", "order.b"}


def test_consistent_order_is_clean():
    rec = LockOrderRecorder()
    a = TracedLock("order.a", recorder=rec)
    b = TracedLock("order.b", recorder=rec)

    def worker():
        for _ in range(50):
            with a:
                with b:
                    pass

    _run_threads(worker, worker, worker)
    assert rec.cycles() == []
    assert rec.snapshot_edges() == {("order.a", "order.b"): 150}


def test_three_lock_cycle_detected():
    rec = LockOrderRecorder()
    locks = {n: TracedLock(f"tri.{n}", recorder=rec) for n in "abc"}

    def pair(x, y):
        with locks[x]:
            with locks[y]:
                pass

    pair("a", "b")
    pair("b", "c")
    pair("c", "a")
    cycles = rec.cycles()
    assert len(cycles) == 1
    assert set(cycles[0]) == {"tri.a", "tri.b", "tri.c"}


def test_reentrant_acquire_no_self_edge():
    rec = LockOrderRecorder()
    r = TracedLock("re.lock", reentrant=True, recorder=rec)
    with r:
        with r:
            pass
    assert rec.cycles() == []
    assert rec.snapshot_edges() == {}
    # both nesting levels accounted as holds
    assert rec.held_stats()["re.lock"]["count"] == 2


def test_hand_over_hand_release_order():
    rec = LockOrderRecorder()
    a = TracedLock("hoh.a", recorder=rec)
    b = TracedLock("hoh.b", recorder=rec)
    a.acquire()
    b.acquire()
    a.release()  # release order != acquire order
    b.release()
    assert rec.cycles() == []
    stats = rec.held_stats()
    assert stats["hoh.a"]["count"] == 1 and stats["hoh.b"]["count"] == 1


def test_held_histogram_exported_through_obs_registry():
    rec = LockOrderRecorder()
    lock = TracedLock("hist.lock", recorder=rec)
    for _ in range(5):
        with lock:
            pass
    snap = rec.registry.snapshot()
    series = snap["avdb_lock_held_seconds"]
    (entry,) = [e for e in series if e["labels"] == {"lock": "hist.lock"}]
    assert entry["count"] == 5
    assert "avdb_lock_held_seconds_bucket" in rec.render_prometheus()


def test_report_shape_and_reset():
    rec = LockOrderRecorder()
    a = TracedLock("rep.a", recorder=rec)
    with a:
        pass
    rep = rec.report()
    assert rep["locks"] == ["rep.a"]
    assert rep["cycles"] == []
    assert rep["held"]["rep.a"]["count"] == 1
    rec.reset()
    assert rec.report() == {
        "locks": [], "edges": {}, "cycles": [], "held": {},
    }


# ---------------------------------------------------------------------------
# serve battery under AVDB_LOCK_TRACE=1


def _tiny_store(store_dir: str) -> int:
    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.types import encode_allele_array

    width = 8
    store = VariantStore(width=width)
    n = 64
    refs = ["A", "C", "G", "T"] * (n // 4)
    alts = ["G", "T", "A", "C"] * (n // 4)
    ref, ref_len = encode_allele_array(refs, width)
    alt, alt_len = encode_allele_array(alts, width)
    h = identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts)
    store.shard(8).append(
        {"pos": np.arange(1000, 1000 + 97 * n, 97, dtype=np.int32)[:n],
         "h": h, "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
        annotations={"cadd_scores": [
            {"CADD_phred": float(i)} if i % 2 else None for i in range(n)
        ]},
    )
    store.save(store_dir)
    return n


@pytest.fixture()
def traced_recorder(monkeypatch):
    """Arm tracing on the GLOBAL recorder for a serve-stack build."""
    monkeypatch.setenv("AVDB_LOCK_TRACE", "1")
    RECORDER.reset()
    yield RECORDER
    RECORDER.reset()


def test_serve_battery_traces_clean(tmp_path, traced_recorder):
    """The real serve stack's hot paths — point batching, bulk lookup,
    region reads (index build + LRU), admission accounting, snapshot
    refresh — driven concurrently under tracing: the acquisition-order
    graph must be acyclic, and the stack's named locks must actually
    show up (an empty graph would mean the battery proved nothing)."""
    from annotatedvdb_tpu.obs.metrics import MetricsRegistry
    from annotatedvdb_tpu.serve.engine import QueryEngine
    from annotatedvdb_tpu.serve.http import ServeContext
    from annotatedvdb_tpu.serve.snapshot import SnapshotManager

    store_dir = str(tmp_path / "store")
    _tiny_store(store_dir)
    manager = SnapshotManager(store_dir)
    registry = MetricsRegistry()
    engine = QueryEngine(manager, registry=registry, region_cache_size=8)
    batcher = BatcherOnLoop(engine, max_batch=16, max_wait_s=0.001,
                            registry=registry)
    ctx = ServeContext(manager, engine, batcher.batcher, registry)
    try:
        errors: list = []

        def hammer(salt: int):
            try:
                for i in range(20):
                    pos = 1000 + 97 * ((i + salt) % 64)
                    ref = ["A", "C", "G", "T"][(i + salt) % 4]
                    alt = ["G", "T", "A", "C"][(i + salt) % 4]
                    batcher.submit(f"8:{pos}:{ref}:{alt}")
                    engine.lookup_many(
                        [f"8:{1000 + 97 * j}:A:G" for j in range(4)]
                    )
                    engine.region("8:1-100000", limit=5,
                                  min_cadd=1.0 if i % 2 else None)
                    assert ctx.admit()
                    ctx.observe("point", 0.001, rows=1)
                    ctx.release()
                    ctx.refresh_snapshot()
            except Exception as err:  # surfaced below, not swallowed
                errors.append(err)

        _run_threads(*(lambda s=s: hammer(s) for s in range(4)))
        assert not errors, errors
    finally:
        batcher.close()
    rep = traced_recorder.report()
    assert rep["cycles"] == [], rep
    seen = set(rep["locks"])
    assert {"serve.engine.cache", "serve.ctx.inflight",
            "serve.snapshot.pin"} <= seen, seen
    assert rep["held"]["serve.ctx.inflight"]["count"] >= 80
