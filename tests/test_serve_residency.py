"""HBM hot-set residency: budget packing, hot/cold churn, generation
swaps, and — the property everything else leans on — byte-parity of
query results with and without a budget engaged (including with the
device probe path forced on, so the managed-cache branch really runs).
"""

from __future__ import annotations

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.serve import QueryEngine, StaticSnapshots
from annotatedvdb_tpu.serve.residency import (
    ResidencyManager,
    budget_from_env,
    device_cache_bytes,
    parse_bytes,
)
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.variant_store import Segment
from annotatedvdb_tpu.types import encode_allele_array

WIDTH = 8
SEG_ROWS = 64


def _segment_rows(base_pos: int, n: int = SEG_ROWS):
    refs = ["A", "C", "G", "T"][: 4] * (n // 4)
    alts = ["G", "T", "A", "C"][: 4] * (n // 4)
    ref, ref_len = encode_allele_array(refs, WIDTH)
    alt, alt_len = encode_allele_array(alts, WIDTH)
    h = identity_hashes(WIDTH, ref, alt, ref_len, alt_len, refs, alts)
    pos = np.arange(base_pos, base_pos + 31 * n, 31, dtype=np.int32)[:n]
    return {"pos": pos, "h": h, "ref_len": ref_len, "alt_len": alt_len}, \
        ref, alt, refs, alts, pos


def _build_store(n_segments: int = 4):
    """chr8 with n disjoint segments (direct append_segment: no merges),
    plus the list of (id, expected-position) queries per segment."""
    store = VariantStore(width=WIDTH)
    shard = store.shard(8)
    queries = []
    for s in range(n_segments):
        cols, ref, alt, refs, alts, pos = _segment_rows(1000 + s * 100_000)
        shard.append_segment(Segment.build(cols, ref, alt))
        shard._starts_cache = None
        queries.append([
            f"8:{int(p)}:{r}:{a}" for p, r, a in zip(pos, refs, alts)
        ])
    return store, shard, queries


def test_parse_bytes_and_env(monkeypatch):
    assert parse_bytes("1024") == 1024
    assert parse_bytes("4k") == 4096
    assert parse_bytes("2m") == 2 << 20
    assert parse_bytes("1.5g") == int(1.5 * (1 << 30))
    for bad in ("", "x", "-4", "4t"):
        with pytest.raises(ValueError):
            parse_bytes(bad)
    monkeypatch.delenv("AVDB_SERVE_HBM_BUDGET", raising=False)
    assert budget_from_env() is None
    monkeypatch.setenv("AVDB_SERVE_HBM_BUDGET", "512k")
    assert budget_from_env() == 512 << 10


def test_hot_set_respects_budget_and_faults_back():
    store, shard, queries = _build_store(4)
    seg_bytes = device_cache_bytes(shard.segments[0], WIDTH)
    # budget fits exactly ONE segment cache: the hottest segment and only
    # the hottest segment may be resident
    manager = ResidencyManager(
        budget_bytes=seg_bytes, upload=True, min_rows=1,
        async_upload=False, plan_interval_s=0.0,
    )
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, residency=manager
    )
    # hammer segment 0
    for _ in range(5):
        assert all(r is not None for r in engine.lookup_many(queries[0]))
    stats = manager.stats()
    assert stats["resident"] == 1
    assert stats["resident_bytes"] <= seg_bytes
    assert shard.segments[0]._device is not None
    assert all(s._device is None for s in shard.segments[1:])
    # now hammer segment 2: heat decays off segment 0, segment 2 faults in
    for _ in range(40):
        assert all(r is not None for r in engine.lookup_many(queries[2]))
    assert shard.segments[2]._device is not None     # faulted back in
    assert shard.segments[0]._device is None         # evicted to host
    assert manager.resident_bytes() <= seg_bytes
    # evicted segment still answers (host path) — byte-identical
    assert all(r is not None for r in engine.lookup_many(queries[0]))


def test_zero_budget_keeps_everything_on_host():
    store, shard, queries = _build_store(2)
    manager = ResidencyManager(budget_bytes=0, upload=True, min_rows=1,
                               async_upload=False, plan_interval_s=0.0)
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, residency=manager
    )
    assert all(r is not None for r in engine.lookup_many(queries[0]))
    assert all(s._device is None for s in shard.segments)
    assert manager.resident_bytes() == 0


def test_managed_segments_never_auto_upload():
    store, shard, queries = _build_store(2)
    manager = ResidencyManager(budget_bytes=1, upload=True, min_rows=1,
                               async_upload=False, plan_interval_s=0.0)
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, residency=manager
    )
    engine.lookup_many(queries[0])
    assert all(s.residency == "managed" for s in shard.segments)
    # budget of 1 byte fits nothing: no cache may ever appear
    for _ in range(10):
        engine.lookup_many(queries[0] + queries[1])
    assert all(s._device is None for s in shard.segments)


def test_generation_swap_drops_tracking():
    store, _shard, queries = _build_store(2)
    manager = ResidencyManager(budget_bytes=1 << 20, upload=True, min_rows=1,
                               async_upload=False, plan_interval_s=0.0)
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, residency=manager
    )
    engine.lookup_many(queries[0])
    assert manager.stats()["generation"] == 1
    store2, shard2, queries2 = _build_store(3)
    engine2 = QueryEngine(
        StaticSnapshots(store2, generation=2), region_cache_size=0,
        residency=manager,
    )
    engine2.lookup_many(queries2[0])
    stats = manager.stats()
    assert stats["generation"] == 2
    assert stats["candidates"] == 3
    assert all(s.residency == "managed" for s in shard2.segments)


def test_generation_swap_clears_displaced_residency():
    """govern() must flip resident=False on displaced entries: a queued
    upload batch on the uploader thread still holds them and gates on
    ``e.resident`` — a retired generation must never spend transfers or
    HBM, nor queue ahead of the new generation's hot set."""
    store, _shard, queries = _build_store(2)
    manager = ResidencyManager(budget_bytes=1 << 30, upload=True, min_rows=1,
                               async_upload=False, plan_interval_s=0.0)
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, residency=manager
    )
    engine.lookup_many(queries[0])
    displaced = list(manager._entries.values())
    assert any(e.resident for e in displaced)
    store2, _shard2, queries2 = _build_store(2)
    engine2 = QueryEngine(
        StaticSnapshots(store2, generation=2), region_cache_size=0,
        residency=manager,
    )
    engine2.lookup_many(queries2[0])
    assert manager.stats()["generation"] == 2
    assert not any(e.resident for e in displaced)


def test_govern_does_not_materialize_key_arrays():
    """govern()'s candidate scan must compute key bounds in O(1) from
    the first/last rows: a freshly loaded store has no combined-key
    arrays, and building them store-wide at govern time (which runs on
    the serving path right after a generation swap) stalls the event
    loop for seconds at genome scale."""
    store, shard, _q = _build_store(3)
    for s in shard.segments:
        s._key = None  # as VariantStore.load leaves them
    manager = ResidencyManager(budget_bytes=1 << 20, upload=False,
                               min_rows=1, async_upload=False,
                               plan_interval_s=0.0)
    manager.govern(StaticSnapshots(store).current())
    assert all(s._key is None for s in shard.segments)
    # O(1) bounds match the materialized truth exactly
    for e in manager._entries.values():
        assert e.key_min == e.seg.key_min
        assert e.key_max == e.seg.key_max


def test_stale_snapshot_cannot_regovern_backwards():
    """An in-flight request still holding a pre-swap snapshot must not
    re-install a retired generation's residency state over the newer
    one — that would displace the live entry set and strand its
    accounted device caches."""
    store1, _s1, queries1 = _build_store(2)
    store2, _s2, queries2 = _build_store(2)
    manager = ResidencyManager(budget_bytes=1 << 30, upload=True, min_rows=1,
                               async_upload=False, plan_interval_s=0.0)
    engine2 = QueryEngine(
        StaticSnapshots(store2, generation=2), region_cache_size=0,
        residency=manager,
    )
    engine2.lookup_many(queries2[0])
    live = list(manager._entries.values())
    assert any(e.resident for e in live)
    # a stale gen-1 snapshot arrives late: govern must be a no-op
    engine1 = QueryEngine(
        StaticSnapshots(store1, generation=1), region_cache_size=0,
        residency=manager,
    )
    engine1.lookup_many(queries1[0])
    assert manager.stats()["generation"] == 2
    assert list(manager._entries.values()) == live
    assert any(e.resident for e in live)


def test_upload_evicted_mid_transfer_drops_cache(monkeypatch):
    """A segment evicted WHILE its host->device transfer is in flight
    must not keep the cache: an installed cache on a ``resident=False``
    entry would be invisible to every future plan — unaccounted,
    unevictable HBM.  The uploader checks residency after the transfer
    (and the probe programs' warm) and installs the copy only then."""
    from annotatedvdb_tpu.serve.residency import _Entry

    store, shard, _queries = _build_store(1)
    seg = shard.segments[0]
    seg.residency = "managed"
    manager = ResidencyManager(
        budget_bytes=1 << 20, upload=True, min_rows=1, async_upload=False
    )
    entry = _Entry(seg, device_cache_bytes(seg, WIDTH))
    entry.resident = entry.pending = True
    manager._entries = {id(seg): entry}

    real = Segment._build_device_cache
    built = []

    def racing_upload(self, device=None):
        built.append(real(self, device))
        # a newer plan evicts mid-transfer
        entry.resident = entry.pending = False
        return built[-1]

    monkeypatch.setattr(Segment, "_build_device_cache", racing_upload)
    manager._do_uploads([entry])
    assert built and seg._device is None
    assert manager.resident_bytes() == 0
    assert manager.stats()["resident"] == 0


def test_planned_resident_is_pending_from_the_same_lock_hold():
    """Between a plan and its apply (another thread's ``/stats`` read can
    fall there) a segment the budget already counts is not reported
    resident: ``pending`` is set where ``resident`` is."""
    from annotatedvdb_tpu.serve.residency import _Entry

    store, shard, _queries = _build_store(1)
    seg = shard.segments[0]
    manager = ResidencyManager(
        budget_bytes=1 << 20, upload=True, min_rows=1, async_upload=False
    )
    entry = _Entry(seg, device_cache_bytes(seg, WIDTH))
    entry.score = 1.0
    manager._entries = {id(seg): entry}
    with manager._lock:
        plan = manager._plan([entry])
    assert plan == ([], [entry])
    between = manager.stats()
    assert between["resident"] == 0
    assert between["resident_bytes"] == entry.nbytes
    manager._apply(plan)
    assert manager.stats()["resident"] == 1 and seg._device is not None
    with manager._lock:
        entry.score = 0.0
        assert manager._plan([entry]) == ([entry], [])
    assert not entry.resident and not entry.pending


def test_evict_applied_after_reupload_keeps_cache():
    """The evict direction of the plan/apply race: an eviction applied
    AFTER a newer plan re-uploaded the segment must leave the fresh
    cache alone — dropping it would strand ``resident=True`` with no
    device bytes behind it (counted against the budget, served from
    host, never re-uploaded because it already looks resident)."""
    from annotatedvdb_tpu.serve.residency import _Entry

    store, shard, _queries = _build_store(1)
    seg = shard.segments[0]
    seg.residency = "managed"
    manager = ResidencyManager(
        budget_bytes=1 << 20, upload=True, min_rows=1, async_upload=False
    )
    entry = _Entry(seg, device_cache_bytes(seg, WIDTH))
    manager._entries = {id(seg): entry}
    # plan1 decided to evict; before its apply runs, a newer plan
    # re-uploads: resident=True with a landed cache
    entry.resident = True
    sentinel = object()
    seg._device = sentinel
    manager._apply(([entry], []))
    assert seg._device is sentinel
    assert manager.resident_bytes() == entry.nbytes
    # and the benign double-apply of a true eviction stays idempotent
    entry.resident = False
    manager._apply(([entry], []))
    manager._apply(([entry], []))
    assert seg._device is None
    assert manager.resident_bytes() == 0


def test_plan_cadence_bounds_plan_rate(monkeypatch):
    """Touches accumulate cheaply; the decay + sort + pack plan runs at
    most once per ``plan_interval_s`` no matter how many probe windows
    land — a bulk spanning many chromosome groups must not pay one plan
    per group, and plan cost must not scale with offered load."""
    store, _shard, queries = _build_store(2)
    manager = ResidencyManager(
        budget_bytes=1 << 20, upload=False, min_rows=1,
        async_upload=False, plan_interval_s=60.0,
    )
    plans = []
    real_plan = ResidencyManager._plan

    def counting_plan(self, entries, decay=1.0):
        plans.append(decay)
        return real_plan(self, entries, decay)

    monkeypatch.setattr(ResidencyManager, "_plan", counting_plan)
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, residency=manager
    )
    for _ in range(20):
        engine.lookup_many(queries[0] + queries[1])
    # interval far in the future: heat accumulated, zero plans ran
    assert not plans
    assert sum(e.score for e in manager._entries.values()) > 0


def test_decay_is_wall_clock():
    """Aging follows elapsed time, not plan count: back-to-back plans
    (a multi-group request) barely decay just-added heat, while an idle
    gap cools the whole set regardless of how few plans ran in it."""
    store, _shard, queries = _build_store(1)
    manager = ResidencyManager(
        budget_bytes=0, upload=False, min_rows=1,
        async_upload=False, plan_interval_s=0.0,
    )
    engine = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, residency=manager
    )
    engine.lookup_many(queries[0])
    entry = next(iter(manager._entries.values()))
    # the plan ran microseconds after the touch: near-zero elapsed decay
    assert entry.score >= SEG_ROWS * 0.9
    # simulate 5 idle minutes, then touch again: history is cold — only
    # the fresh window's heat remains (not old + new)
    with manager._lock:
        manager._last_plan -= 300.0
    engine.lookup_many(queries[0])
    assert entry.score <= SEG_ROWS * 1.01


@pytest.mark.parametrize("force_device", [False, True])
def test_byte_parity_store_4x_budget(monkeypatch, force_device):
    """A store 4x the HBM budget serves point, bulk, and region reads
    byte-identical to the unbounded (no-residency) engine — with the
    device probe branch forced on so managed caches really get probed."""
    if force_device:
        from annotatedvdb_tpu.store import variant_store

        # the CPU test backend normally disables device lookups; force the
        # latch so resident segments ride _probe_device for real
        monkeypatch.setattr(variant_store, "_DEVICE_LOOKUP_OK", True)
    store, shard, queries = _build_store(4)
    total = sum(device_cache_bytes(s, WIDTH) for s in shard.segments)
    manager = ResidencyManager(
        budget_bytes=total // 4, upload=True, min_rows=1,
        async_upload=False, plan_interval_s=0.0,
    )
    plain = QueryEngine(StaticSnapshots(store), region_cache_size=0)
    budgeted = QueryEngine(
        StaticSnapshots(store), region_cache_size=0, residency=manager
    )
    flat = [q for qs in queries for q in qs]
    misses = [f"8:{p}:A:G" for p in range(2, 30, 7)]
    # interleave hot/cold so some segments are resident and some are not
    for _round in range(3):
        batch = flat + misses
        assert budgeted.lookup_many(batch) == plain.lookup_many(batch)
        hot = queries[_round % 4]
        assert budgeted.lookup_many(hot) == plain.lookup_many(hot)
    assert 0 < manager.resident_bytes() <= total // 4
    # region reads: byte-identical envelopes (host-side slicing either way)
    for spec in ("8:1-200000", "8:100000-400000", "8:1-1000000"):
        assert budgeted.region(spec) == plain.region(spec)
        assert budgeted.region(spec, limit=10) == plain.region(spec, limit=10)
