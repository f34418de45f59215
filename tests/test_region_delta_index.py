"""Region reads beside live inserts: a chromosome's interval index in two
parts — the base over the segments the store holds, kept across the
generations inserts make, and a per-generation delta of every newer row —
held byte for byte to the one definition of the dedup policy
(``QueryEngine._region_rows``) and to one whole ``IntervalIndex.build``
over the same shard.

The reference engine reads the same segments as a plain store (no
``overlay_base``: one whole index), at the same generation number, so
every envelope, panel, cursor page and stats summary must come out as the
same bytes."""

import json

import numpy as np
import pytest

from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.serve import (
    MemtableSnapshots,
    QueryEngine,
    SnapshotManager,
    StaticSnapshots,
)
from annotatedvdb_tpu.serve.engine import IntervalIndex, segment_alleles
from annotatedvdb_tpu.serve.snapshot import _OverlayStore
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.memtable import Memtable, build_rows
from annotatedvdb_tpu.store.variant_store import ChromosomeShard, Segment

WIDTH = 8
CODES = (3, 8)
SPAN = (1, 6000)  # rows crowd it: many positions hold several rows
BASES = "ACGT"
SEEDS = (11, 2147483659, 3000000019)
GENERATION = 7


def _row(rng, code: int, pos: int | None = None) -> dict:
    pos = int(rng.integers(*SPAN)) if pos is None else pos
    ref = BASES[int(rng.integers(4))]
    alt = BASES[(BASES.index(ref) + 1 + int(rng.integers(3))) % 4]
    shape = rng.random()
    tail = "".join(BASES[int(b)] for b in rng.integers(0, 4, 3))
    if shape < 0.15:
        alt = ref + tail  # an insertion
    elif shape < 0.25:
        ref, alt = ref + tail, ref  # a deletion
    ann = {}
    if rng.random() < 0.5:
        ann["cadd_scores"] = {"CADD_phred": float(rng.integers(0, 40))}
    if rng.random() < 0.5:
        ann["adsp_most_severe_consequence"] = {
            "rank": int(rng.integers(0, 12))}
    if rng.random() < 0.3:
        ann["allele_frequencies"] = {"gnomad": {"af": float(rng.random())}}
    return {"code": code, "pos": pos, "ref": ref, "alt": alt,
            "ref_snp": int(rng.integers(1, 10**7)), "ann": ann or None}


def _segment(parsed: list, h=None) -> Segment:
    """One sorted segment of ``parsed`` rows (one chromosome), with their
    real identity hashes unless ``h`` overrides them."""
    ((_idxs, rows, ref, alt, ann),) = build_rows(parsed, WIDTH).values()
    if h is not None:
        rows = dict(rows, h=np.asarray(h, np.uint32))
    return Segment.build(rows, ref, alt, annotations=ann or None)


def _base_store(rng, n: int = 500) -> VariantStore:
    """Two segments a chromosome, the newer repeating a few identities of
    the older (first-wins inside the base too)."""
    store = VariantStore(width=WIDTH)
    for code in CODES:
        older = [_row(rng, code) for _ in range(n)]
        newer = [_row(rng, code) for _ in range(n // 4)] \
            + [dict(older[int(i)], ref_snp=1)
               for i in rng.integers(0, n, 8)]
        shard = store.shard(code)
        shard.segments = [_segment(older), _segment(newer)]
        shard._starts_cache = None
    return store


def _inserts(rng, store, n: int = 120) -> dict:
    """Overlay segments a chromosome: new rows (some at a position the
    base holds rows at), and rows that repeat an identity of the base or
    of an earlier overlay segment."""
    out = {}
    for code in CODES:
        held = [s for s in store.shards[code].segments]
        base_pos = np.concatenate([s.cols["pos"] for s in held])
        first = [_row(rng, code) for _ in range(n)] + [
            _row(rng, code, int(p)) for p in rng.choice(base_pos, 10)]
        again = [_row(rng, code) for _ in range(n // 3)] \
            + [dict(first[int(i)], ref_snp=2)
               for i in rng.integers(0, len(first), 5)]
        out[code] = [_segment(first), _segment(again)]
    # a stored identity again, in the overlay: shadowed by the base
    seg = store.shards[CODES[0]].segments[0]
    ref, alt = segment_alleles(seg, 3, WIDTH)
    out[CODES[0]].append(_segment([{
        "code": CODES[0], "pos": int(seg.cols["pos"][3]), "ref": ref,
        "alt": alt, "ref_snp": 3, "ann": None}]))
    return out


def _whole(store):
    """``store``'s shards as one plain store: the whole-index reference."""
    class Whole:
        width = store.width
        shards = {}

    for code, shard in store.shards.items():
        plain = ChromosomeShard(code, store.width)
        plain.segments = list(shard.segments)
        Whole.shards[code] = plain
    return Whole


def _pair(overlay, **kw):
    """(split engine, whole-index reference engine) over the same rows."""
    kw.setdefault("region_cache_size", 0)
    split = QueryEngine(StaticSnapshots(overlay, GENERATION), **kw)
    ref = QueryEngine(StaticSnapshots(_whole(overlay), GENERATION), **kw)
    return split, ref


@pytest.fixture(params=SEEDS)
def live(request):
    rng = np.random.default_rng(request.param)
    store = _base_store(rng)
    overlay = _OverlayStore(store, _inserts(rng, store))
    return rng, store, overlay


def _specs(rng, n: int = 24) -> list:
    out = []
    for _ in range(n):
        code = CODES[int(rng.integers(len(CODES)))]
        start = int(rng.integers(SPAN[0] - 50, SPAN[1]))
        out.append(f"{code}:{max(start, 1)}-"
                   f"{max(start, 1) + int(rng.integers(0, 900))}")
    return out + [f"{CODES[0]}:1-{SPAN[1] + 10}", f"{CODES[1]}:2-2"]


# ---------------------------------------------------------------------------
# the split index against the definitions


def test_split_rows_are_the_dedup_definitions_and_a_whole_builds(live):
    rng, _store, overlay = live
    split, _ref = _pair(overlay)
    snap = split.snapshots.current()
    for code in CODES:
        shard = overlay.shards[code]
        parts = split._region_parts(snap, code)
        assert parts.delta is not None and parts.delta.shadowed > 0
        whole = IntervalIndex.build(shard)
        folded = parts.whole()
        for name in ("pos", "si", "jj"):
            assert np.array_equal(getattr(folded, name),
                                  getattr(whole, name)), name
        for spec in _specs(rng, 12):
            c, _sep, rng_s = spec.partition(":")
            if int(c) != code:
                continue
            start, end = (int(x) for x in rng_s.split("-"))
            b_lo = int(np.searchsorted(parts.base.pos, start, "left"))
            b_hi = int(np.searchsorted(parts.base.pos, end, "right"))
            (d_lo,), (d_hi,) = parts.delta_spans([start], [end])
            si, jj = parts.rows(b_lo, b_hi, int(d_lo), int(d_hi))
            want = QueryEngine._region_rows(shard, start, end)
            assert list(zip(si.tolist(), jj.tolist())) == want, spec


@pytest.mark.parametrize("limit", [None, 0, 1, 7, 100])
def test_single_region_envelopes_match_a_whole_index(live, limit):
    rng, _store, overlay = live
    split, ref = _pair(overlay)
    for spec in _specs(rng):
        assert split.region(spec, limit=limit) == ref.region(
            spec, limit=limit), spec


@pytest.mark.parametrize("route", ["host", "device"])
def test_panels_match_on_the_host_and_device_routes(live, route):
    rng, _store, overlay = live
    kw = {"regions_device_min": 0} if route == "device" else {}
    split, ref = _pair(overlay, **kw)
    specs = _specs(rng, 40)
    for limit in (None, 3):
        got = split.regions_serve(specs, limit=limit, tokenize=True,
                                  host_only=route == "host")
        want = ref.regions_serve(specs, limit=limit, tokenize=True,
                                 host_only=route == "host")
        assert got.assemble() == want.assemble()
    if route == "device":
        assert split.region_panels["device_groups"] > 0


def test_cursor_walks_match_a_whole_index(live):
    rng, _store, overlay = live
    split, ref = _pair(overlay)
    for spec in _specs(rng, 6):
        pages, cursor = [], ""
        while True:
            page = split.region(spec, limit=5, cursor=cursor)
            assert page == ref.region(spec, limit=5, cursor=cursor)
            doc = json.loads(page)
            pages += doc["variants"]
            if not doc.get("next"):
                break
            cursor = doc["next"]
        assert pages == json.loads(ref.region(spec))["variants"]


@pytest.mark.parametrize("filters", [
    {"min_cadd": 20.0}, {"max_conseq_rank": 4},
    {"min_cadd": 10.0, "max_conseq_rank": 8}])
def test_filtered_reads_match_a_whole_index(live, filters):
    rng, _store, overlay = live
    split, ref = _pair(overlay)
    specs = _specs(rng)
    for limit in (None, 2):
        for spec in specs:
            assert split.region(spec, limit=limit, **filters) \
                == ref.region(spec, limit=limit, **filters), spec
        assert split.regions_serve(specs, limit=limit, **filters) \
            .assemble() == ref.regions_serve(specs, limit=limit,
                                             **filters).assemble()


@pytest.mark.parametrize("route", ["host", "device"])
def test_stats_panels_add_up_to_a_whole_indexs(live, route):
    rng, _store, overlay = live
    kw = {"stats_device_min": 0} if route == "device" else {}
    split, ref = _pair(overlay, **kw)
    specs = _specs(rng, 16)
    host_only = route == "host"
    assert split.stats_serve(specs, windows=4, host_only=host_only) \
        .assemble() == ref.stats_serve(specs, windows=4,
                                       host_only=host_only).assemble()


# ---------------------------------------------------------------------------
# the edges


def _one_base(rows: list) -> VariantStore:
    store = VariantStore(width=WIDTH)
    shard = store.shard(CODES[0])
    shard.segments = [_segment(rows)]
    shard._starts_cache = None
    return store


def _edge(case: str):
    """(store, overlay segments, delta rows expected, shadowed expected)."""
    rng = np.random.default_rng(5)
    rows = [_row(rng, CODES[0], p) for p in (100, 200, 200, 300, 400)]
    store = _one_base(rows)
    base_h = store.shards[CODES[0]].segments[0].cols["h"]
    seg = store.shards[CODES[0]].segments[0]
    at_200 = int(np.flatnonzero(seg.cols["pos"] == 200)[0])
    if case == "empty delta":
        # the one overlay row repeats a stored identity: all shadowed
        return store, [_segment([dict(rows[0], ref_snp=9)])], 0, 1
    if case == "same identity, same hash: shadowed":
        return store, [_segment([dict(rows[1], ref_snp=9),
                                 _row(rng, CODES[0], 250)])], 1, 1
    if case == "other identity, same hash: kept":
        other = dict(rows[1], alt="ACGT" if rows[1]["alt"] != "ACGT"
                     else "AG")
        return store, [_segment([other], h=[int(base_h[at_200])])], 1, 0
    if case == "before the first position":
        return store, [_segment([_row(rng, CODES[0], 1),
                                 _row(rng, CODES[0], 100)])], 2, 0
    if case == "after the last position":
        return store, [_segment([_row(rng, CODES[0], 400),
                                 _row(rng, CODES[0], 99999)])], 2, 0
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "empty delta", "same identity, same hash: shadowed",
    "other identity, same hash: kept", "before the first position",
    "after the last position"])
def test_delta_edges_match_a_whole_index(case):
    store, segs, rows, shadowed = _edge(case)
    overlay = _OverlayStore(store, {CODES[0]: segs})
    split, ref = _pair(overlay)
    snap = split.snapshots.current()
    parts = split._region_parts(snap, CODES[0])
    if rows:
        assert (parts.delta.n, parts.delta.shadowed) == (rows, shadowed)
    else:
        assert parts.delta is None  # the read-only route exactly
    for spec in (f"{CODES[0]}:1-100000", f"{CODES[0]}:1-1",
                 f"{CODES[0]}:100-200", f"{CODES[0]}:200-200",
                 f"{CODES[0]}:400-99999"):
        for limit in (None, 1, 2):
            assert split.region(spec, limit=limit) \
                == ref.region(spec, limit=limit), (spec, limit)
    specs = [f"{CODES[0]}:1-100000", f"{CODES[0]}:150-350"]
    assert split.regions_serve(specs, tokenize=True).assemble() \
        == ref.regions_serve(specs, tokenize=True).assemble()


def test_a_read_only_store_takes_the_whole_index_route():
    rng = np.random.default_rng(3)
    store = _base_store(rng)
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0)
    snap = engine.snapshots.current()
    for spec in _specs(rng):
        engine.region(spec)
    engine.regions_serve(_specs(rng))
    for code in CODES:
        parts = engine._region_parts(snap, code)
        assert parts.delta is None
        assert engine._interval_index(snap, code) is parts.base
        assert parts.base.segs == tuple(store.shards[code].segments)
    stats = engine.region_index_stats()
    assert (engine.index_builds, engine.base_builds, engine.delta_builds,
            engine.flush_builds) == (len(CODES), len(CODES), 0, 0)
    assert stats["delta_rows"] == 0
    assert engine.region_scans["delta_reads"] == 0


# ---------------------------------------------------------------------------
# counters, and the live store's guarantee


def _stage_counts(registry) -> dict:
    out = {}
    for line in registry.render_prometheus().splitlines():
        if line.startswith('avdb_stage_seconds_count{stage="region.'):
            stage = line.split('"')[1]
            out[stage] = float(line.rsplit(" ", 1)[1])
    return out


def test_counters_move_once_a_read_and_once_a_build(live):
    rng, store, _overlay = live
    registry = MetricsRegistry()
    mem = Memtable(width=WIDTH)
    engine = QueryEngine(MemtableSnapshots(StaticSnapshots(store), mem),
                         registry=registry)
    spec = f"{CODES[0]}:1-{SPAN[1]}"
    engine.region(spec, limit=3)
    assert engine.region_scans == {"reads": 1, "delta_reads": 0}
    assert _stage_counts(registry) == {
        "region.index": 1, "region.locate": 1, "region.render": 1}
    engine.region(spec, limit=3)  # a region-LRU hit locates nothing
    assert engine.region_scans["reads"] == 1
    assert (engine.base_builds, engine.delta_builds) == (1, 0)
    mem.upsert(store, [_row(rng, CODES[0])])
    engine.region(spec, limit=3)
    engine.region(spec, limit=4)  # the same generation: no second delta
    assert engine.region_scans == {"reads": 3, "delta_reads": 2}
    assert (engine.base_builds, engine.delta_builds) == (1, 1)
    assert _stage_counts(registry)["region.locate"] == 3
    assert engine.region_index_stats()["delta_rows"] == 1
    engine.region(f"{CODES[1]}:1-{SPAN[1]}")  # no insert there
    assert (engine.base_builds, engine.delta_builds) == (2, 1)
    assert engine.region_scans == {"reads": 4, "delta_reads": 2}


def test_no_request_builds_a_whole_index_across_200_inserts(live):
    rng, store, _overlay = live
    mem = Memtable(width=WIDTH)
    engine = QueryEngine(MemtableSnapshots(StaticSnapshots(store), mem),
                         region_cache_size=0)
    snap = engine.snapshots.current()
    for code in CODES:
        engine._base_of(code, snap.store.shards[code], request=False)
    held = {code: engine._region_parts(snap, code).base for code in CODES}
    for k in range(200):
        mem.upsert(store, [_row(rng, CODES[k % 2])])
        engine.region(_specs(rng, 1)[0], limit=int(rng.integers(1, 100)))
        if k % 20 == 0:
            engine.regions_serve(_specs(rng, 8), limit=5)
    snap = engine.snapshots.current()
    assert engine.request_builds == 0 and engine.base_builds == len(CODES)
    assert engine.delta_builds >= 200
    for code in CODES:
        parts = engine._region_parts(snap, code)
        assert parts.base is held[code]  # the same base throughout
        assert parts.delta.n > 0
    _split, ref = _pair(snap.store)
    for spec in _specs(rng):
        assert engine.region(spec).replace(
            f'"generation":{snap.generation}', "") == ref.region(
            spec).replace(f'"generation":{GENERATION}', "")


def test_a_flush_folds_the_bases_before_its_generation_is_pinned(tmp_path):
    rng = np.random.default_rng(17)
    store_dir = str(tmp_path / "store")
    _base_store(rng).save(store_dir)
    manager = SnapshotManager(store_dir, log=lambda m: None)
    mem = Memtable(width=WIDTH, store_dir=store_dir, log=lambda m: None)
    engine = QueryEngine(MemtableSnapshots(manager, mem),
                         region_cache_size=0, regions_device_min=32,
                         regions_max=64)
    snap = engine.snapshots.current()
    for code in CODES:  # what the residency uploader does
        engine.warm_region_index(snap.generation, code,
                                 snap.store.shards[code])
    assert engine.index_uploads == len(CODES)
    base_store = manager.current().store
    for k in range(60):
        mem.upsert(base_store, [_row(rng, CODES[k % 2])])
        engine.region(_specs(rng, 1)[0], limit=10)
    specs = _specs(rng, 40)
    before = engine.regions_serve(specs).pages
    assert mem.flush(base_manager=manager)["status"] == "flushed"
    assert engine.flush_builds == len(CODES)
    assert engine.index_uploads == 2 * len(CODES)  # one fold copy each
    snap = engine.snapshots.current()
    stored = manager.current().store
    assert mem.rows == 0 and all(
        snap.store.shards[code].segments == stored.shards[code].segments
        for code in CODES)
    for code in CODES:
        parts = engine._region_parts(snap, code)
        # the pinned generation's base covers the flushed rows, warmed
        assert parts.delta is None and parts.base.warmed
        whole = IntervalIndex.build(snap.store.shards[code])
        for name in ("pos", "si", "jj"):
            assert np.array_equal(getattr(parts.base, name),
                                  getattr(whole, name)), name
    after = engine.regions_serve(specs).pages
    assert [p.count for p in after] == [p.count for p in before]
    assert engine.request_builds == 0
    assert engine.base_builds == len(CODES)
    _split, ref = _pair(snap.store)
    assert engine.regions_serve(specs).assemble().replace(
        f'"generation":{snap.generation}', "") == ref.regions_serve(
        specs).assemble().replace(f'"generation":{GENERATION}', "")


def test_one_refresh_loads_and_folds_while_another_waits(tmp_path,
                                                         monkeypatch):
    """Two refreshes of one new manifest: the one that loads prepares; a
    second either waits for it (``wait=True``, a flush's hand-over) or
    returns at once — never a second load whose folds would push the
    bases the pinned generation still reads out of the cache."""
    import threading

    from annotatedvdb_tpu.serve import snapshot as snapshot_mod

    rng = np.random.default_rng(23)
    store_dir = str(tmp_path / "store")
    _base_store(rng).save(store_dir)
    manager = SnapshotManager(store_dir, log=lambda m: None)
    mem = Memtable(width=WIDTH, store_dir=store_dir, log=lambda m: None)
    engine = QueryEngine(MemtableSnapshots(manager, mem),
                         region_cache_size=0)
    snap = engine.snapshots.current()
    for code in CODES:
        engine._base_of(code, snap.store.shards[code], request=False)
    base_store = manager.current().store
    for k in range(20):
        mem.upsert(base_store, [_row(rng, CODES[k % 2])])
    # the flush commits; its own hand-over is left to the refreshes below
    assert mem.flush(base_manager=None)["status"] == "flushed"
    entered, release = threading.Event(), threading.Event()
    real_load = snapshot_mod.VariantStore.load
    loads = []

    def held_load(*args, **kw):
        loads.append(1)
        entered.set()
        assert release.wait(30)
        return real_load(*args, **kw)

    monkeypatch.setattr(snapshot_mod.VariantStore, "load", held_load)
    first = threading.Thread(target=manager.refresh)
    first.start()
    assert entered.wait(30)
    assert manager.refresh(wait=False) is False  # busy: no second load
    release.set()
    first.join(30)
    assert manager.refresh() is False  # pinned already
    assert len(loads) == 1 and manager.swaps == 1
    assert engine.flush_builds == len(CODES)
    snap = engine.snapshots.current()
    for spec in _specs(rng):
        engine.region(spec)
    assert engine.request_builds == 0


def test_concurrent_readers_of_a_generation_build_its_delta_once(live):
    """Every reader of a fresh generation misses the parts cache at once:
    they must share one delta a chromosome, and answer alike."""
    import sys
    import threading

    rng, store, _overlay = live
    mem = Memtable(width=WIDTH)
    engine = QueryEngine(MemtableSnapshots(StaticSnapshots(store), mem),
                         region_cache_size=0)
    specs = [f"{code}:1-{SPAN[1]}" for code in CODES]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for generation in range(4):
            mem.upsert(store, [_row(rng, code) for code in CODES])
            got = []
            threads = [threading.Thread(
                target=lambda k=k: got.append(
                    engine.region(specs[k % 2], limit=50)))
                for k in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
            assert not any(t.is_alive() for t in threads)
            assert len(set(got)) == len(CODES)
            assert engine.delta_builds == len(CODES) * (generation + 1)
    finally:
        sys.setswitchinterval(switch)
    assert engine.request_builds == len(CODES)  # the bases, read first
