"""Unit suite for the fused analytics kernels (``ops/stats``) and the
engine's stats path.

Every kernel answer is checked against a brute-force Python oracle (per
interval: scan the rows, filter the missing sentinel, sum/bucket in
plain ints), and the device kernel against its registered numpy twin
byte-for-byte — ``ops.stats.stats_panel_kernel_jit`` vs
``ops.stats.stats_panel_host`` and ``ops.stats.windowed_stats_kernel_jit``
vs ``ops.stats.windowed_stats_host`` (``assert_array_equal``, never
allclose: the AVDB9xx twin contract).  The engine half covers the cached
feature columns (decode-once), the filter rewire's byte parity against
the scalar ``_passes`` definition, memtable-overlay rows, and ``doctor
profile``.
"""

from __future__ import annotations

import json
import math

import numpy as np
import pytest
from numpy.testing import assert_array_equal

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.ops import TWINS
from annotatedvdb_tpu.ops import stats as st
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.types import encode_allele_array


def _random_case(seed, n_rows, n_queries, span=400_000):
    rng = np.random.default_rng(seed)
    pos = np.sort(rng.integers(1, 5_000_000, n_rows).astype(np.int32))
    af = rng.integers(-1, st.AF_SCALE + 1, n_rows).astype(np.int32)
    cadd = rng.integers(-1, 100_001, n_rows).astype(np.int32)
    rank = rng.integers(-1, st.RANK_BUCKETS + 8, n_rows).astype(np.int32)
    starts = rng.integers(1, 5_000_000, n_queries).astype(np.int64)
    ends = starts + rng.integers(0, span, n_queries)
    return pos, af, cadd, rank, starts, ends


def _oracle_interval(pos, values, s, e, edges=None):
    """(present, exact_sum, hist|None) for one interval by linear scan."""
    sel = [v for p, v in zip(pos.tolist(), values.tolist())
           if s <= p <= e and v >= 0]
    hist = None
    if edges is not None:
        hist = [0] * (len(edges) - 1)
        for v in sel:
            b = int(np.searchsorted(edges, v, side="right")) - 1
            hist[min(max(b, 0), len(edges) - 2)] += 1
    return len(sel), sum(sel), hist


# -- kernel vs twin vs oracle ------------------------------------------------


@pytest.mark.parametrize("n_rows,n_queries", [
    (0, 5), (1, 3), (64, 17), (1000, 65), (4096, 9),
])
def test_panel_kernel_twin_byte_exact(n_rows, n_queries):
    """stats_panel (device, via stats_panel_kernel_jit) and
    stats_panel_host answer byte-identically on random columns."""
    pos, af, cadd, rank, starts, ends = _random_case(
        2209_8600 + n_rows, n_rows, n_queries
    )
    dev = st.stats_panel(pos, af, cadd, rank, starts, ends)
    host = st.stats_panel_host(pos, af, cadd, rank, starts, ends)
    assert len(dev) == len(host) == 7
    for d, h in zip(dev, host):
        assert_array_equal(np.asarray(d), np.asarray(h))


def test_panel_matches_brute_oracle():
    pos, af, cadd, rank, starts, ends = _random_case(99, 777, 29)
    lo, hi, af_l, af_h, c_l, c_h, rk = st.stats_panel_host(
        pos, af, cadd, rank, starts, ends
    )
    af_sums = st.lanes_to_sums(af_l)
    c_sums = st.lanes_to_sums(c_l)
    for i, (s, e) in enumerate(zip(starts.tolist(), ends.tolist())):
        count = sum(1 for p in pos.tolist() if s <= p <= e)
        assert int(hi[i] - lo[i]) == count
        p_af, s_af, h_af = _oracle_interval(pos, af, s, e, st.AF_EDGES_FP)
        assert int(np.asarray(af_h[i]).sum()) == p_af
        assert int(af_sums[i]) == s_af
        assert np.asarray(af_h[i]).tolist() == h_af
        p_c, s_c, h_c = _oracle_interval(pos, cadd, s, e, st.CADD_EDGES_FP)
        assert int(np.asarray(c_h[i]).sum()) == p_c
        assert int(c_sums[i]) == s_c
        assert np.asarray(c_h[i]).tolist() == h_c
        # rank rollup: clamped bucket counts
        want = [0] * st.RANK_BUCKETS
        for p, r in zip(pos.tolist(), rank.tolist()):
            if s <= p <= e and r >= 0:
                want[min(r, st.RANK_BUCKETS - 1)] += 1
        assert np.asarray(rk[i]).tolist() == want


@pytest.mark.parametrize("windows", [1, 3, 16])
def test_windowed_kernel_twin_byte_exact(windows):
    """windowed_stats (device, via windowed_stats_kernel_jit) and
    windowed_stats_host answer byte-identically."""
    pos, _af, cadd, _rank, starts, ends = _random_case(5, 513, 21)
    dev = st.windowed_stats(pos, cadd, starts, ends, windows)
    host = st.windowed_stats_host(pos, cadd, starts, ends, windows)
    for d, h in zip(dev, host):
        assert_array_equal(np.asarray(d), np.asarray(h))


def test_windowed_tiles_the_interval_exactly():
    """Windows partition [start, end]: per-window counts sum to the
    interval's row count and boundaries never double-count."""
    pos, _af, cadd, _rank, starts, ends = _random_case(11, 900, 40)
    for w in (1, 4, 7):
        counts, present, lanes = st.windowed_stats_host(
            pos, cadd, starts, ends, w
        )
        lo = np.searchsorted(pos, np.clip(starts, 0, None), side="left")
        hi = np.searchsorted(pos, ends, side="right")
        assert_array_equal(counts.sum(axis=1), (hi - lo).astype(np.int32))
        sums = st.lanes_to_sums(lanes)
        for i, (s, e) in enumerate(zip(starts.tolist(), ends.tolist())):
            p, total, _h = _oracle_interval(pos, cadd, s, e)
            assert int(present[i].sum()) == p
            assert int(sums[i].sum()) == total


def test_empty_intervals_and_all_missing():
    pos = np.asarray([100, 200, 300], np.int32)
    missing = np.full(3, st.STATS_MISSING, np.int32)
    lo, hi, af_l, af_h, c_l, c_h, rk = st.stats_panel_host(
        pos, missing, missing, missing, [1, 150, 400], [50, 250, 500]
    )
    assert (hi - lo).tolist() == [0, 1, 0]
    assert int(np.asarray(af_h).sum()) == 0
    assert int(np.asarray(c_h).sum()) == 0
    assert int(np.asarray(rk).sum()) == 0
    summary = st.interval_summary(1, af_l[1], af_h[1], c_l[1], c_h[1], rk[1])
    assert summary["count"] == 1
    assert summary["af"] == {"present": 0, "mean": None,
                             "spectrum": [0] * (len(st.AF_EDGES_FP) - 1)}
    assert summary["cadd"]["present"] == 0
    assert summary["cadd"]["quantiles"] == {"p50": None, "p90": None,
                                            "p99": None}
    assert summary["conseq"] == {"present": 0, "ranks": {}}


def test_registry_covers_the_stats_kernels():
    assert TWINS["ops.stats.stats_panel_kernel_jit"] == \
        "ops.stats.stats_panel_host"
    assert TWINS["ops.stats.windowed_stats_kernel_jit"] == \
        "ops.stats.windowed_stats_host"


# -- derivation helpers ------------------------------------------------------


def test_quantiles_from_histogram():
    hist = np.asarray([5, 0, 5], np.int64)
    edges = np.asarray([0, 10, 20, 30], np.int64)
    q = st.hist_quantiles(hist, edges, 1, qs=(50, 100))
    # target rank 5 lands exactly at the first bin's last row
    assert q["p50"] == 10.0
    assert q["p100"] == 30.0
    assert st.hist_quantiles(np.zeros(3, np.int64), edges, 1)["p50"] is None


def test_feature_values_decode_rules():
    nan = float("nan")
    # plain numerics decode; bools/strings/missing do not
    cf, rf, af, cfp, ri = st.feature_values(
        {"CADD_phred": 12.5}, {"g": {"af": 0.25}, "x": 0.5}, {"rank": 3}
    )
    assert cf == 12.5 and cfp == 12_500
    assert af == 500_000  # cohort-max: the larger leaf wins
    assert rf == 3.0 and ri == 3
    cf, rf, af, cfp, ri = st.feature_values(
        {"CADD_phred": True}, {"g": "high"}, {"rank": "7"}
    )
    assert math.isnan(cf) and math.isnan(rf)
    assert af == st.STATS_MISSING and cfp == st.STATS_MISSING \
        and ri == st.STATS_MISSING
    # RawJson duck-type: parses fresh, never caches onto the instance
    class Raw:
        def __init__(self, text):
            self.text = text
    cf, _rf, af, cfp, _ri = st.feature_values(
        Raw('{"CADD_phred": 3.25}'), Raw('{"TOPMED": {"af": 1e-4}}'), None
    )
    assert cf == 3.25 and cfp == 3250 and af == 100
    # out-of-range values clamp into the fixed-point domain
    cf, _rf, af, cfp, _ri = st.feature_values(
        {"CADD_phred": -4.0}, {"af": 7.5}, {"rank": -2}
    )
    assert cf == -4.0 and cfp == 0  # filter sees the raw value
    assert af == st.AF_SCALE  # AF clamps to [0, 1]


# -- engine: feature columns, stats_serve, overlay ---------------------------


def _annotated_store(n=64, width=8):
    store = VariantStore(width=width)
    refs = ["A", "C", "G", "T"] * (n // 4)
    alts = ["G", "T", "A", "C"] * (n // 4)
    ref, ref_len = encode_allele_array(refs, width)
    alt, alt_len = encode_allele_array(alts, width)
    h = identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts)
    pos = np.arange(1000, 1000 + 97 * n, 97, dtype=np.int32)[:n]
    store.shard(8).append(
        {"pos": pos, "h": h, "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
        annotations={
            "cadd_scores": [
                {"CADD_phred": float(i % 40)} if i % 2 else None
                for i in range(n)
            ],
            "allele_frequencies": [
                {"gnomad": {"af": (i % 100) / 100.0}} if i % 3 else None
                for i in range(n)
            ],
            "adsp_most_severe_consequence": [
                {"rank": i % 7} if i % 4 else None for i in range(n)
            ],
        },
    )
    return store, pos


def test_engine_stats_matches_brute_reference():
    from annotatedvdb_tpu.serve.engine import QueryEngine
    from annotatedvdb_tpu.serve.snapshot import StaticSnapshots

    store, pos = _annotated_store()
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0,
                         stats_device_min=0)
    specs = ["8:1000-3000", "8:2500-2500", "8:1-999", "7:5-10"]
    result = engine.stats_serve(specs, windows=4)
    doc = json.loads(result.assemble())
    assert doc["n"] == 4 and doc["metrics"] == ["af", "cadd", "conseq"]
    shard = store.shards[8]
    for entry, spec in zip(doc["results"], specs):
        assert entry["region"] == spec
        code_s, rng = spec.split(":")
        s, e = (int(x) for x in rng.split("-"))
        if code_s != "8":
            assert entry["count"] == 0
            continue
        rows = [i for i, p in enumerate(pos.tolist()) if s <= p <= e]
        assert entry["count"] == len(rows)
        phreds = [
            shard.annotations["cadd_scores"][i]["CADD_phred"]
            for i in rows if shard.annotations["cadd_scores"][i]
        ]
        assert entry["cadd"]["present"] == len(phreds)
        if phreds:
            want = round(
                sum(int(round(p * st.CADD_SCALE)) for p in phreds)
                / (len(phreds) * st.CADD_SCALE), 9)
            assert entry["cadd"]["mean"] == want
        assert sum(entry["windows"]["counts"]) == len(rows)


def test_engine_stats_device_host_and_forced_twin_identical():
    from annotatedvdb_tpu.serve.engine import QueryEngine
    from annotatedvdb_tpu.serve.snapshot import StaticSnapshots

    store, _pos = _annotated_store()
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0,
                         stats_device_min=0)
    specs = [f"8:{1000 + 13 * i}-{1500 + 13 * i}" for i in range(40)]
    via_device = engine.stats_serve(specs, windows=3).assemble()
    via_host = engine.stats_serve(specs, windows=3,
                                  host_only=True).assemble()
    assert via_device == via_host


def test_engine_stats_covers_memtable_overlay_rows():
    """Upserted rows (memtable overlay segments) join the analytics the
    moment they are visible — first-wins with the stored rows, exactly
    like every other read path."""
    from annotatedvdb_tpu.serve.engine import QueryEngine
    from annotatedvdb_tpu.serve.snapshot import StaticSnapshots
    from annotatedvdb_tpu.serve.snapshot import MemtableSnapshots
    from annotatedvdb_tpu.store.memtable import Memtable

    store, _pos = _annotated_store(n=16)
    base = StaticSnapshots(store)
    memtable = Memtable(width=store.width)
    provider = MemtableSnapshots(base, memtable)
    engine = QueryEngine(provider, region_cache_size=0, stats_device_min=0)
    spec = "8:900000-990000"  # far above the stored rows
    before = json.loads(engine.stats_serve([spec]).assemble())
    assert before["results"][0]["count"] == 0
    memtable.upsert(store, [{
        "code": 8, "pos": 900_500, "ref": "A", "alt": "G",
        "ref_snp": None,
        "ann": {"cadd_scores": {"CADD_phred": 33.0}},
    }])
    after = json.loads(engine.stats_serve([spec]).assemble())
    assert after["generation"] > before["generation"]
    entry = after["results"][0]
    assert entry["count"] == 1
    assert entry["cadd"]["present"] == 1
    assert entry["cadd"]["mean"] == 33.0


def test_feature_columns_cached_per_generation():
    """The sidecar decodes ONCE per (generation, chromosome): repeated
    stats/filter calls reuse the cached columns."""
    from annotatedvdb_tpu.serve.engine import QueryEngine
    from annotatedvdb_tpu.serve.snapshot import StaticSnapshots

    store, _pos = _annotated_store()
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0)
    calls = {"n": 0}
    real = st.feature_values

    def counting(*a):
        calls["n"] += 1
        return real(*a)

    import annotatedvdb_tpu.serve.engine as engine_mod

    orig = engine_mod.stats_ops.feature_values
    engine_mod.stats_ops.feature_values = counting
    try:
        engine.stats_serve(["8:1000-2000"])
        first = calls["n"]
        assert first == store.n  # one decode per row, once
        engine.stats_serve(["8:1000-9000"])
        engine.region("8:1000-9000", min_cadd=5.0)
        assert calls["n"] == first  # cache hit: zero further decodes
    finally:
        engine_mod.stats_ops.feature_values = orig


# -- the filter rewire: byte parity with the scalar definition ---------------


def _tricky_filter_store(width=8):
    """Annotation shapes that exercise every _passes branch: missing
    column values, non-dict values, bool/str 'numbers', int vs float."""
    store = VariantStore(width=width)
    n = 12
    refs = ["A"] * n
    alts = ["G"] * n
    ref, ref_len = encode_allele_array(refs, width)
    alt, alt_len = encode_allele_array(alts, width)
    h = identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts)
    pos = np.arange(100, 100 + 10 * n, 10, dtype=np.int32)
    cadd = [None, {"CADD_phred": 5}, {"CADD_phred": 5.0001},
            {"CADD_phred": True}, {"CADD_phred": "9"}, {"other": 1},
            {"CADD_phred": 4.9999}, {"CADD_phred": 0}, None,
            {"CADD_phred": 40}, {"CADD_phred": -1.5}, {"CADD_phred": 5}]
    ms = [{"rank": 2}, None, {"rank": 7}, {"rank": 2.5}, {"rank": False},
          {"rank": 0}, {"norank": 3}, {"rank": 3}, {"rank": 1},
          {"rank": 9}, {"rank": 2}, None]
    store.shard(8).append(
        {"pos": pos, "h": h, "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
        annotations={"cadd_scores": cadd,
                     "adsp_most_severe_consequence": ms},
    )
    return store


@pytest.mark.parametrize("min_cadd,max_rank", [
    (5.0, None), (None, 2), (5.0, 2), (0.0, 0), (4.9999, 7),
])
def test_filtered_region_bytes_unchanged(min_cadd, max_rank):
    """The vectorized feature-column filter path renders byte-identical
    envelopes to the scalar per-row ``_passes`` reference — the
    regression pin for the sidecar re-parse hot-spot fix."""
    from annotatedvdb_tpu.serve.engine import (
        QueryEngine,
        RegionPage,
        _region_bin,
        closed_form_path,
    )
    from annotatedvdb_tpu.serve.snapshot import StaticSnapshots

    store = _tricky_filter_store()
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0)
    got = engine.region("8:1-100000", min_cadd=min_cadd,
                        max_conseq_rank=max_rank)
    # reference: the scalar definition over the brute-force row walk
    shard = store.shards[8]
    kept = [
        (si, j) for si, j in engine._region_rows(shard, 1, 100_000)
        if QueryEngine._passes(shard.segments[si], j, min_cadd, max_rank)
    ]
    level, leaf = _region_bin(1, 100_000)
    want = RegionPage(
        shard, "8", level, closed_form_path("8", level, leaf),
        len(kept), 1, np.asarray([si for si, _j in kept], np.int32),
        np.asarray([j for _si, j in kept], np.int64), "8:1-100000", None,
        paged=False,
    ).assemble()
    assert got == want
    # the cursor-paged walk rides the same filter path
    paged = engine.region("8:1-100000", min_cadd=min_cadd,
                          max_conseq_rank=max_rank, limit=3, cursor="")
    doc = json.loads(paged)
    assert doc["count"] == len(kept)
    assert doc["returned"] == min(3, len(kept))


def test_batch_regions_filter_parity_after_rewire():
    from annotatedvdb_tpu.serve.engine import QueryEngine
    from annotatedvdb_tpu.serve.snapshot import StaticSnapshots

    store = _tricky_filter_store()
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0)
    specs = ["8:1-100000", "8:100-150", "8:160-220"]
    singles = [engine.region(s, min_cadd=5.0, max_conseq_rank=7)
               for s in specs]
    batch = engine.regions_serve(specs, min_cadd=5.0, max_conseq_rank=7)
    assert [p.assemble() for p in batch.pages] == singles


# -- doctor profile ----------------------------------------------------------


def test_doctor_profile_cli_matches_stats_serve(tmp_path):
    """The offline whole-store profile renders the SAME summary shapes
    — over the SAME first-wins-deduplicated row view — the serving
    stats path computes: the chunk-streamed accumulation must agree
    exactly with one full-span panel, including across a planted
    shadowed duplicate (which must count ONCE, with the older row's
    annotation values)."""
    from annotatedvdb_tpu.cli.doctor import main
    from annotatedvdb_tpu.serve.engine import QueryEngine
    from annotatedvdb_tpu.serve.snapshot import StaticSnapshots
    from annotatedvdb_tpu.store.variant_store import Segment

    store, _pos = _annotated_store()
    # plant a shadowed duplicate of the first row in a NEWER segment
    # with a wildly different CADD value: first-wins must hide it from
    # the profile exactly as it hides it from serving
    shard = store.shards[8]
    width = store.width
    ref, ref_len = encode_allele_array(["A"], width)
    alt, alt_len = encode_allele_array(["G"], width)
    h = identity_hashes(width, ref, alt, ref_len, alt_len, ["A"], ["G"])
    shard.append_segment(Segment.build(
        {"pos": np.asarray([1000], np.int32), "h": h,
         "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
        annotations={"cadd_scores": [{"CADD_phred": 9999.0}]},
    ))
    shard._starts_cache = None
    store_dir = str(tmp_path / "profstore")
    store.save(store_dir)
    out_path = str(tmp_path / "report.json")
    rc = main(["profile", "--storeDir", store_dir, "--out", out_path,
               "--chunkRows", "13"])
    assert rc == 0
    with open(out_path) as f:
        report = json.load(f)
    assert report["rows"] == store.n  # stored rows, duplicate included
    group = report["groups"]["8"]
    assert group["segments"] >= 1 and group["read_amp"] == group["segments"]
    # the shadowed duplicate counted ONCE (and its 9999 phred never
    # reached any histogram — the older row's value won)
    assert group["count"] == store.n - 1
    # cross-check: a serving stats panel over the whole chromosome span
    # must report the identical aggregation (same decode, same dedup,
    # same shapes)
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0)
    entry = json.loads(
        engine.stats_serve(["8:1-64000000"]).assemble()
    )["results"][0]
    for key in ("count", "af", "cadd", "conseq"):
        assert group[key] == entry[key], key
    assert report["totals"]["count"] == store.n - 1
    assert report["bins"] == st.edges_payload()


def test_doctor_profile_cli_unreadable_store_exits_2(tmp_path, capsys):
    from annotatedvdb_tpu.cli.doctor import main

    rc = main(["profile", "--storeDir", str(tmp_path / "missing")])
    assert rc == 2
    assert "doctor profile" in capsys.readouterr().err


def test_stats_device_copies_join_the_device_byte_ledger():
    """The feature columns' retained HBM copies are accounted against
    INDEX_DEVICE_BYTES exactly like the interval index's position array
    — and a ledger eviction (or a failed kernel) actually drops them."""
    from annotatedvdb_tpu.serve.engine import QueryEngine
    from annotatedvdb_tpu.serve.snapshot import StaticSnapshots

    store, _pos = _annotated_store()
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0,
                         stats_device_min=0)
    specs = [f"8:{1000 + 7 * i}-{2000 + 7 * i}" for i in range(4)]
    engine.stats_serve(specs)
    snap = engine.snapshots.current()
    feats = engine._interval_index(snap, 8).feats
    assert feats.device_bytes() > 0
    ledgered = {id(obj) for obj, _b in engine._index_device.values()}
    assert id(feats) in ledgered
    total = sum(b for _o, b in engine._index_device.values())
    assert total >= feats.device_bytes()
    # a failed kernel drops BOTH the device copy and its ledger entry
    def boom(index, f, starts, ends):
        raise RuntimeError("injected")

    engine._device_stats = boom
    engine.stats_serve(specs)  # host fallback, byte-identical
    assert feats.device_bytes() == 0
    assert id(feats) not in {
        id(obj) for obj, _b in engine._index_device.values()
    }
