"""``POST /stats/region`` serving battery.

The contract under test: the analytics surface answers **byte-identically
on both front ends**, under the device path, the ``host_only`` twin, a
breaker-forced host fallback, and across a live snapshot swap — with the
full admission shape (grammar 400s, brownout shed, deadline 504s, the
interval cap) and the engine's answers pinned against an independent
brute-force reference that shares only the decode/summary helpers
(``ops.stats.feature_values`` / ``summary_from_totals``).
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.ops import stats as st
from annotatedvdb_tpu.serve import (
    DeviceBreaker,
    QueryEngine,
    QueryError,
    SnapshotManager,
)
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.variant_store import RawJson
from annotatedvdb_tpu.types import chromosome_label, encode_allele_array
from annotatedvdb_tpu.utils import faults
from conftest import start_server, stop_server

WIDTH = 8
CHROMS = (1, 8, 23)
BASES = ("A", "C", "G", "T")


def _rows_for(code: int, base_pos: int, n: int, salt: int):
    rows = []
    for i in range(n):
        k = (i + salt) % 4
        rows.append({
            "chrom": code, "pos": base_pos + 977 * i,
            "ref": BASES[k], "alt": BASES[(k + 1) % 4],
            "cadd": round(0.5 * i + code, 2) if i % 3 == 0 else None,
            "rank": (i % 30) + 1 if i % 4 == 0 else None,
            "af": round((i % 50) / 50.0, 4) if i % 2 == 0 else None,
        })
    return rows


def _append(shard, rows):
    refs = [r["ref"] for r in rows]
    alts = [r["alt"] for r in rows]
    ref, ref_len = encode_allele_array(refs, WIDTH)
    alt, alt_len = encode_allele_array(alts, WIDTH)
    h = identity_hashes(WIDTH, ref, alt, ref_len, alt_len, refs, alts)
    shard.append(
        {"pos": np.asarray([r["pos"] for r in rows], np.int32),
         "h": h, "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
        annotations={
            "cadd_scores": [
                {"CADD_phred": r["cadd"]} if r["cadd"] is not None
                else None for r in rows
            ],
            "adsp_most_severe_consequence": [
                {"conseq": "missense_variant", "rank": r["rank"]}
                if r["rank"] is not None else None for r in rows
            ],
            "allele_frequencies": [
                RawJson(json.dumps(
                    {"GnomAD": {"af": r["af"]}, "1000Genomes": r["af"] / 2}
                )) if i % 5 == 0 and r["af"] is not None
                else ({"GnomAD": {"af": r["af"]}}
                      if r["af"] is not None else None)
                for i, r in enumerate(rows)
            ],
        },
    )


def _build_store(store_dir: str | None):
    store = VariantStore(width=WIDTH)
    truth: list[dict] = []
    for code in CHROMS:
        shard = store.shard(code)
        for run, base in enumerate((500, 120_000, 2_000_000)):
            rows = _rows_for(code, base, 40, salt=run)
            _append(shard, rows)
            truth.extend(rows)
    if store_dir is not None:
        store.save(store_dir)
    return store, truth


PANEL = [
    (8, 1, 10_000), (8, 490, 600), (8, 120_000, 160_000),
    (1, 1, 3_000_000), (23, 2_000_000, 2_005_000), (11, 1, 5_000),
    (1, 500, 500), (8, 1, 5_000_000), (23, 1, 4_000_000),
]


def _specs():
    return [f"{chromosome_label(c)}:{s}-{e}" for c, s, e in PANEL]


def _brute_entry(truth, code, start, end, metrics=st.STATS_METRICS,
                 windows=None):
    """Independent reference: accumulate one interval's totals in plain
    Python from the truth rows (no dedup needed: the store is
    loader-deduplicated), render through the shared summary shape."""
    rows = [r for r in truth
            if r["chrom"] == code and start <= r["pos"] <= end]
    af_sum = cadd_sum = 0
    af_hist = np.zeros(len(st.AF_EDGES_FP) - 1, np.int64)
    cadd_hist = np.zeros(len(st.CADD_EDGES_FP) - 1, np.int64)
    ranks = np.zeros(st.RANK_BUCKETS, np.int64)
    afs, cadds = [], []
    for r in sorted(rows, key=lambda r: r["pos"]):
        _cf, _rf, af_fp, cadd_fp, rank_i = st.feature_values(
            {"CADD_phred": r["cadd"]} if r["cadd"] is not None else None,
            {"GnomAD": {"af": r["af"]}} if r["af"] is not None else None,
            {"rank": r["rank"]} if r["rank"] is not None else None,
        )
        afs.append(af_fp)
        cadds.append(cadd_fp)
        if af_fp >= 0:
            af_sum += af_fp
        if cadd_fp >= 0:
            cadd_sum += cadd_fp
        if rank_i >= 0:
            ranks[rank_i] += 1
    _p, _s, af_hist = st.column_totals(np.asarray(afs or [-1], np.int64),
                                       st.AF_EDGES_FP) if afs else \
        (0, 0, af_hist)
    if cadds:
        _p, _s, cadd_hist = st.column_totals(
            np.asarray(cadds, np.int64), st.CADD_EDGES_FP
        )
    block = None
    if windows:
        pos = np.asarray(sorted(r["pos"] for r in rows), np.int64)
        counts, present, means = [], [], []
        span = end - start + 1
        q, rem = divmod(span, windows)
        bounds = [start + q * w + (rem * w) // windows
                  for w in range(windows + 1)]
        by_pos = {}
        for r in rows:
            by_pos.setdefault(r["pos"], r)
        for w in range(windows):
            in_w = [p for p in pos.tolist()
                    if bounds[w] <= p < bounds[w + 1]] \
                if w < windows - 1 else [
                    p for p in pos.tolist() if bounds[w] <= p <= end]
            counts.append(len(in_w))
            fps = []
            for p in in_w:
                r = by_pos[p]
                if r["cadd"] is not None:
                    fps.append(int(round(r["cadd"] * st.CADD_SCALE)))
            present.append(len(fps))
            means.append(
                round(sum(fps) / (len(fps) * st.CADD_SCALE), 9)
                if fps else None
            )
        block = {"n": windows, "counts": counts,
                 "cadd_present": present, "cadd_mean": means}
    return {
        "region": f"{chromosome_label(code)}:{start}-{end}",
        **st.summary_from_totals(len(rows), af_sum, af_hist, cadd_sum,
                                 cadd_hist, ranks, list(metrics), block),
    }


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    store_dir = str(tmp_path_factory.mktemp("stats_store"))
    _store, truth = _build_store(store_dir)
    manager = SnapshotManager(store_dir)
    engine = QueryEngine(manager, region_cache_size=8)
    return store_dir, truth, manager, engine


# ---------------------------------------------------------------------------
# engine parity


def test_stats_parity_vs_brute_reference(served):
    _dir, truth, _manager, engine = served
    doc = json.loads(engine.stats_serve(_specs(), windows=4).assemble())
    assert doc["n"] == len(PANEL)
    assert doc["bins"] == st.edges_payload()
    for (code, start, end), entry in zip(PANEL, doc["results"]):
        assert entry == _brute_entry(truth, code, start, end, windows=4), \
            entry["region"]


def test_stats_metrics_subset_renders_only_selected(served):
    _dir, truth, _manager, engine = served
    doc = json.loads(
        engine.stats_serve(["8:1-10000"], metrics=["cadd"]).assemble()
    )
    entry = doc["results"][0]
    assert "cadd" in entry and "af" not in entry and "conseq" not in entry
    assert doc["metrics"] == ["cadd"]
    assert entry == _brute_entry(truth, 8, 1, 10_000, metrics=["cadd"])


def test_stats_device_host_and_breaker_fallback_identical(served):
    store_dir, _truth, _manager, engine = served
    specs = _specs()
    want = engine.stats_serve(specs, windows=3).assemble()
    assert engine.stats_serve(specs, windows=3,
                              host_only=True).assemble() == want
    # forced device: every group through the jitted kernels
    dev_engine = QueryEngine(SnapshotManager(store_dir),
                             region_cache_size=0, stats_device_min=0)
    assert dev_engine.stats_serve(specs, windows=3).assemble() == want
    # breaker-forced host fallback: a failing device kernel feeds the
    # breaker, answers stay byte-identical, and an open group stops
    # paying device attempts
    breaker = DeviceBreaker(cooldown_s=30.0)
    sick = QueryEngine(SnapshotManager(store_dir), region_cache_size=0,
                       stats_device_min=0, breaker=breaker)
    calls = {"n": 0}

    def boom(index, feats, starts, ends):
        calls["n"] += 1
        raise RuntimeError("injected stats kernel failure")

    sick._device_stats = boom
    sick._device_windows = lambda *a, **k: boom(*a[:4])
    for _ in range(breaker.failure_threshold):
        assert sick.stats_serve(specs, windows=3).assemble() == want
    codes = sorted({c for c, _s, _e in PANEL
                    if sick.snapshots.current().store.shards.get(c)})
    assert all(breaker.state(c) == "open" for c in codes)
    before = calls["n"]
    assert sick.stats_serve(specs, windows=3).assemble() == want
    assert calls["n"] == before  # open breaker: no device attempt


def test_stats_grammar_and_cap(served):
    store_dir, _truth, _manager, engine = served
    with pytest.raises(QueryError):
        engine.stats_serve(["8:1-100", "not-a-region"])
    with pytest.raises(QueryError):
        engine.stats_serve(["8:9-3"])
    with pytest.raises(QueryError, match="metrics"):
        engine.stats_serve(["8:1-100"], metrics=["af", "nope"])
    with pytest.raises(QueryError, match="metrics"):
        engine.stats_serve(["8:1-100"], metrics=[])
    with pytest.raises(QueryError, match="windows"):
        engine.stats_serve(["8:1-100"], windows=0)
    with pytest.raises(QueryError, match="windows"):
        engine.stats_serve(["8:1-100"], windows=st.MAX_WINDOWS + 1)
    capped = QueryEngine(SnapshotManager(store_dir), region_cache_size=0,
                         stats_max=2)
    with pytest.raises(QueryError, match="cap"):
        capped.stats_serve(["8:1-10", "8:1-10", "8:1-10"])


def test_stats_fault_fails_only_its_request(served):
    """serve.stats raise/eio fail exactly the armed request; the next
    panel answers byte-identically (the serve.regions contract)."""
    from annotatedvdb_tpu.utils.faults import InjectedFault

    _dir, _truth, _manager, engine = served
    specs = ["8:1-10000", "1:1-3000000"]
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
    assert engine.stats_serve(specs).assemble() == want


def test_stats_snapshot_swap_invalidates(served, tmp_path):
    """A loader commit swaps the generation and the analytics reflect
    the new rows — generation-keyed feature columns age out exactly like
    every other generation-keyed cache."""
    store_dir = str(tmp_path / "swap_store")
    _build_store(store_dir)
    manager = SnapshotManager(store_dir, ttl_s=0.0)
    engine = QueryEngine(manager, region_cache_size=0)
    spec = "8:9000000-9000100"
    before = json.loads(engine.stats_serve([spec]).assemble())
    assert before["results"][0]["count"] == 0
    store = VariantStore.load(store_dir)
    _append(store.shard(8), [{
        "chrom": 8, "pos": 9_000_050, "ref": "A", "alt": "T",
        "cadd": 12.0, "rank": 3, "af": 0.25,
    }])
    store.save(store_dir)
    assert manager.refresh()
    after = json.loads(engine.stats_serve([spec]).assemble())
    assert after["generation"] == before["generation"] + 1
    entry = after["results"][0]
    assert entry["count"] == 1
    assert entry["cadd"]["present"] == 1 and entry["cadd"]["mean"] == 12.0
    assert entry["af"]["present"] == 1 and entry["af"]["mean"] == 0.25


# ---------------------------------------------------------------------------
# HTTP: both front ends


def _get(port: int, path: str, headers=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=20) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


def _post(port: int, path: str, payload, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=20) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


@pytest.fixture()
def server(served):
    store_dir, _truth, _manager, _engine = served
    srv = start_server(store_dir=store_dir)
    try:
        yield srv
    finally:
        stop_server(srv)


def test_http_stats_byte_parity_with_the_engine(server, served):
    """Oracle: ``engine.stats_serve(...).assemble()`` called directly."""
    _dir, _truth, _manager, engine = served
    port = server.server_address[1]
    bodies = [
        {"regions": _specs()},
        {"regions": _specs(), "metrics": ["af", "conseq"]},
        {"regions": ["8:1-10000"], "windows": 8},
        {"regions": []},
    ]
    for body in bodies:
        status, text = _post(port, "/stats/region", body)
        assert status == 200
        want = engine.stats_serve(
            body["regions"], metrics=body.get("metrics"),
            windows=body.get("windows"),
        ).assemble()
        assert text == want, body
    # kind=stats metrics counted
    _st, metrics = _get(port, "/metrics")
    assert 'avdb_query_requests_total{kind="stats"}' in metrics


def test_http_stats_grammar_400_parity(server, served):
    """Oracle: the shared body grammar (``parse_stats_body``) and the
    engine's own validation called directly — the 400 body is the
    ``QueryError`` either raises."""
    from annotatedvdb_tpu.serve.http import STATS_BODY_ERROR, parse_stats_body

    engine = served[3]
    port = server.server_address[1]

    def refusal(body):
        try:
            specs, metrics, windows = parse_stats_body(
                json.dumps(body).encode())
            engine.stats_serve(specs, metrics=metrics, windows=windows)
        except QueryError as err:
            return str(err)
        raise AssertionError(f"{body!r} was accepted")

    assert refusal({"regions": "x"}) == STATS_BODY_ERROR
    for body in (
        {"regions": "x"},
        {"regions": [3]},
        {"regions": ["8:9-3"]},
        {"regions": ["8:1-10"], "metrics": "af"},
        {"regions": ["8:1-10"], "metrics": ["af", "nope"]},
        {"regions": ["8:1-10"], "windows": True},
        {"regions": ["8:1-10"], "windows": 0},
        ["not", "an", "object"],
    ):
        status, text = _post(port, "/stats/region", body)
        assert status == 400, body
        assert json.loads(text) == {"error": refusal(body)}, body


def test_http_stats_brownout_and_deadline_parity(server):
    from annotatedvdb_tpu.serve.http import (
        MSG_BROWNOUT_STATS,
        MSG_DEADLINE_ADMISSION,
    )

    body = {"regions": ["8:1-10000"]}
    ctx, port = server.ctx, server.server_address[1]
    # a sub-microsecond budget is dead by the admission check: 504
    status, text = _post(port, "/stats/region", body,
                         headers={"X-Deadline-Ms": "0.0001"})
    assert status == 504
    assert json.loads(text) == {"error": MSG_DEADLINE_ADMISSION}
    # brownout level 3 sheds analytics while point reads keep serving
    ctx.governor.force_level(3)
    try:
        status, text = _post(port, "/stats/region", body)
        assert status == 503
        assert json.loads(text) == {"error": MSG_BROWNOUT_STATS}
    finally:
        ctx.governor.force_level(0)
    status, _text = _post(port, "/stats/region", body)
    assert status == 200


def test_http_stats_cap_is_400(served, monkeypatch):
    monkeypatch.setenv("AVDB_SERVE_STATS_MAX", "2")
    store_dir, _truth, _manager, _engine = served
    httpd = start_server(store_dir=store_dir)
    try:
        port = httpd.server_address[1]
        status, text = _post(port, "/stats/region",
                             {"regions": ["8:1-10", "8:1-10", "8:1-10"]})
        assert status == 400 and "cap" in text
        status, _ = _post(port, "/stats/region", {"regions": ["8:1-10"]})
        assert status == 200
    finally:
        stop_server(httpd)


def test_http_stats_fault_500_once_then_serves(server):
    """An armed serve.stats fault surfaces as ONE 500 to the one caller;
    the next request answers normally."""
    body = {"regions": ["8:1-10000"]}
    port = server.server_address[1]
    _st, want = _post(port, "/stats/region", body)
    try:
        faults.reset("serve.stats:1:raise")
        status, text = _post(port, "/stats/region", body)
        assert status == 500 and "InjectedFault" in text
    finally:
        faults.reset("")
    status, text = _post(port, "/stats/region", body)
    assert status == 200 and text == want
