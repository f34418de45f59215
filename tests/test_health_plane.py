"""The health plane (PR 17): metrics time-series history, SLO burn-rate
alerting, and their serving surfaces.

Layers under test, bottom up: bucket-quantile estimation against numpy
ground truth; the sample arithmetic (counter deltas/rates, histogram
window deltas, fraction-above interpolation); the snapshot ring with its
persisted mirror, harvest, and replay; the SLO state machines
(multi-window burn + ok -> pending -> firing -> resolved hysteresis);
and the ``/alerts`` + ``/metrics/history`` routes byte-identical to
their payload builders, with the fleet views and ``doctor slo`` on top."""

import json
import time

import numpy as np
import pytest

from annotatedvdb_tpu.obs.metrics import MetricsRegistry, bucket_quantile
from annotatedvdb_tpu.obs.slo import (
    HealthPlane,
    SloRegistry,
    SloSpec,
    default_slos,
    fraction_above,
    replay_history,
    worst_of,
)
from annotatedvdb_tpu.obs.timeseries import (
    TimeSeriesRing,
    counter_delta,
    counter_rate,
    derive_series,
    harvest,
    histogram_window,
    history_path,
    list_history,
    load_history,
    trailing_samples,
    window_samples,
)
from conftest import start_server, stop_server

# ---------------------------------------------------------------------------
# quantile estimation (the satellite: pinned against numpy)


EDGES = tuple(round(0.1 * i, 1) for i in range(1, 101))  # 0.1 .. 10.0


def test_histogram_quantile_matches_numpy_within_bucket_width():
    rng = np.random.default_rng(7)
    vals = rng.uniform(0.0, 9.5, size=2_000)
    reg = MetricsRegistry()
    h = reg.histogram("t_q", EDGES, "test")
    for v in vals:
        h.observe(float(v))
    for q in (0.1, 0.5, 0.9, 0.99):
        est = h.quantile(q)
        truth = float(np.percentile(vals, q * 100))
        # bucket interpolation cannot beat the bucket width
        assert abs(est - truth) <= 0.1 + 1e-9, (q, est, truth)


def test_histogram_quantile_open_top_bucket_returns_max_edge():
    reg = MetricsRegistry()
    h = reg.histogram("t_top", (0.1, 1.0), "test")
    for _ in range(10):
        h.observe(50.0)  # all land in the +Inf tail
    # the honest answer is "at least the highest finite edge"
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.99) == 1.0


def test_histogram_quantile_empty_is_none_and_bad_q_raises():
    reg = MetricsRegistry()
    h = reg.histogram("t_empty", (0.1, 1.0), "test")
    assert h.quantile(0.5) is None
    with pytest.raises(ValueError):
        bucket_quantile((0.1,), [0, 0], 0, 1.5)
    # malformed counts row (length mismatch) is a no-answer, not a crash
    assert bucket_quantile((0.1, 1.0), [1, 2], 3, 0.5) is None


# ---------------------------------------------------------------------------
# sample arithmetic


def _counter_sample(t: float, name: str, value: float,
                    labels: dict | None = None) -> dict:
    return {"t": t, "metrics": {
        name: [{"kind": "counter", "labels": labels or {},
                "value": value}],
    }}


def test_counter_delta_and_rate_clamp_worker_restart():
    a = _counter_sample(100.0, "reqs", 500.0)
    b = _counter_sample(110.0, "reqs", 550.0)
    assert counter_delta(a, b, "reqs") == 50.0
    assert counter_rate(a, b, "reqs") == 5.0
    # a respawned worker restarts its counters: negative delta is a
    # restart, not negative work
    c = _counter_sample(120.0, "reqs", 30.0)
    assert counter_delta(b, c, "reqs") == 0.0
    # absent metric in the newer sample = no judgment
    assert counter_delta(a, {"t": 130.0, "metrics": {}}, "reqs") is None


def test_histogram_window_is_the_delta_histogram():
    def hsample(t, counts, count):
        return {"t": t, "metrics": {"lat": [
            {"kind": "histogram", "labels": {}, "edges": [0.1, 1.0],
             "counts": counts, "count": count},
        ]}}

    first = hsample(0.0, [5, 1, 0], 6)
    last = hsample(10.0, [15, 3, 2], 20)
    edges, counts, count = histogram_window(first, last, "lat")
    assert edges == [0.1, 1.0]
    assert counts == [10, 2, 2]
    assert count == 14


def test_fraction_above_interpolates_inside_the_split_bucket():
    edges, counts, count = (0.1, 1.0), [8, 2, 0], 10
    # threshold on an edge: everything in the upper buckets is above
    assert fraction_above(edges, counts, count, 0.1) == pytest.approx(0.2)
    # threshold splitting the first bucket (0..0.1): linear share above
    assert fraction_above(edges, counts, count, 0.05) == pytest.approx(0.6)
    # +Inf tail is always above
    assert fraction_above((0.1,), [0, 4], 4, 0.1) == 1.0
    assert fraction_above(edges, [0, 0, 0], 0, 0.1) is None


def test_window_samples_bracketing():
    samples = [{"t": float(t)} for t in range(10)]
    first, last = window_samples(samples, 3.0)
    assert (first["t"], last["t"]) == (6.0, 9.0)
    # a young ring spans less than the window: the honest span it has
    first, last = window_samples(samples[:2], 60.0)
    assert (first["t"], last["t"]) == (0.0, 1.0)
    assert window_samples(samples[:1], 60.0) is None
    # zero-width window still yields a delta (last two samples)
    first, last = window_samples(samples, 0.0)
    assert (first["t"], last["t"]) == (8.0, 9.0)


def test_derive_series_rates_gauges_and_quantiles():
    samples = [
        {"t": 0.0, "metrics": {
            "reqs": [{"kind": "counter", "labels": {}, "value": 0.0}],
            "depth": [{"kind": "gauge", "labels": {}, "value": 1.0}],
            "lat": [{"kind": "histogram", "labels": {},
                     "edges": [0.1, 1.0], "counts": [0, 0, 0],
                     "count": 0}],
        }},
        {"t": 10.0, "metrics": {
            "reqs": [{"kind": "counter", "labels": {}, "value": 50.0}],
            "depth": [{"kind": "gauge", "labels": {}, "value": 2.0}],
            "lat": [{"kind": "histogram", "labels": {},
                     "edges": [0.1, 1.0], "counts": [10, 0, 0],
                     "count": 10}],
        }},
    ]
    series = {(s["name"]): s for s in derive_series(samples)}
    assert [p["value"] for p in series["depth"]["points"]] == [1.0, 2.0]
    assert series["reqs"]["points"] == [{"t": 10.0, "rate": 5.0}]
    [lat_point] = series["lat"]["points"]
    assert lat_point["rate"] == 1.0
    # all 10 observations inside (0, 0.1]: p50 interpolates to the middle
    assert lat_point["p50"] == pytest.approx(0.05)
    assert lat_point["p99"] == pytest.approx(0.099)


# ---------------------------------------------------------------------------
# the ring: sample / prune / persist / load / harvest


def test_ring_roundtrip_prune_persist_harvest(tmp_path):
    store_dir = str(tmp_path / "store")
    clk = {"t": 1000.0}
    reg = MetricsRegistry()
    c = reg.counter("work_total", "test")
    ring = TimeSeriesRing(
        reg, worker=3, path=history_path(store_dir, 3),
        tick_s=1.0, history_s=5.0, clock=lambda: clk["t"],
    )
    assert ring.enabled
    for _ in range(8):
        c.inc(10)
        ring.sample()
        clk["t"] += 1.0
    # retention pruned: only the trailing history_s seconds remain
    samples = ring.samples()
    assert 5 <= len(samples) <= 6
    assert float(samples[-1]["t"]) - float(samples[0]["t"]) <= 5.0
    assert ring.span_s() == float(samples[-1]["t"]) - float(samples[0]["t"])

    assert ring.persist({"firing": 0}, force=True)
    doc = load_history(ring.path)
    assert doc["worker"] == 3 and doc["type"] == "timeseries"
    assert doc["firing"] == 0
    assert len(doc["samples"]) == len(samples)

    # harvest preserves the mirror with the death reason stamped in
    out = harvest(ring.path, store_dir, 3, "died rc=-9")
    assert out is not None
    hdoc = load_history(out)
    assert hdoc["harvested"]["reason"] == "died rc=-9"
    files = list_history(store_dir)
    assert files["live"] == [ring.path]
    assert files["harvested"] == [out]

    # a foreign file refuses to load
    bad = tmp_path / "store" / "history" / "junk.ts.json"
    bad.write_text(json.dumps({"type": "flight"}))
    with pytest.raises(ValueError):
        load_history(str(bad))


def test_ring_disabled_when_either_knob_zero(tmp_path):
    reg = MetricsRegistry()
    for tick_s, history_s in ((0.0, 300.0), (1.0, 0.0)):
        ring = TimeSeriesRing(reg, tick_s=tick_s, history_s=history_s)
        assert not ring.enabled
        assert not ring.due()
        assert ring.tick() is False
        assert ring.samples() == []


def test_env_knobs_fail_loudly_on_junk(monkeypatch):
    from annotatedvdb_tpu.obs import slo as slo_mod
    from annotatedvdb_tpu.obs import timeseries as ts_mod

    cases = [
        ("AVDB_OBS_TICK_S", ts_mod.obs_tick_from_env),
        ("AVDB_OBS_HISTORY_S", ts_mod.obs_history_from_env),
        ("AVDB_SLO_FAST_S", slo_mod.slo_fast_window_from_env),
        ("AVDB_SLO_SLOW_S", slo_mod.slo_slow_window_from_env),
        ("AVDB_SLO_BURN", slo_mod.slo_burn_from_env),
        ("AVDB_SLO_AVAIL_TARGET", slo_mod.slo_avail_target_from_env),
        ("AVDB_SLO_LOAD_FLOOR", slo_mod.slo_load_floor_from_env),
    ]
    for var, reader in cases:
        monkeypatch.setenv(var, "banana")
        with pytest.raises(ValueError, match=var):
            reader()
        monkeypatch.delenv(var)
        assert reader() >= 0  # defaults parse
    # domain checks beyond "is a number"
    monkeypatch.setenv("AVDB_SLO_AVAIL_TARGET", "1.5")
    with pytest.raises(ValueError):
        slo_mod.slo_avail_target_from_env()
    monkeypatch.delenv("AVDB_SLO_AVAIL_TARGET")
    monkeypatch.setenv("AVDB_SLO_BURN", "0")
    with pytest.raises(ValueError):
        slo_mod.slo_burn_from_env()
    monkeypatch.delenv("AVDB_SLO_BURN")
    # the slow window must sit beyond the fast window
    monkeypatch.setenv("AVDB_SLO_FAST_S", "60")
    monkeypatch.setenv("AVDB_SLO_SLOW_S", "30")
    with pytest.raises(ValueError):
        slo_mod.slo_slow_window_from_env()


# ---------------------------------------------------------------------------
# the SLO state machine: burn arithmetic + hysteresis


def _avail_sample(t: float, served: float, errors: float) -> dict:
    return {"t": t, "metrics": {
        "avdb_query_requests_total": [
            {"kind": "counter", "labels": {"kind": "point"},
             "value": served},
        ],
        "avdb_query_errors_total": [
            {"kind": "counter", "labels": {"kind": "point"},
             "value": errors},
        ],
    }}


def _breach_timeline() -> list:
    """100 requests/tick throughout; 50 errors/tick on ticks 3-4 only.
    With fast=1 tick and slow=2 ticks of window, the expected walk is
    ok(t<=2) -> pending(t=3) -> firing(t=4) -> resolved(t=7)."""
    samples, served, errors = [], 0.0, 0.0
    for t in range(8):
        if t in (3, 4):
            errors += 50.0
        served += 100.0
        samples.append(_avail_sample(float(t), served, errors))
    return samples


AVAIL_SPEC = dict(target=0.999)


def _avail_registry():
    return SloRegistry(
        MetricsRegistry(),
        specs=[SloSpec("availability", "availability", "test",
                       **AVAIL_SPEC)],
        fast_s=1.0, slow_s=2.0, burn_threshold=2.0,
    )


def test_slo_hysteresis_walks_ok_pending_firing_resolved():
    slos = _avail_registry()
    samples = _breach_timeline()
    states = []
    for i in range(len(samples)):
        [row] = slos.evaluate(samples[: i + 1],
                              now=float(samples[i]["t"]))
        states.append(row["state"])
    assert states == ["ok", "ok", "ok", "pending", "firing",
                      "firing", "firing", "resolved"]
    [final] = slos.alerts()
    assert final["fired_total"] == 1
    assert slos.firing() == 0
    assert slos.worst_state() == "resolved"
    # the breach burn hit the cap: 33% errors against a 0.1% budget
    assert final["burn_fast"] == 0.0  # clean at the final tick


def test_slo_burn_requires_both_windows():
    """One hot fast window never pages: the slow window must agree."""
    slos = SloRegistry(
        MetricsRegistry(),
        specs=[SloSpec("availability", "availability", "test",
                       **AVAIL_SPEC)],
        fast_s=1.0, slow_s=60.0, burn_threshold=2.0,
    )
    # long clean history, then one hot tick: the slow window dilutes the
    # burst below threshold, so the state never leaves ok
    samples, served = [], 0.0
    for t in range(60):
        served += 100.0
        samples.append(_avail_sample(float(t), served, 0.0))
    samples.append(_avail_sample(60.0, served + 100.0, 5.0))
    for i in range(len(samples)):
        [row] = slos.evaluate(samples[: i + 1],
                              now=float(samples[i]["t"]))
    assert row["state"] == "ok"
    assert row["burn_fast"] > 2.0  # the fast window IS hot
    assert row["burn_slow"] < 2.0  # ... but the slow window says budget


def test_replay_history_reproduces_the_episode():
    replay = replay_history(_breach_timeline(), fast_s=1.0, slow_s=2.0,
                            burn_threshold=2.0)
    walks = [(e["from"], e["to"], e["t"]) for e in replay["episodes"]
             if e["slo"] == "availability"]
    assert walks == [("ok", "pending", 3.0), ("pending", "firing", 4.0),
                     ("firing", "resolved", 7.0)]
    assert replay["ticks"] == 8 and replay["span_s"] == 7.0
    assert replay["max_burn"]["availability"] > 2.0
    [avail] = [a for a in replay["alerts"] if a["slo"] == "availability"]
    assert avail["state"] == "resolved" and avail["fired_total"] == 1


def test_worst_of_ranking():
    assert worst_of([]) == "ok"
    assert worst_of(["ok", "resolved"]) == "resolved"
    assert worst_of(["resolved", "pending", "ok"]) == "pending"
    assert worst_of(["pending", "firing"]) == "firing"


# ---------------------------------------------------------------------------
# the gauge-ceiling kind (PR 18: follower replication lag)


def _lag_sample(t: float, lag: float | None) -> dict:
    metrics = {} if lag is None else {
        "avdb_replication_lag_seconds": [
            {"kind": "gauge", "labels": {}, "value": lag},
        ],
    }
    return {"t": t, "metrics": metrics}


LAG_SPEC = dict(metric="avdb_replication_lag_seconds", ceiling=5.0,
                objective=0.9)


def test_gauge_ceiling_burn_is_the_breached_point_fraction():
    spec = SloSpec("replication_lag", "gauge_ceiling", "t", **LAG_SPEC)
    # 10 points, 3 past the ceiling: frac 0.3 against a 0.1 budget = 3.0
    win = [_lag_sample(float(t), 8.0 if t < 3 else 0.1)
           for t in range(10)]
    assert spec.burn((win[0], win[-1]), window=win) == pytest.approx(3.0)
    # every point clean -> burn 0; every point hot -> 1/0.1 = 10
    clean = [_lag_sample(float(t), 0.2) for t in range(4)]
    assert spec.burn((clean[0], clean[-1]), window=clean) == 0.0
    hot = [_lag_sample(float(t), 9.0) for t in range(4)]
    assert spec.burn((hot[0], hot[-1]), window=hot) \
        == pytest.approx(10.0)
    # metric absent (not a follower) = no judgment, never a clean 0
    bare = [_lag_sample(float(t), None) for t in range(4)]
    assert spec.burn((bare[0], bare[-1]), window=bare) is None
    # ceiling 0 = dormant (the AVDB_REPL_MAX_LAG_S=0 story), even hot
    dormant = SloSpec("replication_lag", "gauge_ceiling", "t",
                      metric="avdb_replication_lag_seconds", ceiling=0.0)
    assert dormant.burn((hot[0], hot[-1]), window=hot) is None
    # pair-only callers (no window kwarg) get the two-point fallback
    assert spec.burn((hot[0], hot[-1])) == pytest.approx(10.0)
    note = spec.target_note()
    assert note == {"ceiling": 5.0, "objective": 0.9}


def test_trailing_samples_bracketing():
    samples = [_lag_sample(float(t), 0.0) for t in range(10)]
    win = trailing_samples(samples, 3.0, now=9.0)
    assert [s["t"] for s in win] == [6.0, 7.0, 8.0, 9.0]
    # a window thinner than two samples falls back to the newest two
    assert [s["t"] for s in trailing_samples(samples, 0.0, now=20.0)] \
        == [8.0, 9.0]
    assert trailing_samples(samples[:1], 3.0) is None


def test_replication_lag_slo_fires_on_sustained_breach_then_resolves():
    """The lag-gauge walk mirrors the availability one: a follower stuck
    past the bound for both windows pages; catching back up resolves."""
    slos = SloRegistry(
        MetricsRegistry(),
        specs=[SloSpec("replication_lag", "gauge_ceiling", "test",
                       **LAG_SPEC)],
        fast_s=1.0, slow_s=2.0, burn_threshold=2.0,
    )
    # lag healthy (ticks 0-2), stuck at 30s (ticks 3-5), recovered
    lag = {0: 0.1, 1: 0.1, 2: 0.1, 3: 30.0, 4: 30.0, 5: 30.0,
           6: 0.1, 7: 0.1, 8: 0.1, 9: 0.1}
    samples, states = [], []
    for t in range(10):
        samples.append(_lag_sample(float(t), lag[t]))
        [row] = slos.evaluate(list(samples), now=float(t))
        states.append(row["state"])
    assert states[:3] == ["ok", "ok", "ok"]
    assert "firing" in states
    assert states[-1] == "resolved"
    [final] = slos.alerts()
    assert final["fired_total"] == 1
    assert final["kind"] == "gauge_ceiling"
    assert final["ceiling"] == 5.0


def test_default_slos_declare_replication_lag(monkeypatch):
    [spec] = [s for s in default_slos() if s.name == "replication_lag"]
    assert spec.kind == "gauge_ceiling"
    assert spec.params["metric"] == "avdb_replication_lag_seconds"
    assert spec.params["ceiling"] == 5.0  # the AVDB_REPL_MAX_LAG_S default
    # the readiness knob IS the alerting knob: 0 disables both planes
    monkeypatch.setenv("AVDB_REPL_MAX_LAG_S", "0")
    [spec] = [s for s in default_slos() if s.name == "replication_lag"]
    assert spec.params["ceiling"] == 0.0
    hot = [_lag_sample(float(t), 99.0) for t in range(4)]
    assert spec.burn((hot[0], hot[-1]), window=hot) is None


def test_health_plane_tick_persists_alert_extras(tmp_path):
    store_dir = str(tmp_path / "store")
    clk = {"t": 500.0}
    reg = MetricsRegistry()
    hp = HealthPlane(
        reg, store_dir=store_dir, worker=0,
        specs=[SloSpec("availability", "availability", "t",
                       **AVAIL_SPEC)],
        tick_s=1.0, history_s=60.0, fast_s=1.0, slow_s=2.0,
        burn_threshold=2.0, clock=lambda: clk["t"],
    )
    assert hp.enabled and hp.errors == 0
    assert hp.tick()
    clk["t"] += 1.0
    hp.close()  # forced final persist
    doc = load_history(hp.ring.path)
    assert doc["firing"] == 0
    assert [a["slo"] for a in doc["alerts"]] == ["availability"]


# ---------------------------------------------------------------------------
# serving surfaces: /alerts + /metrics/history on BOTH front ends


def _build_store(store_dir: str) -> None:
    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.types import encode_allele_array

    width = 8
    store = VariantStore(width=width)
    n = 16
    refs, alts = ["A"] * n, ["G"] * n
    ref, ref_len = encode_allele_array(refs, width)
    alt, alt_len = encode_allele_array(alts, width)
    h = identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts)
    store.shard(8).append(
        {"pos": np.arange(1000, 1000 + 10 * n, 10, dtype=np.int32),
         "h": h, "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
    )
    store.save(store_dir)


def _get(port: int, path: str):
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


@pytest.fixture()
def health_served(tmp_path):
    """A server over one store with a HealthPlane (tick_s high enough
    that only the test's manual ticks move it — the payloads must be
    deterministic for byte-parity)."""
    store_dir = str(tmp_path / "store")
    _build_store(store_dir)
    clk = {"t": 2000.0}
    registry = MetricsRegistry()
    health = HealthPlane(
        registry, store_dir=store_dir, worker=0,
        specs=[SloSpec("availability", "availability", "test",
                       **AVAIL_SPEC)],
        tick_s=30.0, history_s=600.0, fast_s=1.0, slow_s=2.0,
        burn_threshold=2.0, clock=lambda: clk["t"],
    )
    # start the time-gate NOW: the server's maintenance tick may not
    # sneak a startup tick in — only the test's manual ticks move the ring
    health.ring._last_tick = time.monotonic()
    server = start_server(store_dir=store_dir, registry=registry,
                          health=health)
    try:
        yield (store_dir, clk, health, server.server_address[1],
               server.ctx)
    finally:
        stop_server(server)


def _tick_n(clk, health, n: int, step: float = 1.0) -> None:
    for _ in range(n):
        assert health.tick()
        clk["t"] += step


def test_alerts_and_history_byte_parity_with_the_builders(health_served):
    """Oracle: ``alerts_payload`` / ``metrics_history_payload`` called
    directly on the server's context with the same query string."""
    from urllib.parse import urlparse

    from annotatedvdb_tpu.serve.http import (
        alerts_payload,
        metrics_history_payload,
    )

    _store_dir, clk, health, tport, ctx = health_served
    _tick_n(clk, health, 4)
    for path in ("/alerts", "/metrics/history", "/metrics/history?window=2",
                 "/metrics/history?window=junk"):
        status, body = _get(tport, path)
        assert status == 200, (path, status)
        builder = alerts_payload if path.startswith("/alerts") \
            else metrics_history_payload
        assert body == builder(ctx, urlparse(path).query), path

    rec = json.loads(_get(tport, "/alerts")[1])
    assert rec["enabled"] is True and rec["worker"] == 0
    assert rec["state"] == "ok" and rec["firing"] == 0
    assert rec["windows"] == {"fast_s": 1.0, "slow_s": 2.0}
    assert [a["slo"] for a in rec["alerts"]] == ["availability"]

    hist = json.loads(_get(tport, "/metrics/history")[1])
    assert hist["enabled"] is True and hist["samples"] == 4
    assert hist["span_s"] == 3.0
    assert any(s["name"] == "avdb_slo_burn_rate" for s in hist["series"])
    # ?window trims to the trailing seconds; junk windows are ignored
    trimmed = json.loads(_get(tport, "/metrics/history?window=1.5")[1])
    assert trimmed["samples"] == 2
    sloppy = json.loads(_get(tport, "/metrics/history?window=junk")[1])
    assert sloppy["samples"] == 4


def test_healthz_and_prometheus_carry_alert_state(health_served):
    _store_dir, clk, health, port, _ctx = health_served
    _tick_n(clk, health, 2)
    hz = json.loads(_get(port, "/healthz")[1])
    assert hz["alerts"] == "ok" and hz["alerts_firing"] == 0
    _status, metrics = _get(port, "/metrics")
    assert "avdb_slo_burn_rate" in metrics
    assert "avdb_alerts_firing" in metrics


def test_fleet_views_merge_sibling_mirrors(health_served):
    store_dir, clk, health, port, _ctx = health_served
    _tick_n(clk, health, 3)
    # a sibling worker's persisted mirror (fresh enough for the TTL)
    sib_reg = MetricsRegistry()
    sib_reg.counter("sib_total", "t").inc(7)
    sib = TimeSeriesRing(sib_reg, worker=1,
                         path=history_path(store_dir, 1),
                         tick_s=1.0, history_s=60.0)
    sib.sample()
    sib.sample()
    sib.persist({"alerts": [{"slo": "availability", "state": "firing"}],
                 "firing": 1}, force=True)
    rec = json.loads(_get(port, "/alerts?fleet=1")[1])
    assert rec["fleet"] is True
    assert set(rec["workers"]) == {"0", "1"}
    assert rec["workers"]["1"]["state"] == "firing"
    assert rec["firing"] == 1
    assert rec["state"] == "firing"  # worst across the fleet
    hist = json.loads(_get(port, "/metrics/history?fleet=1")[1])
    assert set(hist["workers"]) == {"0", "1"}
    assert hist["workers"]["1"]["samples"] == 2


def test_requests_and_probes_never_tick_the_plane(tmp_path):
    """The plane is ticked by the server's maintenance tick alone: a
    completed request (``ctx.observe``) and a readiness probe
    (``ctx.ready_state``) leave a due plane untouched — neither a request
    nor the event loop ever pays for a sample + persist."""
    from annotatedvdb_tpu.serve import QueryEngine, SnapshotManager
    from annotatedvdb_tpu.serve.aio import LoopBatcher
    from annotatedvdb_tpu.serve.http import ServeContext

    store_dir = str(tmp_path / "store")
    _build_store(store_dir)
    registry = MetricsRegistry()
    health = HealthPlane(registry, store_dir=store_dir, worker=0,
                         tick_s=0.01, history_s=60.0)
    manager = SnapshotManager(store_dir, log=lambda m: None)
    engine = QueryEngine(manager, registry=registry)
    ctx = ServeContext(manager, engine, LoopBatcher(engine), registry,
                       health=health)
    time.sleep(0.02)
    assert health.due()
    for _ in range(3):
        ctx.observe("point", 0.001, rows=1)
        assert ctx.ready_state() == (True, "ok")
    assert health.due() and health.ring.samples() == []
    assert health.tick()  # what the maintenance tick runs on its pool
    assert len(health.ring.samples()) == 1


def test_disabled_plane_payloads(tmp_path):
    store_dir = str(tmp_path / "store")
    _build_store(store_dir)
    httpd = start_server(store_dir=store_dir)
    try:
        port = httpd.server_address[1]
        rec = json.loads(_get(port, "/alerts")[1])
        assert rec == {"enabled": False, "worker": 0,
                       "state": "disabled", "firing": 0, "alerts": []}
        hist = json.loads(_get(port, "/metrics/history")[1])
        assert hist["enabled"] is False and hist["series"] == []
        hz = json.loads(_get(port, "/healthz")[1])
        assert hz["alerts"] == "disabled" and hz["alerts_firing"] == 0
    finally:
        stop_server(httpd)


# ---------------------------------------------------------------------------
# doctor slo


def test_doctor_slo_replays_harvested_history(tmp_path, capsys):
    from annotatedvdb_tpu.cli import doctor

    store_dir = tmp_path / "store"
    hist_dir = store_dir / "history"
    hist_dir.mkdir(parents=True)
    doc = {
        "type": "timeseries", "worker": 2, "t": time.time(),
        "tick_s": 1.0, "history_s": 60.0,
        "samples": _breach_timeline(),
        "harvested": {"reason": "died rc=-9", "t": time.time()},
    }
    (hist_dir / "1700000000000-w2.json").write_text(json.dumps(doc))
    rc = doctor.main([
        "slo", "--storeDir", str(store_dir), "--fast", "1.0",
        "--slow", "2.0", "--burn", "2.0", "--json",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    [rep] = out["replays"]
    assert rep["worker"] == 2
    assert rep["harvested"]["reason"] == "died rc=-9"
    walks = [(e["from"], e["to"]) for e in rep["episodes"]
             if e["slo"] == "availability"]
    assert ("pending", "firing") in walks
    assert ("firing", "resolved") in walks

    # human rendering names the file, the reason and the states
    rc = doctor.main(["slo", "--storeDir", str(store_dir),
                      "--fast", "1.0", "--slow", "2.0", "--burn", "2.0"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "died rc=-9" in err and "availability" in err


def test_doctor_slo_no_history_exits_2(tmp_path, capsys):
    from annotatedvdb_tpu.cli import doctor

    empty = tmp_path / "store"
    empty.mkdir()
    assert doctor.main(["slo", "--storeDir", str(empty)]) == 2
    err = capsys.readouterr().err
    assert "AVDB_OBS_TICK_S" in err
    assert doctor.main(["slo", "--storeDir",
                        str(tmp_path / "missing")]) == 2
