"""Autonomous storage management: watermark semantics, load-aware
pausing, the shared preemption-retry policy, disk-pressure degradation
(507 on both front ends, SIGKILL-safe, recovery pinned), the extended
heartbeat health slots, and `doctor status`."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.maintenance import (
    DiskReserveGuard,
    MaintenanceDaemon,
    store_status,
)
from annotatedvdb_tpu.store.variant_store import Segment
from annotatedvdb_tpu.utils import faults
from annotatedvdb_tpu.utils.retry import retry_preempted
from conftest import bulk_envelope, start_server, stop_server

WIDTH = 8


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.reset("")


def _fragment(store_dir: str, nseg: int, n: int = 120,
              code: int = 6) -> None:
    """``nseg`` disjoint checkpoint segments on one chromosome — each
    save is a real loader checkpoint, so the store's manifest carries
    ``nseg`` on-disk segment files for the group."""
    store = VariantStore(width=WIDTH)
    shard = store.shard(code)
    for k in range(nseg):
        cols = {
            "pos": np.arange(500 + 50_000 * k, 500 + 50_000 * k + n,
                             dtype=np.int32),
            "h": np.arange(n, dtype=np.uint32) + 1,
            "ref_len": np.full(n, 1, np.int32),
            "alt_len": np.full(n, 1, np.int32),
        }
        shard.append_segment(Segment.build(
            cols, np.full((n, WIDTH), 65, np.uint8),
            np.full((n, WIDTH), 71, np.uint8),
        ))
        shard._starts_cache = None
        store.save(store_dir)


def _amp(daemon: MaintenanceDaemon) -> int:
    return max(daemon.read_amp().values(), default=0)


def _daemon(store_dir, **kw):
    kw.setdefault("high", 4)
    kw.setdefault("low", 2)
    kw.setdefault("tick_s", 0.05)
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("log", lambda m: None)
    return MaintenanceDaemon(store_dir, **kw)


def _resume_now(daemon) -> None:
    """Collapse a pending backoff so the next tick evaluates again."""
    with daemon._lock:
        daemon._resume_at = 0.0


# ---------------------------------------------------------------------------
# watermark edge semantics


def test_exactly_at_high_watermark_trips(tmp_path):
    """>= trips: a group holding EXACTLY the high watermark's segment
    count engages the daemon and gets compacted."""
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=4)
    d = _daemon(store_dir, high=4, low=2)
    assert d.tick() == "pass"
    assert _amp(d) == 1
    assert d.stats()["passes"] == 1


def test_below_high_watermark_stays_idle(tmp_path):
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=3)
    d = _daemon(store_dir, high=4, low=2)
    assert d.tick() == "idle"
    assert _amp(d) == 3  # byte-untouched: no pass ran
    assert d.stats()["passes"] == 0


def test_hysteresis_exit_below_low_watermark(tmp_path):
    """Engaged state ends only once every group is at/below LOW — and a
    store sitting BETWEEN low and high never re-engages (that is the
    hysteresis: entry and exit are different lines)."""
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=5)
    d = _daemon(store_dir, high=4, low=2)
    assert d.tick() == "pass"
    assert d.stats()["engaged"] is False  # converged: amp 1 <= low 2
    # grow the store back to BETWEEN low and high: 3 segments
    store = VariantStore.load(store_dir)
    shard = store.shard(6)
    for k in range(2):
        n = 50
        cols = {
            "pos": np.arange(9_000_000 + 50_000 * k,
                             9_000_000 + 50_000 * k + n, dtype=np.int32),
            "h": np.arange(n, dtype=np.uint32) + 7,
            "ref_len": np.full(n, 1, np.int32),
            "alt_len": np.full(n, 1, np.int32),
        }
        shard.append_segment(Segment.build(
            cols, np.full((n, WIDTH), 65, np.uint8),
            np.full((n, WIDTH), 84, np.uint8),
        ))
        shard._starts_cache = None
        store.save(store_dir)
    assert _amp(d) == 3  # low < 3 < high
    assert d.tick() == "idle"  # engaged only at >= high, never between
    assert d.stats()["passes"] == 1


def test_compact_min_segments_floor_wins_over_watermark(tmp_path,
                                                        monkeypatch):
    """A compactor floor ABOVE the watermark makes every pass a no-op;
    the daemon must disengage instead of spinning no-op passes."""
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=5)
    monkeypatch.setenv("AVDB_COMPACT_MIN_SEGMENTS", "99")
    d = _daemon(store_dir, high=4, low=2, cooldown_s=5.0)
    assert d.tick() == "noop"
    assert _amp(d) == 5  # floor won: nothing was merged
    st = d.stats()
    assert st["engaged"] is False and st["passes"] == 0
    # the watermark condition persists, so without a cooldown the next
    # tick would re-engage/re-plan/re-log the same pair forever — the
    # noop installed a backoff instead of a hammering loop
    assert d.tick() == "cooldown"
    assert st["backoff_s"] >= 0.0
    _resume_now(d)
    assert d.tick() == "noop"  # re-evaluates after the backoff only


def test_backoff_doubles_on_repeated_preemptions(tmp_path, monkeypatch):
    """Repeated clean preemptions back the daemon off exponentially —
    never a tight retry loop against a busy writer."""
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=5)
    d = _daemon(store_dir, high=4, low=2, cooldown_s=10.0, retries=0)
    monkeypatch.setattr(
        d, "_compact_once",
        lambda: {"status": "aborted", "reason": "test writer"},
    )
    assert d.tick() == "preempted"
    st1 = d.stats()
    assert st1["preemptions"] == 1
    assert 9.0 < st1["backoff_s"] <= 10.0
    assert d.tick() == "cooldown"  # the backoff actually holds
    _resume_now(d)
    assert d.tick() == "preempted"
    st2 = d.stats()
    assert st2["preemptions"] == 2
    assert 19.0 < st2["backoff_s"] <= 20.0  # doubled
    assert st2["engaged"] is True  # still committed to converging


def test_retry_preempted_is_used_before_backoff(tmp_path, monkeypatch):
    """The shared preemption-retry policy: one clean preemption retries
    in-pass (the chaos-soak behavior, hoisted); only a pass that stays
    preempted after the retries becomes a setback."""
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=5)
    d = _daemon(store_dir, high=4, low=2, retries=1)
    calls = {"n": 0}
    real = d._compact_once

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            return {"status": "aborted", "reason": "racing writer"}
        return real()

    monkeypatch.setattr(d, "_compact_once", flaky)
    assert d.tick() == "pass"
    assert calls["n"] == 2  # aborted once, retried, landed
    assert d.stats()["preemptions"] == 0


def test_paused_when_worker_health_hot_resumes_when_calm(tmp_path):
    """Load-awareness: brownout >= 1 (or a breached p99 target) on any
    live worker pauses the daemon BEFORE it opens a segment; calm health
    resumes it after the cool-down."""
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=5)
    health = {"brownout_max": 1, "exceed_max": 0.0}
    d = _daemon(store_dir, high=4, low=2, cooldown_s=5.0,
                health=lambda: dict(health))
    assert d.tick() == "paused"
    assert _amp(d) == 5  # the pass never started
    assert d.stats()["paused"] == 1
    # p99-exceedance alone is also hot
    health.update(brownout_max=0, exceed_max=0.2)
    _resume_now(d)
    d._hot_check_at = 0.0  # drop the health cache
    assert d.tick() == "paused"
    # calm again: the pass runs
    health.update(exceed_max=0.0)
    _resume_now(d)
    d._hot_check_at = 0.0
    assert d.tick() == "pass"
    assert _amp(d) == 1


def test_mid_pass_health_abort_counts_as_paused(tmp_path, monkeypatch):
    """A pass our own health cancel aborted mid-run reports as a PAUSE
    (the brownout-paused-compaction observable the soak asserts on)."""
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=5)
    calls = {"n": 0}

    def health():
        calls["n"] += 1
        # calm at the pre-pass gate, hot at the post-abort check
        return {"brownout_max": 0 if calls["n"] == 1 else 1,
                "exceed_max": 0.0}

    d = _daemon(store_dir, high=4, low=2, cooldown_s=1.0, retries=0,
                health=health)
    monkeypatch.setattr(
        d, "_compact_once",
        lambda: {"status": "aborted", "reason": "cancelled mid-merge"},
    )
    assert d.tick() == "paused"
    st = d.stats()
    assert st["paused"] == 1 and st["preemptions"] == 1


def test_daemon_disables_after_consecutive_hard_failures(tmp_path,
                                                         monkeypatch):
    """Hard failures back off and, after MAX_CONSEC_FAILURES, disable
    the daemon loudly — never a compact-crash loop."""
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=5)
    logs: list = []
    d = _daemon(store_dir, high=4, low=2, cooldown_s=0.0, retries=0,
                log=logs.append)

    def boom():
        raise OSError("disk on fire")

    monkeypatch.setattr(d, "_compact_once", boom)
    for _ in range(MaintenanceDaemon.MAX_CONSEC_FAILURES):
        _resume_now(d)
        assert d.tick() == "failed"
    st = d.stats()
    assert st["disabled"] is True
    assert st["failures"] == MaintenanceDaemon.MAX_CONSEC_FAILURES
    assert d.tick() == "disabled"  # permanently out, no more passes
    assert any("DISABLED" in m for m in logs)


def test_daemon_metrics_registered(tmp_path):
    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=4)
    registry = MetricsRegistry()
    d = _daemon(store_dir, high=4, low=2, registry=registry)
    assert d.tick() == "pass"
    text = registry.render_prometheus()
    assert "avdb_maintain_passes_total 1" in text
    assert "avdb_maintain_preemptions_total 0" in text
    assert "avdb_maintain_paused_total 0" in text


def test_bad_watermark_knob_fails_fleet_startup(tmp_path, monkeypatch):
    """A typo'd AVDB_MAINTAIN_* must fail startup loudly (the ServeFleet
    resolves knobs at __init__), never silently disable autonomy."""
    from annotatedvdb_tpu.serve.fleet import ServeFleet

    monkeypatch.setenv("AVDB_MAINTAIN_SEGMENTS_HIGH", "banana")
    with pytest.raises(ValueError, match="AVDB_MAINTAIN_SEGMENTS_HIGH"):
        ServeFleet(str(tmp_path), port=0, workers=1, maintain=True)


# ---------------------------------------------------------------------------
# retry_preempted (the shared policy itself)


def test_retry_preempted_passes_through_success():
    calls = {"n": 0}

    def run():
        calls["n"] += 1
        return {"status": "compacted"}

    assert retry_preempted(run, retries=3)["status"] == "compacted"
    assert calls["n"] == 1


def test_retry_preempted_bounded_and_returns_last_report():
    calls = {"n": 0}

    def run():
        calls["n"] += 1
        return {"status": "aborted", "reason": "busy"}

    report = retry_preempted(run, retries=2, base_delay=0.0)
    assert report["status"] == "aborted"
    assert calls["n"] == 3  # initial + 2 retries, then give up


def test_retry_preempted_never_retries_hard_failures():
    calls = {"n": 0}

    def run():
        calls["n"] += 1
        raise OSError("hard")

    with pytest.raises(OSError):
        retry_preempted(run, retries=5)
    assert calls["n"] == 1


def test_retry_preempted_stops_on_success_mid_sequence():
    reports = [{"status": "aborted"}, {"status": "compacted"}]
    calls = {"n": 0}

    def run():
        calls["n"] += 1
        return reports[calls["n"] - 1]

    assert retry_preempted(run, retries=5,
                           base_delay=0.0)["status"] == "compacted"
    assert calls["n"] == 2


# ---------------------------------------------------------------------------
# heartbeat health slots + fleet aggregation


def test_hb_slot_roundtrip_and_worker_health_aggregation(tmp_path):
    import mmap as mmap_mod
    import struct

    from annotatedvdb_tpu.serve.fleet import HB_SLOT, ServeFleet

    fleet = ServeFleet(str(tmp_path), port=0, workers=3)
    try:
        class _Live:
            def poll(self):
                return None

        class _Dead:
            def poll(self):
                return 0

        fleet._procs = {0: _Live(), 1: _Live(), 2: _Dead()}
        now = time.time()
        HB_SLOT.pack_into(fleet._hb_mm, 0, now, 0.01, 0, 5)
        HB_SLOT.pack_into(fleet._hb_mm, HB_SLOT.size, now, 0.30, 2, 9)
        # worker 2 is dead: its (stale, hot) slot must not count
        HB_SLOT.pack_into(fleet._hb_mm, 2 * HB_SLOT.size, now, 1.0, 3, 99)
        h = fleet.worker_health()
        assert h["workers"] == 2
        assert h["brownout_max"] == 2
        assert h["exceed_max"] == pytest.approx(0.30)
        assert h["queue_depth_max"] == 9
        # a live worker that has not ticked yet (beat 0) contributes
        # nothing — startup reads as calm, not as brownout
        HB_SLOT.pack_into(fleet._hb_mm, HB_SLOT.size, 0.0, 0.9, 3, 1)
        h = fleet.worker_health()
        assert h["workers"] == 1 and h["brownout_max"] == 0
        # the wedge watchdog still reads the beat as the first field
        beat = struct.unpack_from("<d", fleet._hb_mm, 0)[0]
        assert beat == pytest.approx(now)
        assert isinstance(fleet._hb_mm, mmap_mod.mmap)
    finally:
        fleet._reserve.close()
        fleet._hb_mm.close()
        os.unlink(fleet._hb_path)


def test_aio_tick_publishes_health_fields(tmp_path):
    """The worker side of the health contract: the maintenance tick
    writes (beat, exceedance, brownout level, queue depth) into its
    slot."""
    from annotatedvdb_tpu.serve.aio import build_aio_server
    from annotatedvdb_tpu.serve.fleet import HB_SLOT

    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=1)
    hb = tmp_path / "hb"
    hb.write_bytes(b"\x00" * HB_SLOT.size)
    server = build_aio_server(
        store_dir=store_dir, port=0, heartbeat_file=str(hb),
        heartbeat_index=0,
    )
    try:
        server.ctx.governor.force_level(2)
        server.start_background()
        deadline = time.monotonic() + 10
        beat = level = 0
        while time.monotonic() < deadline:
            beat, _exceed, level, _depth = HB_SLOT.unpack_from(
                server._hb_mm, 0
            )
            if beat > 0.0 and level == 2:
                break
            time.sleep(0.05)
        assert beat > 0.0
        assert level == 2
    finally:
        server.shutdown()
        server.ctx.batcher.close()


def test_governor_exposes_exceedance():
    from annotatedvdb_tpu.serve.resilience import OverloadGovernor

    gov = OverloadGovernor(depth_fn=lambda: 0, max_queue=100,
                           p99_target_s=0.001)
    assert gov.exceedance == 0.0
    for _ in range(50):
        gov.note_latency(1.0)  # way over target
    assert gov.exceedance > 0.0


# ---------------------------------------------------------------------------
# disk-pressure degradation (507 contract)


def _seed_serve_store():
    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.types import encode_allele_array

    store = VariantStore(width=WIDTH)
    ref, ref_len = encode_allele_array(["A"] * 3, WIDTH)
    alt, alt_len = encode_allele_array(["C"] * 3, WIDTH)
    store.shard(3).append(
        {"pos": np.asarray([10, 20, 30], np.int32),
         "h": identity_hashes(WIDTH, ref, alt, ref_len, alt_len),
         "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
    )
    return store


def _request(port, method, path, body=None, timeout=15):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None,
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as err:
        return err.code, err.read()


@pytest.fixture()
def live(tmp_path):
    """A write-enabled server over an on-disk store with its memtable +
    WAL (the test_upsert shape): ``{"store_dir", "port", "ctx"}``."""
    from annotatedvdb_tpu.serve import MemtableSnapshots, SnapshotManager
    from annotatedvdb_tpu.store.memtable import Memtable
    from annotatedvdb_tpu.store.wal import WriteAheadLog

    store_dir = str(tmp_path / "store")
    _seed_serve_store().save(store_dir)
    registry = MetricsRegistry()
    mgr = SnapshotManager(store_dir, log=lambda m: None)
    mem = Memtable(
        width=WIDTH, store_dir=store_dir,
        wal=WriteAheadLog(store_dir, "serve-w0", log=lambda m: None),
        registry=registry, log=lambda m: None,
    )
    server = start_server(manager=MemtableSnapshots(mgr, mem),
                          memtable=mem, registry=registry)
    yield {
        "store_dir": store_dir, "port": server.server_address[1],
        "ctx": server.ctx,
    }
    stop_server(server)
    mem.wal.close(remove_if_empty=True)


def test_disk_reserve_507_parity_reads_survive_and_recovery(live):
    """The disk-pressure contract end to end: with the reserve breached
    upserts answer 507 while point/bulk reads keep serving; freeing
    space (reserve cleared) resumes upserts.  Oracle: the one message
    constant ``MSG_DISK_RESERVE``, and for the reads the engine called
    directly (``lookup_many``)."""
    from annotatedvdb_tpu.serve import QueryEngine
    from annotatedvdb_tpu.serve.http import MSG_DISK_RESERVE

    store_dir, port, ctx = live["store_dir"], live["port"], live["ctx"]
    ctx.disk_guard = DiskReserveGuard(
        store_dir, reserve=1 << 60, ttl_s=0.0, log=lambda m: None
    )
    up = {"variants": [{"id": "3:70:A:G"}]}
    status, body = _request(port, "POST", "/variants/upsert", up)
    assert status == 507
    assert json.loads(body) == {"error": MSG_DISK_RESERVE}
    # reads keep serving through the degraded window
    ids = ["3:10:A:C", "3:20:A:C"]
    records = QueryEngine(ctx.manager, region_cache_size=0).lookup_many(ids)
    status, body = _request(port, "GET", "/variant/3:10:A:C")
    assert (status, body.decode()) == (200, records[0])
    assert b'"3:10:A:C"' in body
    status, body = _request(port, "POST", "/variants", {"ids": ids})
    assert (status, body.decode()) == (200, bulk_envelope(records))
    assert all(records)
    # the shed is visible in metrics
    assert "avdb_upsert_disk_shed_total 1" in \
        ctx.registry.render_prometheus()
    # space freed -> upserts resume (recovery)
    ctx.disk_guard = DiskReserveGuard(
        store_dir, reserve=1, ttl_s=0.0, log=lambda m: None
    )
    status, body = _request(port, "POST", "/variants/upsert", up)
    assert status == 200 and json.loads(body)["accepted"] == 1
    status, body = _request(port, "POST", "/variants/upsert",
                            {"variants": [{"id": "3:77:A:G"}]})
    assert status == 200 and json.loads(body)["accepted"] == 1


def test_flush_of_acked_rows_runs_under_disk_guard(live):
    """The guard sheds NEW writes only: a memtable flush of rows acked
    before the window commits to segments (it is what drains the WAL)."""
    store_dir = live["store_dir"]
    ctx = live["ctx"]
    st, _ = _request(live["port"], "POST", "/variants/upsert",
                     {"variants": [{"id": "3:90:A:G"}]})
    assert st == 200
    ctx.disk_guard = DiskReserveGuard(
        store_dir, reserve=1 << 60, ttl_s=0.0, log=lambda m: None
    )
    st, _ = _request(live["port"], "POST", "/variants/upsert",
                     {"variants": [{"id": "3:91:A:G"}]})
    assert st == 507
    result = ctx.memtable.flush(base_manager=ctx.manager.base)
    assert result["status"] == "flushed"
    assert ctx.memtable.rows == 0
    rows = json.load(open(os.path.join(store_dir, "manifest.json")))[
        "stats"]["rows"]
    assert int(rows["3"]) == 4  # 3 loaded + the acked upsert


def test_flush_retries_transient_io(live):
    """ENOSPC/EIO on a flush gets the bounded backoff-retry: one
    injected blip and the flush still lands (nothing wedges)."""
    ctx = live["ctx"]
    st, _ = _request(live["port"], "POST", "/variants/upsert",
                     {"variants": [{"id": "3:95:A:G"}]})
    assert st == 200
    assert ctx.memtable.rows == 1
    faults.reset("memtable.flush:1:eio")
    ctx._flush_memtable(ctx.manager.base)
    assert ctx.memtable.rows == 0  # retried past the blip and flushed


def test_upsert_sigkill_in_degraded_window_loses_nothing_acked(tmp_path):
    """Through the REAL serve CLI: rows acked before the reserve breach
    survive a SIGKILL DURING the degraded window (WAL replay), new
    upserts 507 inside it, and clearing the reserve restores full
    service with every acked row present."""
    store_dir = str(tmp_path / "store")
    _seed_serve_store().save(store_dir)

    def spawn(env_extra):
        import re

        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   AVDB_MEMTABLE_FLUSH_S="0", AVDB_MEMTABLE_BYTES="0")
        env.pop("AVDB_FAULT", None)
        env.pop("AVDB_STORE_DISK_RESERVE_BYTES", None)
        env.update(env_extra)
        proc = subprocess.Popen(
            [sys.executable, "-m", "annotatedvdb_tpu", "serve",
             "--storeDir", store_dir, "--port", "0", "--upserts"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for _ in range(50):
            line = proc.stdout.readline()
            if not line:
                break
            m = re.search(r"http://([\d.]+):(\d+)", line)
            if m:
                return proc, m.group(1), int(m.group(2))
        raise AssertionError("no serve address line")

    def post(host, port, vid):
        return _request(port, "POST", "/variants/upsert",
                        {"variants": [{"id": vid}]})

    # phase 1: healthy disk — ack two rows, then SIGKILL (unflushed:
    # flush triggers are disabled, so the WAL is their only durability)
    proc, host, port = spawn({})
    try:
        st, body = post(host, port, "3:40:A:G")
        assert st == 200 and json.loads(body)["accepted"] == 1
        st, body = post(host, port, "3:50:A:G")
        assert st == 200 and json.loads(body)["accepted"] == 1
    finally:
        proc.kill()
        proc.wait(timeout=30)

    # phase 2: the degraded window — reserve breached from startup.
    # WAL replay restores the acked rows; reads serve them; new writes
    # 507; a SIGKILL here loses nothing acked.
    proc, host, port = spawn({"AVDB_STORE_DISK_RESERVE_BYTES": "1000g"})
    try:
        for vid in ("3:40:A:G", "3:50:A:G"):
            st, body = _request(port, "GET", f"/variant/{vid}")
            assert st == 200, (vid, body)
        st, body = post(host, port, "3:60:A:G")
        assert st == 507
        from annotatedvdb_tpu.serve.http import MSG_DISK_RESERVE

        assert json.loads(body)["error"] == MSG_DISK_RESERVE
    finally:
        proc.kill()  # SIGKILL mid-degraded-window
        proc.wait(timeout=30)

    # phase 3: space freed — acked rows still present, upserts resume
    proc, host, port = spawn({})
    try:
        for vid in ("3:40:A:G", "3:50:A:G"):
            st, _body = _request(port, "GET", f"/variant/{vid}")
            assert st == 200
        st, _body = _request(port, "GET", "/variant/3:60:A:G")
        assert st == 404  # the 507'd write was never acknowledged
        st, body = post(host, port, "3:60:A:G")
        assert st == 200 and json.loads(body)["accepted"] == 1
    finally:
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0


# ---------------------------------------------------------------------------
# doctor status


def test_store_status_report_and_cli(tmp_path, monkeypatch):
    from annotatedvdb_tpu.store.memtable import Memtable
    from annotatedvdb_tpu.store.wal import WriteAheadLog

    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=5)
    # pending WAL records + assorted debris
    wal = WriteAheadLog(store_dir, "serve-w0", log=lambda m: None)
    mem = Memtable(width=WIDTH, store_dir=store_dir, wal=wal,
                   log=lambda m: None)
    mem.upsert(None, [{"code": 6, "pos": 42, "ref": "A", "alt": "G",
                       "ref_snp": None, "ann": None}])
    wal.close()
    open(os.path.join(store_dir, "chr6.000099.flush.tmp.npz"), "wb").close()
    open(os.path.join(store_dir, "chr6.000098.compact.tmp.npz"),
         "wb").close()
    open(os.path.join(store_dir, "serve-w1.000001.wal.tmp"), "wb").close()

    monkeypatch.setenv("AVDB_MAINTAIN_SEGMENTS_HIGH", "4")
    monkeypatch.setenv("AVDB_STORE_DISK_RESERVE_BYTES", "1000g")
    report = store_status(store_dir)
    assert report["groups"]["6"]["segments"] == 5
    assert report["read_amp"]["max"] == 5
    assert report["watermarks"]["high"] == 4
    assert report["watermarks"]["over_high"] == ["6"]
    assert report["wal"]["files"] == 1
    assert report["wal"]["records_pending_replay"] == 1
    assert report["debris"] == {"flush_tmp": 1, "compact_tmp": 1,
                                "wal_tmp": 1, "stale_tmp": 0}
    assert report["disk"]["breached"] is True  # 1000g reserve

    from annotatedvdb_tpu.cli.doctor import main as doctor_main

    rc = doctor_main(["status", "--storeDir", store_dir, "--json"])
    assert rc == 0


def test_store_status_includes_last_ledger_records(tmp_path):
    from annotatedvdb_tpu.store.compact import compact_store

    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=4)
    report = compact_store(store_dir)
    assert report["status"] == "compacted"
    status = store_status(store_dir)
    assert status["ledger"]["last_compact"] is not None
    assert status["ledger"]["last_compact"]["files_before"] == 4
    assert status["read_amp"]["max"] == 1


def test_store_status_missing_store_exits_2(tmp_path):
    from annotatedvdb_tpu.cli.doctor import main as doctor_main

    rc = doctor_main(["status", "--storeDir",
                      str(tmp_path / "nothing"), "--json"])
    assert rc == 2


def test_doctor_compact_retries_flag(tmp_path, monkeypatch):
    """`doctor compact --retries N` rides the shared retry_preempted
    policy: a pass cleanly preempted once (a racing commit between plan
    and swap) lands on the retry instead of exiting 1."""
    from annotatedvdb_tpu.cli import doctor as doctor_mod
    from annotatedvdb_tpu.store import compact as compact_mod

    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=4)
    calls = {"n": 0}
    real = compact_mod.compact_store

    def flaky(store, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            return {"status": "aborted", "reason": "test race",
                    "labels": [], "files_before": 0, "files_after": 0,
                    "bytes_before": 0, "bytes_after": 0,
                    "bytes_reclaimed": 0, "rows": 0, "rows_dropped": 0,
                    "seconds": 0.0}
        return real(store, **kw)

    monkeypatch.setattr(compact_mod, "compact_store", flaky)
    rc = doctor_mod.main(["compact", "--storeDir", store_dir,
                          "--retries", "1", "--json"])
    assert rc == 0
    assert calls["n"] == 2


# ---------------------------------------------------------------------------
# review-round regressions


def test_retry_preempted_never_retries_callers_own_cancel():
    """A pass the CALLER itself cancelled (SIGTERM, daemon stop, hot
    health) is not a preemption to retry — re-running would only delay
    the shutdown behind backoff sleeps."""
    calls = {"n": 0}

    def run():
        calls["n"] += 1
        return {"status": "aborted", "reason": "cancelled before merge"}

    report = retry_preempted(run, retries=5, base_delay=0.0,
                             cancel=lambda: True)
    assert report["status"] == "aborted"
    assert calls["n"] == 1  # no retries against our own cancel


def test_bad_disk_reserve_knob_fails_fleet_startup(tmp_path, monkeypatch):
    """A typo'd AVDB_STORE_DISK_RESERVE_BYTES must fail the fleet at
    startup (rc 1 via the cli), not be discovered inside every spawned
    worker as a rapid-death respawn loop."""
    from annotatedvdb_tpu.serve.fleet import ServeFleet

    monkeypatch.setenv("AVDB_STORE_DISK_RESERVE_BYTES", "512mb")
    with pytest.raises(ValueError,
                       match="AVDB_STORE_DISK_RESERVE_BYTES"):
        ServeFleet(str(tmp_path), port=0, workers=1)


def test_store_status_unreadable_free_space_reports_breached(
        tmp_path, monkeypatch):
    """An unreadable free-space reading reports breached, matching the
    serving guard's fail-toward-refusing-writes semantics — the health
    report must never say 'ok' while workers shed 507."""
    import annotatedvdb_tpu.store.maintenance as maintenance

    store_dir = str(tmp_path / "s")
    _fragment(store_dir, nseg=1)
    monkeypatch.setenv("AVDB_STORE_DISK_RESERVE_BYTES", "1k")

    def boom(path):
        raise OSError("statvfs failed")

    monkeypatch.setattr(maintenance, "free_disk_bytes", boom)
    report = store_status(store_dir)
    assert report["disk"]["free_bytes"] == -1
    assert report["disk"]["breached"] is True
