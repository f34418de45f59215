"""Multi-process serve fleet: N workers on one port over one readonly
store generation, in both port-sharing modes (SO_REUSEPORT and the
parent accept-handoff fallback), with graceful SIGTERM drain.  The
dead-worker restart case lives in tests/test_fault_matrix.py (fault
point ``serve.worker``)."""

from __future__ import annotations

import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from test_serve import _build_store, _vid


@pytest.fixture(scope="module")
def fleet_store(tmp_path_factory):
    store_dir = str(tmp_path_factory.mktemp("fleet_store"))
    truth = _build_store(store_dir)
    return store_dir, truth


def _spawn_fleet(store_dir: str, workers: int = 2, extra=()):
    proc = subprocess.Popen(
        [sys.executable, "-m", "annotatedvdb_tpu", "serve",
         "--storeDir", store_dir, "--port", "0",
         "--workers", str(workers), *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    line = proc.stdout.readline()
    m = re.search(r"http://([\d.]+):(\d+)", line)
    assert m, f"no fleet address line: {line!r}"
    return proc, m.group(1), int(m.group(2))


def _get(host: str, port: int, path: str, timeout: float = 5.0):
    with urllib.request.urlopen(
        f"http://{host}:{port}{path}", timeout=timeout
    ) as r:
        return r.status, r.read().decode()


def _wait_healthy(host: str, port: int, deadline_s: float = 90.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            status, _ = _get(host, port, "/healthz")
            if status == 200:
                return
        except OSError:
            pass
        time.sleep(0.25)
    raise AssertionError("fleet never became healthy")


@pytest.mark.parametrize("extra,label", [
    ((), "reuseport-or-default"),
    (("--_forceHandoff",), "parent-accept-handoff"),
])
def test_fleet_serves_and_drains(fleet_store, extra, label):
    store_dir, truth = fleet_store
    proc, host, port = _spawn_fleet(store_dir, workers=2, extra=extra)
    try:
        _wait_healthy(host, port)
        # all three query kinds answer through the shared port
        status, body = _get(host, port, f"/variant/{_vid(truth[0])}")
        assert status == 200
        assert json.loads(body)["position"] == truth[0]["pos"]
        status, body = _get(host, port, "/region/8:1-10000?limit=3")
        assert status == 200 and json.loads(body)["returned"] == 3
        ok = sum(
            1 for r in truth[:20]
            if _get(host, port, f"/variant/{_vid(r)}")[0] == 200
        )
        assert ok == 20, f"{label}: {ok}/20 served"
    finally:
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    assert rc == 0, proc.stdout.read()[-2000:]


def test_fleet_reuseport_detection_runs():
    from annotatedvdb_tpu.serve.fleet import reuseport_available

    assert isinstance(reuseport_available(), bool)


def test_bad_workers_env_exits_cleanly(tmp_path, capsys, monkeypatch):
    """A malformed AVDB_SERVE_WORKERS must exit ``serve: cannot start``
    rc=1 like every other knob, not an unhandled traceback."""
    from annotatedvdb_tpu.cli.serve import main

    monkeypatch.setenv("AVDB_SERVE_WORKERS", "two")
    rc = main(["--storeDir", str(tmp_path / "missing")])
    assert rc == 1
    assert "bad AVDB_SERVE_WORKERS" in capsys.readouterr().err


def test_fleet_gives_up_on_instant_death_workers(fleet_store, monkeypatch):
    """A worker that can never start (bad inherited env knob) must end
    the fleet with rc=1 after MAX_RAPID_DEATHS consecutive rapid deaths,
    not respawn forever."""
    from annotatedvdb_tpu.serve.fleet import ServeFleet

    store_dir, _truth = fleet_store
    monkeypatch.setenv("AVDB_SERVE_CLIENT_RATE", "abc")
    lines: list[str] = []
    fleet = ServeFleet(store_dir, workers=1, restart_backoff_s=0.01,
                       drain_s=2.0, log=lines.append)
    fleet.MAX_RAPID_DEATHS = 2
    rc = fleet.run()
    assert rc == 1
    assert any("giving up" in ln for ln in lines), lines


def test_worker_without_a_device_exits_with_one_line(tmp_path, capsys,
                                                     monkeypatch):
    """A serving process that JAX can give no device (on a chip: a second
    worker, the first holds it) says so in one line and exits with the
    code the supervisor refuses on — no traceback, no lazy failure at the
    first request."""
    from annotatedvdb_tpu.cli import serve
    from annotatedvdb_tpu.serve.fleet import NO_DEVICE_RC
    from annotatedvdb_tpu.utils import runtime

    def held(*a, **kw):
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED: "
                           "libtpu lockfile\nsecond line")

    monkeypatch.setattr(runtime, "device_summary", held)
    rc = serve.main(["--storeDir", str(tmp_path / "unused")])
    err = capsys.readouterr().err
    assert rc == NO_DEVICE_RC
    assert err.count("\n") == 1
    assert "cannot start: JAX found no usable device" in err
    assert "second line" not in err


def test_fleet_refuses_when_a_worker_finds_no_device(fleet_store):
    """What `serve --workers 2` met on one chip was five respawns of the
    second worker and then exit 1; now the first such death ends the
    fleet at once, with the reason."""
    from annotatedvdb_tpu.serve.fleet import NO_DEVICE_RC, ServeFleet

    store_dir, _truth = fleet_store
    lines: list[str] = []
    fleet = ServeFleet(store_dir, workers=2, restart_backoff_s=0.01,
                       drain_s=2.0, log=lines.append)
    fleet._worker_cmd = lambda index: [
        sys.executable, "-c", f"raise SystemExit({NO_DEVICE_RC})"
    ]
    t0 = time.monotonic()
    assert fleet.run() == 1
    assert time.monotonic() - t0 < 20
    assert any("refusing to start" in ln for ln in lines), lines
    assert not any("restart #" in ln for ln in lines), lines


def test_drain_survives_a_worker_that_outlives_sigkill(fleet_store):
    """Met on a v5e: a worker SIGKILLed inside the accelerator driver's
    initialization was not reaped within the wait, and the supervisor died
    of TimeoutExpired mid-drain.  The drain reports it and returns."""
    from annotatedvdb_tpu.serve.fleet import ServeFleet

    class Unreapable:
        def poll(self):
            return None

        def send_signal(self, _sig):
            pass

        def kill(self):
            pass

        def wait(self, timeout=None):
            raise subprocess.TimeoutExpired("worker", timeout)

    store_dir, _truth = fleet_store
    lines: list[str] = []
    fleet = ServeFleet(store_dir, workers=1, drain_s=0.05, log=lines.append)
    fleet.KILL_WAIT_S = 0.05
    fleet._procs[0] = Unreapable()
    try:
        assert fleet._drain() == 1
    finally:  # what run()'s own finally would release
        fleet._reserve.close()
        fleet._hb_mm.close()
        os.unlink(fleet._hb_path)
        shutil.rmtree(fleet._telemetry_dir, ignore_errors=True)
    assert any("not waiting for it" in ln for ln in lines), lines


def test_every_forwarded_knob_is_an_option_a_worker_parses(monkeypatch):
    """The supervisor forwards knob flags to every worker verbatim: one
    the worker's parser does not know is a usage error in every worker,
    which is a respawn loop.  With every knob set, the forwarded argv
    parses, carries the values, and names no front end (there is one)."""
    from annotatedvdb_tpu.cli.serve import _build_parser, _knob_args

    monkeypatch.delenv("AVDB_SERVE_HBM_BUDGET", raising=False)
    monkeypatch.delenv("AVDB_SERVE_UPSERTS", raising=False)
    parser = _build_parser()
    args = parser.parse_args([
        "--storeDir", "x", "--upserts", "--maxBatch", "64",
        "--batchWaitMs", "1.5", "--maxQueue", "99", "--regionCache", "7",
        "--clientRate", "5", "--streamThreshold", "12",
        "--snapshotTtlMs", "40", "--hbmBudget", "1m",
    ])
    knobs = _knob_args(args, workers=2)
    assert "--frontend" not in knobs
    worker = parser.parse_args(
        ["--storeDir", "x", "--_workerIndex", "0"] + knobs)
    assert worker.upserts is True
    assert (worker.maxBatch, worker.batchWaitMs, worker.maxQueue,
            worker.regionCache, worker.clientRate, worker.streamThreshold,
            worker.snapshotTtlMs) == (64, 1.5, 99, 7, 5.0, 12, 40.0)
    assert worker.hbmBudget == str((1 << 20) // 2)
    # with nothing set, nothing is forwarded
    assert _knob_args(parser.parse_args(["--storeDir", "x"]), 2) == []


def test_fleet_splits_hbm_budget_across_workers(monkeypatch):
    """The HBM budget caps ONE shared device: each worker must get an
    equal share, never the full budget (flag and env var alike)."""
    from annotatedvdb_tpu.cli.serve import _build_parser, _knob_args

    monkeypatch.delenv("AVDB_SERVE_HBM_BUDGET", raising=False)
    args = _build_parser().parse_args(
        ["--storeDir", "x", "--hbmBudget", "1g"]
    )
    knobs = _knob_args(args, workers=4)
    assert knobs[knobs.index("--hbmBudget") + 1] == str((1 << 30) // 4)
    # the inherited env var would re-apply the FULL budget in every
    # worker: the explicit (divided) flag must always be forwarded
    monkeypatch.setenv("AVDB_SERVE_HBM_BUDGET", "512k")
    args = _build_parser().parse_args(["--storeDir", "x"])
    knobs = _knob_args(args, workers=2)
    assert knobs[knobs.index("--hbmBudget") + 1] == str((512 << 10) // 2)
    # unmanaged stays unmanaged
    monkeypatch.delenv("AVDB_SERVE_HBM_BUDGET")
    assert "--hbmBudget" not in _knob_args(args, workers=2)
    # an explicit 0 is the managed degenerate case (nothing resident),
    # NOT unmanaged: it must reach the workers
    args = _build_parser().parse_args(
        ["--storeDir", "x", "--hbmBudget", "0"]
    )
    knobs = _knob_args(args, workers=2)
    assert knobs[knobs.index("--hbmBudget") + 1] == "0"
