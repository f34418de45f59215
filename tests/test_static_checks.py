"""Tier-1 static-analysis gate: the shipped tree stays clean under
``avdb_check`` (and the chained check script), and the analyzer stays fast
enough to run on every PR.

This is the enforcement half of the suite — the analyzer's own behavior
is pinned fixture-by-fixture in ``tests/test_avdb_check.py``.  A finding
here means new code violated a project invariant (trace-safety,
lock-discipline, registry-drift, env-drift, CLI-contract, hygiene,
async-safety, cross-front-end parity, device/host twin contract): fix
it or suppress with ``# avdb: noqa[CODE] -- reason`` per README "Static
analysis & code health".  The chained script additionally runs the serve
smoke under ``AVDB_LOCK_TRACE=1`` — the dynamic lock-order/deadlock
detector — and fails on any acquisition-order cycle.
"""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN = ["annotatedvdb_tpu", "tools", "tests", "bench.py", "chip_smoke.py"]


def test_tree_is_clean_and_fast():
    """Acceptance gate: zero findings over the whole tree, bounded wall.

    The budget is a guardrail against the analyzer going quadratic, not
    a latency SLO: it was 10s when the tree held 136 files, and at 182
    files on this 2-3x-swinging container a clean run measures 9-11s —
    20s keeps the quadratic-blowup alarm while surviving a slow
    scheduling window."""
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "avdb_check.py"),
         *SCAN],
        capture_output=True, text=True, cwd=REPO,
    )
    wall = time.monotonic() - t0
    assert p.returncode == 0, (
        "avdb_check found violations (fix or noqa-with-reason; "
        "see README 'Static analysis & code health'):\n" + p.stdout
    )
    assert wall < 20.0, f"analyzer took {wall:.1f}s (budget 20s)"


def test_run_checks_script_clean():
    """The chained entry point (avdb_check + ruff-if-present + bench
    schema + lock-order-traced serve smoke + chaos smoke) gates every
    future PR from one script."""
    p = subprocess.run(
        ["bash", os.path.join(REPO, "tools", "run_checks.sh")],
        capture_output=True, text=True, cwd=REPO,
    )
    assert p.returncode == 0, p.stdout + "\n" + p.stderr


def test_fault_point_registry_matches_call_sites():
    """Every faults.POINTS entry is reachable: the analyzer's AVDB301/302
    guard the call sites and the matrix; this pins the registry itself
    against the live fire() sites (a deleted call site should delete its
    registry entry too)."""
    import re

    from annotatedvdb_tpu.utils import faults

    fired = set()
    pkg = os.path.join(REPO, "annotatedvdb_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn), encoding="utf-8") as f:
                # only real point names (docstrings discussing the
                # `faults.fire("<point>")` pattern don't count)
                fired.update(
                    re.findall(r'faults\.fire\(\s*"([a-z][a-z0-9_.]*)"',
                               f.read())
                )
    assert fired == set(faults.POINTS), (
        f"faults.POINTS drift: registered-but-never-fired "
        f"{sorted(set(faults.POINTS) - fired)}, "
        f"fired-but-unregistered {sorted(fired - set(faults.POINTS))}"
    )
