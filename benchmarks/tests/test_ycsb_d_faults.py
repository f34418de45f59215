"""The YCSB D cell's write-path fault: ``pytest benchmarks/tests -q``
(CPU, by hand — not part of the repository's tier-1 run).

A rehearsal with the WAL append's fsync taken out underneath
(``faults_wal/sitecustomize.py``) has to read ``correct`` false through
``fsync_short``: the SIGKILL durability check cannot see a missing fsync.
"""

from __future__ import annotations

import os

import pytest

from test_harness import BENCH, run_cell


@pytest.mark.parametrize("fault", ["serve.ack_before_fsync"])
def test_an_ack_without_its_fsync_reads_not_correct(fault):
    line, stderr = run_cell("serve.ycsb-d-latest", 0, 2147483701, env={
        "PYTHONPATH": os.path.join(BENCH, "tests", "faults_wal"),
        "AVDB_BENCH_TEST_FAULT": fault})
    assert line["correct"] is False, stderr[-1500:]
    assert line["checks"]["fsync_short"]["value"] > 0
    assert line["checks"]["acked_not_durable"]["value"] == 0
