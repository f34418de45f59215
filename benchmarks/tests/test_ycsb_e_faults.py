"""The YCSB E cell: ``pytest benchmarks/tests -q`` (CPU, by hand — not part
of the repository's tier-1 run).

A rehearsal of ``serve.ycsb-e-scan`` has to read ``correct``; and the plain
reference (``reference/ycsb_e_check.py``) — the yardstick here, not the
program — has to find each fault planted in the replies it is handed: an
acknowledged insert dropped, a scan one row short, rows out of order, a
stale generation.
"""

from __future__ import annotations

import json
import sys

import numpy as np
import pytest

from test_harness import BENCH, run_cell

if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import ycsb_d_check, ycsb_e_check  # noqa: E402
from traffic import inserts as traffic_inserts  # noqa: E402
from traffic.vcf import Expected, synth_vcf_rows  # noqa: E402

CHROMOSOMES = ("1", "2", "22")
WIDTH = 49


def test_a_rehearsal_reads_correct():
    line, stderr = run_cell("serve.ycsb-e-scan", 0, 2147483713)
    assert line["correct"] is True, stderr[-1500:]
    assert line["failed"] == 0
    for name in ("scans_wrong", "acked_not_in_scan", "request_index_builds",
                 "acked_not_durable", "fsync_short"):
        assert line["checks"][name]["value"] == 0


@pytest.fixture(scope="module")
def world():
    """A small store, 300 inserts, and 40 scans whose windows hold some."""
    exp = Expected(synth_vcf_rows(6000, 7, CHROMOSOMES), CHROMOSOMES)
    new = traffic_inserts.new_records(exp, 300, 7)
    model = ycsb_e_check.ScanModel(exp, WIDTH, new)
    rng = np.random.default_rng(7)
    specs = []
    for _ in range(40):
        row = int(rng.integers(exp.n_rows))
        ci, pos = int(exp.kept["chrom"][row]), int(exp.kept["pos"][row])
        specs.append(((ci, pos, pos + 2999), int(rng.integers(20, 60))))
    return exp, new, model, specs


def _scans(model, specs, shown, must, may, alter=None):
    out = []
    for spec, limit in specs:
        env = model.envelope(spec, limit, shown, 3)
        if alter is not None:
            alter(env)
        out.append((spec, limit, must, may, 200, json.dumps(env).encode()))
    return out


def test_the_reference_passes_what_the_model_says(world):
    _exp, new, model, specs = world
    acked = np.ones(len(new["pos"]), bool)
    got = ycsb_e_check.compare_scans(model, _scans(model, specs, acked,
                                                   acked, acked))
    assert got["scans_wrong"] == got["acked_not_in_scan"] == 0
    assert got["records_compared"] > 0
    # an insert in flight may be shown or left out
    half = acked.copy()
    half[::2] = False
    none = np.zeros_like(acked)
    got = ycsb_e_check.compare_scans(model, _scans(model, specs, half,
                                                   none, acked))
    assert got["scans_wrong"] == 0


@pytest.mark.parametrize("fault", [
    "acknowledged_insert_dropped", "one_row_short", "rows_out_of_order",
    "stale_generation"])
def test_the_reference_finds_a_planted_fault(world, fault):
    exp, new, model, specs = world
    acked = np.ones(len(new["pos"]), bool)
    shown = acked.copy()
    alter = None
    if fault in ("acknowledged_insert_dropped", "stale_generation"):
        # one acknowledged insert, or the newer half of them, left out
        dropped = [j for j in range(len(new["pos"]))
                   if any(new["chrom"][j] == ci and start <= new["pos"][j]
                          <= end for (ci, start, end), _limit in specs)]
        assert dropped, "no scan window holds an insert"
        if fault == "acknowledged_insert_dropped":
            shown[dropped[0]] = False
        else:
            shown[len(acked) // 2:] = False
    elif fault == "one_row_short":
        def alter(env):
            env["variants"].pop()
            env["returned"] -= 1
    else:
        def alter(env):
            rows = env["variants"]
            rows[0], rows[-1] = rows[-1], rows[0]
    got = ycsb_e_check.compare_scans(
        model, _scans(model, specs, shown, acked, acked, alter))
    assert got["scans_wrong"] > 0, got
    if fault in ("acknowledged_insert_dropped", "stale_generation"):
        assert got["acked_not_in_scan"] > 0
        # and the read-back misses it too
        dmodel = ycsb_d_check.Model(exp, None, new)
        for j in range(len(acked)):
            dmodel.insert(j)
        replies = [([j for j in dmodel.acked], 200, json.dumps({
            "results": [ycsb_d_check.insert_record(CHROMOSOMES, new, j)
                        if shown[j] else None for j in dmodel.acked]
        }).encode())]
        assert ycsb_d_check.compare_read_back(
            dmodel, replies)["acked_not_read_back"] > 0

