"""The two readers that read the program's own spans and waits
(``pytest benchmarks/tests -q``, CPU, by hand like the rest), on hand-made
dictionaries: what a reduction names of the device's idle time
(``xplane_named``) and what a load's run record says its threads waited
(``run_record_stalls``)."""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

from common import load_json  # noqa: E402
from readers import run_record_stalls, xplane_idle, xplane_named  # noqa: E402


def reduced(idle_gaps, window_s=40.0, busy_s=2.0):
    return {"xplane": {"window_s": window_s, "busy_s": busy_s,
                       "idle_gaps": idle_gaps}}


def test_named_share_is_one_minus_untraced_over_idle():
    # the ledger's PR 25 load line: 34.87 s untraced of 38 - 0.266 s idle
    got = xplane_named.read(reduced(
        [["host:untraced", 34.87], ["host:python:np.asarray", 1.03]],
        window_s=38.0, busy_s=0.266))
    assert got == pytest.approx(100 * (1 - 34.87 / 37.734))
    assert 7 < got < 9


def test_no_capture_gives_nothing():
    assert xplane_named.read({}) is None
    assert xplane_named.read({"xplane": None}) is None


def test_untraced_not_among_the_ten_reads_100():
    gaps = [[f"host:python:avdb.load.{s}", 3.0] for s in "abcdefghij"]
    assert xplane_named.read(reduced(gaps)) == 100.0


def test_a_device_never_idle_gives_nothing_and_excess_is_clamped():
    assert xplane_named.read(reduced([], window_s=5.0, busy_s=5.0)) is None
    # gaps are attributed on the first device, busy is averaged over all:
    # the share cannot go under 0
    assert xplane_named.read(reduced([["host:untraced", 50.0]])) == 0.0


def test_named_share_of_a_real_reduction():
    device = {"/device:TPU:0": [("op", 0.0, 1.0), ("op", 9.0, 10.0)]}
    host = [("python:avdb.load", 1.0, 7.0), ("python:avdb.load.build", 2.0, 5.0)]
    red = xplane_idle.reduce_events(device, host)
    gaps = dict(red["idle_gaps"])
    assert gaps["host:python:avdb.load.build"] == pytest.approx(3.0)
    assert gaps["host:python:avdb.load"] == pytest.approx(3.0)
    assert gaps["host:untraced"] == pytest.approx(2.0)  # 7.0 - 9.0
    assert xplane_named.read({"xplane": red}) == pytest.approx(75.0)


STALLS = {
    "ingest": {"items": 40, "producer_block_s": 1.5, "consumer_wait_s": 0.25,
               "max_depth": 2},
    "dispatch": {"items": 40, "producer_block_s": 0.0,
                 "consumer_wait_s": 2.0, "max_depth": 2},
    "store-writer": {"items": 40, "producer_block_s": 0.75,
                     "consumer_wait_s": 0.0, "max_depth": 3},
}
ALL = [[b, f] for b in STALLS for f in ("producer_block_s",
                                        "consumer_wait_s")]


def test_waits_sum_the_named_pairs_per_million_rows():
    art = {"run_record": {"queue_stalls": STALLS}, "rows_stored": 2_000_000}
    assert run_record_stalls.read(art, ALL) == pytest.approx(4.5 / 2)
    assert run_record_stalls.read(
        art, [["ingest", "producer_block_s"]]) == pytest.approx(0.75)
    # a load whose threads never waited honestly reads 0
    quiet = {b: dict(r, producer_block_s=0.0, consumer_wait_s=0.0)
             for b, r in STALLS.items()}
    assert run_record_stalls.read(
        {"run_record": {"queue_stalls": quiet}, "rows_stored": 5}, ALL) == 0.0


def test_waits_give_nothing_without_a_table_rows_or_a_named_pair():
    art = {"run_record": {"queue_stalls": STALLS}, "rows_stored": 1000}
    assert run_record_stalls.read({}, ALL) is None
    assert run_record_stalls.read({"run_record": {}, "rows_stored": 9},
                                  ALL) is None  # a serial load: no table
    assert run_record_stalls.read(dict(art, rows_stored=0), ALL) is None
    assert run_record_stalls.read(art, [["nowhere", "producer_block_s"],
                                        ["ingest", "no_such_field"]]) is None


def test_the_metric_file_names_all_three_boundaries_both_fields():
    spec = load_json(os.path.join(BENCH, "metrics",
                                  "load_wait_s_per_Mrow.json"))
    assert spec["reader"] == "run_record_stalls"
    assert sorted(map(tuple, spec["args"]["pairs"])) == sorted(
        map(tuple, ALL))
