"""The reader and the metric files that read the serving loop's account
(``pytest benchmarks/tests -q``, CPU, by hand like the rest):
``stats_ratio`` on hand-made ``/stats`` documents, and every metric of the
loop clock and of the three loop stages resolving to a ``BENCHMARK.json``
entry that lists its cells."""

from __future__ import annotations

import importlib
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

from common import load_json  # noqa: E402
from readers import prom, stats_ratio  # noqa: E402

BENCHMARK = load_json(os.path.join(ROOT, "BENCHMARK.json"))

LOOP_METRICS = {
    "serve.point-zipf": [
        "serve_point_read_mean_ms", "serve_point_wake_mean_ms",
        "serve_point_reply_mean_ms", "serve_point_loop_busy_pct",
        "serve_point_loop_busy_ms_per_read",
        "serve_point_loop_other_ms_per_read"],
    "serve.region-panel": [
        "serve_regions_read_mean_ms", "serve_regions_wake_mean_ms",
        "serve_regions_loop_busy_pct"],
    "serve.bulk-lookup": [
        "serve_read_mean_ms", "serve_wake_mean_ms", "serve_reply_mean_ms"],
}


def loop(busy_s, wait_s, other_s=0.0):
    return {"loop": {"busy_s": busy_s, "wait_s": wait_s,
                     "wall_s": busy_s + wait_s, "other_s": other_s}}


def test_a_ratio_is_change_over_change_times_scale():
    artefacts = {"stats_before": loop(10.0, 30.0), "stats_after": loop(40.0, 40.0)}
    got = stats_ratio.read(artefacts, path="loop.busy_s", over="loop.wall_s",
                           scale=100.0)
    assert got == pytest.approx(75.0)  # 30 s busy of the window's 40
    assert stats_ratio.read(artefacts, path="loop.wait_s",
                            over="loop.busy_s") == pytest.approx(1 / 3)


@pytest.mark.parametrize("before, after", [
    ({}, {}),                                     # no document at all
    ({"rows": 1}, {"rows": 1}),                   # the parent: no counter
    (loop(1.0, 1.0), {"rows": 1}),                # gone after
    ({"loop": {"busy_s": 1.0}}, {"loop": {"busy_s": 2.0}}),  # no denominator
])
def test_no_counter_gives_nothing(before, after):
    assert stats_ratio.read(
        {"stats_before": before, "stats_after": after},
        path="loop.busy_s", over="loop.wall_s") is None
    assert stats_ratio.read({}, path="loop.busy_s", over="loop.wall_s") is None


def test_no_change_of_the_denominator_gives_nothing():
    same = loop(3.0, 5.0)
    assert stats_ratio.read({"stats_before": same, "stats_after": dict(same)},
                            path="loop.busy_s", over="loop.wall_s") is None


@pytest.mark.parametrize("cell, metric", [
    (cell, metric) for cell, names in LOOP_METRICS.items() for metric in names])
def test_a_loop_metric_resolves_to_an_entry_with_its_cells(cell, metric):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    spec = load_json(os.path.join(BENCH, "metrics", f"{metric}.json"))
    for key, value in entry.items():
        assert spec[key] == value, key
    assert entry["workloads"] == [cell] and entry["layer"] == "serving"
    moved = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == entry["moves"])
    assert cell in moved["workloads"]
    reader = importlib.import_module(f"readers.{spec['reader']}")
    # on the parent's artefacts (no loop block, no such stage) the reader
    # finds nothing and does not raise: the line leaves the metric out
    parent = {"stats_before": {"rows": 1}, "stats_after": {"rows": 1},
              "prom_before": {}, "prom_after": {}, "requests_sent": 10,
              "ids_sent": 10}
    assert reader.read(parent, **spec["args"]) is None


def test_the_readers_find_the_change_they_are_pointed_at():
    """Every loop metric of the point cell on one hand-made window: 1,000
    reads, the loop busy 0.3 s of 0.4, 0.1 s of it in no part."""
    text0 = ('avdb_stage_seconds_sum{stage="wake"} 1.0\n'
             'avdb_stage_seconds_count{stage="wake"} 500\n')
    text1 = ('avdb_stage_seconds_sum{stage="wake"} 3.5\n'
             'avdb_stage_seconds_count{stage="wake"} 1500\n'
             'avdb_stage_seconds_sum{stage="read"} 0.1\n'
             'avdb_stage_seconds_count{stage="read"} 1000\n')
    artefacts = {"stats_before": loop(1.0, 1.0, 0.5),
                 "stats_after": loop(1.3, 1.1, 0.6),
                 "prom_before": prom.parse(text0),
                 "prom_after": prom.parse(text1),
                 "requests_sent": 1000, "ids_sent": 1000}
    got = {}
    for metric in LOOP_METRICS["serve.point-zipf"]:
        spec = load_json(os.path.join(BENCH, "metrics", f"{metric}.json"))
        reader = importlib.import_module(f"readers.{spec['reader']}")
        got[metric] = reader.read(artefacts, **spec["args"])
    assert got["serve_point_wake_mean_ms"] == pytest.approx(2.5)
    assert got["serve_point_read_mean_ms"] == pytest.approx(0.1)
    assert got["serve_point_reply_mean_ms"] is None  # nothing observed
    assert got["serve_point_loop_busy_pct"] == pytest.approx(75.0)
    assert got["serve_point_loop_busy_ms_per_read"] == pytest.approx(0.3)
    assert got["serve_point_loop_other_ms_per_read"] == pytest.approx(0.1)
