"""``readers/xplane_named_all``: every idle gap named, by hand-made events."""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from readers import xplane_idle, xplane_named_all  # noqa: E402


def test_every_gap_is_named_not_the_longest():
    # operations at 1.0-2.0, 3.0-3.5, 4.0-4.4 in the window 0.5-5.0: gaps
    # 0.5-1.0, 2.0-3.0, 3.5-4.0, 4.4-5.0 = 2.6 s idle
    device = [(1.0, 2.0), (3.0, 3.5), (4.0, 4.4)]
    host = [("outer", 2.0, 3.0), ("inner", 2.2, 2.7), ("late", 3.6, 4.7),
            ("gone", 0.0, 0.4)]
    out = xplane_named_all.name_all(device, host, (0.5, 5.0))
    assert out["gaps"] == 4 and out["idle_s"] == pytest.approx(2.6)
    named = dict(out["idle_gaps"])
    assert named["host:inner"] == pytest.approx(0.5)
    assert named["host:outer"] == pytest.approx(0.5)
    assert named["host:late"] == pytest.approx(0.4 + 0.3)
    # 0.5-1.0, 3.5-3.6 and 4.7-5.0 lie under no span
    assert out["untraced_s"] == pytest.approx(0.5 + 0.1 + 0.3)
    assert xplane_named_all.read({"xplane_all_gaps": out}) \
        == pytest.approx(100 * (1 - 0.9 / 2.6))
    assert xplane_named_all.read({}) is None


def test_it_agrees_with_the_standard_reduction_where_that_names_every_gap():
    device = [(float(k), k + 0.25) for k in range(1, 150)]
    host = [(f"span{k % 7}", k + 0.3, k + 0.9) for k in range(1, 150)]
    window = (0.0, 151.0)
    standard = xplane_idle.reduce_events(
        {"/device:TPU:0": [("op", s, e) for s, e in device]}, host, window,
        top=20)
    out = xplane_named_all.name_all(device, host, window, top=20)
    assert out["gaps"] == 150
    assert out["idle_s"] == pytest.approx(
        standard["window_s"] - standard["busy_s"])
    ours, theirs = dict(out["idle_gaps"]), dict(standard["idle_gaps"])
    assert ours.keys() == theirs.keys()
    for name, seconds in theirs.items():
        assert ours[name] == pytest.approx(seconds)
