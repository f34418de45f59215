"""The region cell's warm-up against a scripted server
(``pytest benchmarks/tests -q``, CPU, by hand like the rest): a server that
is still setting up may shed a panel (503) and is waited out; one that
says ready is held to 200; ready includes the overload ladder at rest."""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

from common import RunFailed  # noqa: E402
from drivers import serve_regions  # noqa: E402

PARAMS = {"warmup_requests_per_client": 2}


class Server:
    """``script``: one (status, device groups answered, indexes ready,
    brownout level) per panel, the last repeated."""

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0
        self.state = (200, 0, 0, 0)
        self.device_groups = 0

    def call(self, _panel):
        self.state = self.script[min(self.calls, len(self.script) - 1)]
        self.calls += 1
        self.device_groups += self.state[1]
        return self.state[0], b'{"error": "scripted"}', 0.0, 0.1

    def get_json(self, path):
        assert path == "/stats"
        _status, _groups, ready, level = self.state
        return {
            "region_index": {"candidates": 3, "built": ready,
                             "device": ready, "builds": 3, "uploads": 3},
            "region_panels": {"host_groups": 0,
                              "device_groups": self.device_groups},
            "residency": {"candidates": 3, "resident": ready},
            "brownout": {"level": level, "name": "scripted"},
        }


@pytest.fixture(autouse=True)
def no_pace(monkeypatch):
    monkeypatch.setattr(serve_regions, "WARM_PACE_S", 0.0)


def warm(server):
    return serve_regions.warm_up(server, server, ["panel"], PARAMS, 3, False)


def test_a_shed_panel_before_ready_is_waited_out_and_counted():
    server = Server([(200, 3, 0, 0), (200, 3, 0, 2), (503, 0, 3, 3),
                     (503, 0, 3, 3), (200, 3, 3, 1), (200, 3, 3, 0)])
    got = warm(server)
    assert got["shed_before_ready"] == 2
    assert len(got["panels_before_ready_ms"]) == 6
    assert got["requests"] == 8 and server.calls == 8


def test_ready_waits_for_the_ladder_to_rest():
    # indexes ready from the first panel on, the ladder two panels later
    server = Server([(200, 3, 3, 2), (200, 3, 3, 1), (200, 3, 3, 0)])
    assert len(warm(server)["panels_before_ready_ms"]) == 3


def test_a_shed_panel_after_ready_fails_the_run():
    server = Server([(200, 3, 3, 0), (200, 3, 3, 0), (503, 0, 3, 3)])
    with pytest.raises(RunFailed, match="after ready -> 503"):
        warm(server)


@pytest.mark.parametrize("status", [None, 500, 429])
def test_any_other_status_before_ready_fails_the_run(status):
    with pytest.raises(RunFailed, match=f"warm-up panel 2 -> {status}"):
        warm(Server([(200, 3, 0, 0), (status, 0, 0, 0)]))


def test_a_server_without_the_readiness_report_fails_at_once():
    server = Server([(200, 3, 3, 0)])
    server.get_json = lambda path: {"residency": {}}
    with pytest.raises(RunFailed, match="no region_index"):
        warm(server)
    assert server.calls == 0
