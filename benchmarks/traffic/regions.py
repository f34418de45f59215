"""The generator of interval traffic: target panels for ``POST /regions``
against a store of the generated rows, from the parameters of a workload
file.

Parameters (``workloads/<cell>.json`` ``parameters``):

- ``clients``: closed-loop clients, each waiting for the last byte of its
  reply before it sends again;
- ``intervals_per_request``: targets in one panel;
- ``interval_width_bp``: ``"lo-hi"``, a target's width in bases, uniform,
  both ends included (a BITS query interval is 1-based and inclusive:
  ``start..start+width-1``);
- ``limit``: the most records an envelope returns;
- ``requests_per_client``: how many distinct panels each client is given; a
  client that uses them up starts again.

Each target lies on a chromosome drawn uniformly from the store's, its
start drawn uniformly over that chromosome's stored span (first to last
generated position); targets stay in the order drawn — a panel is a list a
user wrote, not a sorted BED file.  Everything is drawn from the seed;
bodies are encoded here, in set-up.
"""

from __future__ import annotations

import json

import numpy as np


class Panel:
    __slots__ = ("method", "path", "body", "regions", "specs")

    def __init__(self, regions: list, specs: list, limit: int):
        self.method, self.path = "POST", "/regions"
        self.body = json.dumps({"regions": regions, "limit": limit}).encode()
        self.regions = regions  # "chr:start-end", in request order
        self.specs = specs      # (chromosome index, start, end) of each


def stored_spans(exp) -> list:
    """(first, last) generated position of each chromosome block."""
    chrom, pos = exp.kept["chrom"], exp.kept["pos"]
    spans = []
    for ci in range(len(exp.chromosomes)):
        block = pos[chrom == ci]
        spans.append((int(block.min()), int(block.max())))
    return spans


def build(exp, params: dict, seed: int) -> list:
    """``params['clients']`` lists of :class:`Panel`."""
    rng = np.random.default_rng([int(seed), 5])
    per = int(params["intervals_per_request"])
    lo_w, hi_w = (int(x) for x in str(params["interval_width_bp"]).split("-"))
    limit = int(params["limit"])
    spans = stored_spans(exp)
    first = np.array([s[0] for s in spans])
    last = np.array([s[1] for s in spans])
    labels = exp.chromosomes
    clients = []
    for _client in range(int(params["clients"])):
        panels = []
        for _k in range(int(params["requests_per_client"])):
            ci = rng.integers(len(labels), size=per)
            start = rng.integers(first[ci], last[ci] + 1)
            end = start + rng.integers(lo_w, hi_w + 1, size=per) - 1
            specs = list(zip(ci.tolist(), start.tolist(), end.tolist()))
            panels.append(Panel(
                [f"{labels[c]}:{s}-{e}" for c, s, e in specs], specs, limit))
        clients.append(panels)
    return clients
