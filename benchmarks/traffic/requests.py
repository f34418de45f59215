"""The one generator of read traffic: identity lookups against a store of
the generated rows, from the parameters of a workload file.

Parameters (``workloads/<cell>.json`` ``parameters``):

- ``clients``: closed-loop clients, each waiting for its reply before it
  sends again (callers of a pipeline);
- ``ids_per_request``: ids in one request — above 1 a ``POST /variants``
  bulk lookup, 1 a ``GET /variant/<id>`` point read;
- ``absent_per_request``: how many of them were never generated (a kept
  row's alleles past the last position of its chromosome block);
- ``key_distribution``: ``"uniform"`` — the present ids of a request drawn
  without replacement, uniformly, from the stored rows; or
  ``{"zipf": theta}`` — drawn with replacement, rank ``r`` of a seeded
  permutation of the rows with probability proportional to ``r**-theta``;
- ``requests_per_client``: how many distinct requests each client is given;
  a client that uses them up starts again (every seed gets the same number
  of requests of the same sizes; only the ids differ).

Everything is drawn from the seed; bodies are encoded here, in set-up.
"""

from __future__ import annotations

import json

import numpy as np


class Request:
    __slots__ = ("method", "path", "body", "ids", "rows")

    def __init__(self, method, path, body, ids, rows):
        self.method, self.path, self.body = method, path, body
        self.ids = ids    # the ids named, in request order
        self.rows = rows  # kept-row index of each id, -1 = never generated


def _zipf_sampler(n: int, theta: float, rng):
    weights = np.arange(1, n + 1, dtype=np.float64) ** -theta
    cdf = np.cumsum(weights / weights.sum())
    ranks = rng.permutation(n)  # which row holds each popularity rank
    return lambda size: ranks[np.searchsorted(cdf, rng.random(size))]


def build(exp, params: dict, seed: int) -> list:
    """``params['clients']`` lists of :class:`Request`."""
    rng = np.random.default_rng([int(seed), 2])
    n_kept = exp.n_rows
    per = int(params["ids_per_request"])
    n_absent = int(params.get("absent_per_request", 0))
    n_present = per - n_absent
    dist = params.get("key_distribution", "uniform")
    if dist == "uniform":
        def draw(size):
            return rng.choice(n_kept, size=size, replace=False)
    else:
        draw = _zipf_sampler(n_kept, float(dist["zipf"]), rng)
    last = [exp.last_pos(ci) for ci in range(len(exp.chromosomes))]
    kept = exp.kept
    clients = []
    for _client in range(int(params["clients"])):
        requests = []
        for _k in range(int(params["requests_per_client"])):
            picks = draw(n_present)
            ids = exp.idents(picks).tolist()
            rows = picks.tolist()
            for j, i in enumerate(rng.choice(n_kept, size=n_absent,
                                             replace=False).tolist()):
                ci = int(kept["chrom"][i])
                ids.append(f"{exp.chromosomes[ci]}:{last[ci] + 1 + j}:"
                           f"{kept['ref'][i].decode()}:"
                           f"{kept['alt'][i].decode()}")
                rows.append(-1)
            order = rng.permutation(per)
            ids = [ids[j] for j in order]
            rows = [rows[j] for j in order]
            if per == 1:
                requests.append(Request("GET", f"/variant/{ids[0]}", None,
                                        ids, rows))
            else:
                requests.append(Request(
                    "POST", "/variants",
                    json.dumps({"ids": ids}).encode(), ids, rows))
        clients.append(requests)
    return clients
