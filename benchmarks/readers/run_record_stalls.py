"""Time work waited at the load pipeline's stage boundaries, from a load's
run record (``queue_stalls``, written by ``obs/session.py``).

The overlapped loader keeps, per boundary (``ingest``, ``dispatch``,
``store-writer``), the seconds the boundary's producer sat blocked on a full
queue (``producer_block_s``: its consumer is the bottleneck) and the seconds
its consumer waited on an empty one (``consumer_wait_s``: the producer
starved it).  ``read`` sums the named ``[boundary, field]`` pairs of the
timed load and divides by the rows it stored, in millions — the same
denominator as the stage seconds of ``run_record``, so a wait reads beside
the busy time it interrupted.  It is an accumulated count: a load whose
threads never waited honestly reads 0.  A record without a ``queue_stalls``
table, or with none of the named pairs, gives nothing.
"""

from __future__ import annotations


def read(artefacts: dict, pairs: list) -> float | None:
    record = artefacts.get("run_record")
    rows = artefacts.get("rows_stored")
    if not record or not rows:
        return None
    stalls = record.get("queue_stalls")
    if not isinstance(stalls, dict):
        return None
    found = [float(stalls[boundary][field]) for boundary, field in pairs
             if field in (stalls.get(boundary) or {})]
    if not found:
        return None
    return sum(found) / (rows / 1e6)
