"""Per-layer metrics from a load's run record (``type: "run"`` in the
store's ``ledger.jsonl``, written by ``obs/session.py``).

``read`` sums the busy seconds of the named ``stages`` of the timed load
and divides by the rows it stored, in millions.  Stage seconds are
per-thread busy time on the host's clock; threads overlap, so the layers'
numbers do not sum to the wall.  A record without any of the named stages
gives nothing.
"""

from __future__ import annotations

import json
import os


def last_run_record(store_dir: str) -> dict | None:
    """The newest completed ``type: "run"`` record of a store's ledger."""
    record = None
    try:
        with open(os.path.join(store_dir, "ledger.jsonl")) as f:
            for line in f:
                entry = json.loads(line)
                if entry.get("type") == "run" \
                        and entry.get("status") == "completed":
                    record = entry
    except (OSError, ValueError):
        return None
    return record


def stage_seconds(record: dict) -> dict:
    """{stage: busy seconds} of a run record."""
    return {name: float(rec.get("seconds", 0.0))
            for name, rec in (record.get("stages") or {}).items()}


def read(artefacts: dict, stages: list) -> float | None:
    record = artefacts.get("run_record")
    rows = artefacts.get("rows_stored")
    if not record or not rows:
        return None
    seconds = stage_seconds(record)
    found = [seconds[s] for s in stages if s in seconds]
    if not found:
        return None
    return sum(found) / (rows / 1e6)
