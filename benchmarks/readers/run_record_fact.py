"""One ratio of two facts out of a load's run record (``type: "run"`` in
the store's ``ledger.jsonl``, written by ``obs/session.py``).

``read`` takes ``path`` over ``per`` — each a dotted path of keys from the
top of the timed load's record, such as ``execution.sidecar.visited`` over
``execution.sidecar.rows`` — times ``scale``.  Both are counts the program
made once per unit of work, so the ratio repeats exactly for one input.  A
record without either fact (a program from before the counter), or a zero
``per``, gives nothing.
"""

from __future__ import annotations

from readers.stats import dig


def read(artefacts: dict, path: str, per: str,
         scale: float = 1.0) -> float | None:
    record = artefacts.get("run_record")
    if not record:
        return None
    value, base = dig(record, path), dig(record, per)
    if value is None or not base:
        return None
    return scale * value / base
