"""Per-layer metrics that are one ``/stats`` counter over another, both read
before and after the window.

``read`` takes the change of ``path`` over the change of ``over`` (keys from
the top of the document, as ``stats`` spells them), times ``scale``: with
``path`` ``loop.busy_s``, ``over`` ``loop.wall_s`` and ``scale`` 100 it is
the share of the window the server's event loop spent running callbacks.
Either counter missing from either document (a program that does not keep
it), or a denominator that did not move, gives nothing.
"""

from __future__ import annotations

from readers.stats import dig


def read(artefacts: dict, path: str, over: str,
         scale: float = 1.0) -> float | None:
    before = artefacts.get("stats_before") or {}
    after = artefacts.get("stats_after") or {}
    values = [dig(doc, key) for key in (path, over) for doc in (before, after)]
    if any(v is None for v in values):
        return None
    top0, top1, bottom0, bottom1 = values
    if bottom1 == bottom0:
        return None
    return scale * (top1 - top0) / (bottom1 - bottom0)
