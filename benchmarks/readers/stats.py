"""Per-layer metrics from the server's ``/stats`` document, read before and
after the window.

``read`` takes the change of one counter (``path``: keys from the top of
the document) over ``per`` — a count the harness made of what it sent in
the window (``artefacts[per]``) — times ``scale``.  Useful outcomes over
attempts: with ``path`` ``device_lookup.device_queries`` and ``per``
``ids_sent`` it is the share of looked-up ids that reached the device
probe.  Nothing sent, or no such counter, gives nothing.
"""

from __future__ import annotations


def dig(doc: dict, path: str):
    for key in path.split("."):
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc


def read(artefacts: dict, path: str, per: str,
         scale: float = 1.0) -> float | None:
    before = dig(artefacts.get("stats_before") or {}, path)
    after = dig(artefacts.get("stats_after") or {}, path)
    sent = artefacts.get(per)
    if before is None or after is None or not sent:
        return None
    return scale * (after - before) / sent
