"""Per-layer metrics from the server's own ``/metrics`` (Prometheus text),
scraped before and after the window.

``read`` takes the change of a histogram's ``_sum`` over the change of its
``_count`` — the mean of what the server observed inside the window —
times ``scale`` (1000 for seconds -> ms).  No observation in the window
gives nothing.
"""

from __future__ import annotations


def parse(text: str) -> dict:
    """{series (name + label text): value}."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            try:
                out[series] = float(value)
            except ValueError:
                continue
    return out


def read(artefacts: dict, histogram: str, labels: str = "",
         scale: float = 1.0) -> float | None:
    before, after = artefacts.get("prom_before"), artefacts.get("prom_after")
    if before is None or after is None:
        return None

    def delta(suffix):
        key = f"{histogram}_{suffix}{labels}"
        if key not in after:
            return None
        return after[key] - before.get(key, 0.0)

    total, count = delta("sum"), delta("count")
    if total is None or not count:
        return None
    return scale * total / count
