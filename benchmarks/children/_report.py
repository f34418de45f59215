"""What both children report to the driver, and how: the device as JAX
gives it, and small JSON files written atomically."""

from __future__ import annotations

import json
import os


def write_json(path: str, doc: dict) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def device_report() -> dict:
    """The device as JAX reports it, and the peak on the fullest chip."""
    import jax

    devices = jax.devices()
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def start_trace(trace_dir: str, python_tracer_level: int = 0) -> None:
    """A ``jax.profiler`` capture with the Python tracer off by default: a
    Python-heavy window would be millions of events."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = python_tracer_level
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
