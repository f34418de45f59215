"""From a ``jax.profiler`` capture to the device's busy and idle time.

:func:`reduce_events` is the arithmetic, on plain tuples so that a test can
check it by hand: busy is the union of the intervals in which an operation
ran on the device, the window is the traced span, idle share is
1 - busy / window.  :func:`reduce_trace` reads the capture
(``<dir>/plugins/profile/<time>/*.xplane.pb``) with
``jax.profiler.ProfileData`` and feeds it.  A capture with no device plane
(a CPU rehearsal) gives nothing: a reader never returns 0 for a device it
did not see.

Which planes and lines count (looked at by hand on a v5e capture, PR 25):
device planes are named ``/device:TPU:<n>``; the line ``XLA Ops`` holds one
event per executed operation, ``XLA Modules`` one per program run.  Busy
time is taken from ``XLA Ops``, falling back to ``XLA Modules``; the other
lines (``Async XLA Ops``, ``Steps``, ...) would double-count.  ``device_ops``
in the breakdown sums by program (``XLA Modules``, the jitted function's
name): single operations carry fusion numbers that no refactor keeps.  Host
spans come from the ``/host:CPU`` plane (XLA's own TraceMe spans; the Python
tracer is off), those of a millisecond or more.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OP_LINES = ("XLA Ops", "XLA Modules")


def union_seconds(intervals: list) -> float:
    """Total length of the union of (start, end) intervals."""
    busy, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                busy += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        busy += cur_end - cur_start
    return busy


def idle_gaps(intervals: list, window: tuple) -> list:
    """The gaps of the union inside ``window`` as (start, end), longest
    first."""
    gaps, at = [], window[0]
    for start, end in sorted(intervals):
        if start > at:
            gaps.append((at, min(start, window[1])))
        at = max(at, end)
    if at < window[1]:
        gaps.append((at, window[1]))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def attribute_gap(gap: tuple, host_events: list) -> dict:
    """What the host was doing in an idle gap: {name: seconds}.  Host spans
    nest, so each moment of the gap goes to the shortest span that covers
    it, and what no span covers to ``host:untraced``."""
    inside = [(name, max(start, gap[0]), min(end, gap[1]), end - start)
              for name, start, end in host_events
              if start < gap[1] and end > gap[0]]
    cuts = sorted({gap[0], gap[1], *(e[1] for e in inside),
                   *(e[2] for e in inside)})
    out: dict = {}
    for lo, hi in zip(cuts, cuts[1:]):
        covering = [e for e in inside if e[1] <= lo and e[2] >= hi]
        name = (f"host:{min(covering, key=lambda e: e[3])[0]}"
                if covering else "host:untraced")
        out[name] = out.get(name, 0.0) + (hi - lo)
    return out


def reduce_events(device_events: dict, host_events: list,
                  window: tuple | None = None, top: int = 10,
                  named_events: list | None = None) -> dict | None:
    """``device_events``: {device: [(name, start_s, end_s), ...]};
    ``host_events``: [(name, start_s, end_s), ...]; ``window``: the traced
    span (default: first start to last end of anything seen);
    ``named_events``: what ``device_ops`` sums by name when it is not the
    operations themselves (a trace's program runs).  Busy seconds are
    averaged over the devices."""
    if not any(device_events.values()):
        return None
    every = [e for events in device_events.values() for e in events]
    if window is None:
        seen = every + list(host_events)
        window = (min(e[1] for e in seen), max(e[2] for e in seen))
    window_s = window[1] - window[0]
    if window_s <= 0:
        return None
    busy = [union_seconds([(s, e) for _n, s, e in events])
            for events in device_events.values()]
    busy_s = sum(busy) / len(busy)
    by_op: dict = {}
    for name, start, end in (named_events or every):
        by_op[name] = by_op.get(name, 0.0) + (end - start)
    first = next(iter(device_events.values()))
    gaps = idle_gaps([(s, e) for _n, s, e in first], window)
    named: dict = {}
    host_events = sorted(host_events, key=lambda e: e[1])
    for gap in gaps[:200]:  # the longest; the rest are between back-to-back ops
        for key, seconds in attribute_gap(gap, host_events).items():
            named[key] = named.get(key, 0.0) + seconds
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "device_ops": sorted(([n, s] for n, s in by_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s] for n, s in named.items()),
                            key=lambda x: -x[1])[:top],
        "device_events": len(every),
    }


def find_capture(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def keep_capture(trace_dir: str, log_dir: str,
                 limit: int = 32 << 20) -> None:
    """Leave the newest capture beside the children's logs (one file,
    overwritten by the next traced run) unless it is large."""
    import shutil

    path = find_capture(trace_dir)
    if path is not None and os.path.getsize(path) <= limit:
        shutil.copyfile(path, os.path.join(log_dir, "last.xplane.pb"))


def reduce_trace(trace_dir: str, window_s: float | None = None) -> dict | None:
    """The reduction of the capture under ``trace_dir``.  ``window_s``,
    when given, is the traced span by the clock of whoever started and
    stopped the trace; the window then ends at the capture's last event
    and starts that long before."""
    path = find_capture(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events: dict = {}
    host_events: list = []
    programs: list = []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        lines = {line.name: line for line in plane.lines}
        if on_device:
            line = next((lines[n] for n in OP_LINES if n in lines
                         and any(True for _ in lines[n].events)), None)
            if line is not None:
                device_events[plane.name] = [
                    (e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                    for e in line.events
                ]
            if "XLA Modules" in lines:  # program runs: names that last
                programs += [
                    (e.name.split("(")[0], e.start_ns / 1e9,
                     (e.start_ns + e.duration_ns) / 1e9)
                    for e in lines["XLA Modules"].events
                ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns >= 1_000_000:  # >= 1 ms names a gap
                        host_events.append((
                            f"{line.name.split('/')[0]}:{e.name}"[:80],
                            e.start_ns / 1e9,
                            (e.start_ns + e.duration_ns) / 1e9,
                        ))
    if not device_events:
        return None
    window = None
    if window_s:
        seen = [e for ev in device_events.values() for e in ev] + host_events
        last = max(e[2] for e in seen)
        first = min(e[1] for e in seen)
        window = (min(first, last - window_s), last)
    return reduce_events(device_events, host_events, window,
                         named_events=programs or None)


def breakdown(reduced: dict | None) -> dict:
    """The result line's ``breakdown`` of a traced run."""
    reduced = reduced or {}
    return {"device_ops": reduced.get("device_ops", []),
            "idle_gaps": reduced.get("idle_gaps", [])}


def summary(reduced: dict | None) -> dict:
    """What a note on stderr says of a reduction."""
    if reduced is None:
        return {"found": False}
    return {"found": True, **{k: reduced[k] for k in
                              ("busy_s", "window_s", "device_events")}}


def read(artefacts: dict) -> float | None:
    reduced = artefacts.get("xplane")
    return None if not reduced else reduced["idle_pct"]
