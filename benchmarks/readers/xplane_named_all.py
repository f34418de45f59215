"""How much of the device's idle time the capture can name, over every gap.

``xplane_idle`` names the 200 longest idle gaps of a capture and
``xplane_named`` reads its share from those: right for a cell whose idle
time lies in a few hundred long gaps, and a reading of the cut where the
gaps are many and short — a point microbatch probes three times a drain,
some 650 probes and 2,000 gaps in five seconds, and more than half of the
idle time lay in gaps that were never looked at (PERF.md section 7,
question 9e).  :func:`name_all` attributes **every** gap, by the same rule
(``xplane_idle.attribute_gap``: each moment to the shortest host span of a
millisecond or more that covers it, what none covers to
``host:untraced``), in one sweep over gaps and spans in time order.
:func:`reduce_trace` reads the capture for it.  ``read`` is 100 x (1 -
``host:untraced`` seconds / idle seconds) of that: the share of the idle
time during which some span — XLA's own or one of the program's ``avdb.*``
annotations — says what the host was doing.

No capture, no device plane (a CPU rehearsal) or a device that was never
idle gives nothing.
"""

from __future__ import annotations

from readers import xplane_idle
from readers.xplane_named import UNTRACED


def name_all(device_intervals: list, host_events: list, window: tuple,
             top: int = 10) -> dict | None:
    """``device_intervals``: [(start_s, end_s)] of one device's operations;
    ``host_events``: [(name, start_s, end_s)]; ``window``: the traced span.
    {idle_s, untraced_s, gaps, idle_gaps: the ``top`` names by seconds}."""
    gaps = sorted(xplane_idle.idle_gaps(device_intervals, window))
    idle_s = sum(end - start for start, end in gaps)
    if idle_s <= 0:
        return None
    events = sorted(host_events, key=lambda e: e[1])
    named: dict = {}
    live: list = []  # spans that began before this gap's end and go on
    at = 0
    for gap in gaps:
        while at < len(events) and events[at][1] < gap[1]:
            live.append(events[at])
            at += 1
        # gaps come in time order: a span that ended before this one began
        # covers no later one
        live = [e for e in live if e[2] > gap[0]]
        for key, seconds in xplane_idle.attribute_gap(gap, live).items():
            named[key] = named.get(key, 0.0) + seconds
    return {
        "idle_s": idle_s,
        "untraced_s": named.get(UNTRACED, 0.0),
        "gaps": len(gaps),
        "idle_gaps": sorted(([n, s] for n, s in named.items()),
                            key=lambda x: -x[1])[:top],
    }


def reduce_trace(trace_dir: str, window_s: float | None = None) -> dict | None:
    """:func:`name_all` of the capture under ``trace_dir``, over the planes,
    lines, spans and window ``xplane_idle.reduce_trace`` takes."""
    path = xplane_idle.find_capture(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    device: list = []
    host: list = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xplane_idle.DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            for name in xplane_idle.OP_LINES:
                if device or name not in lines:
                    continue
                device = [(e.start_ns / 1e9,
                           (e.start_ns + e.duration_ns) / 1e9)
                          for e in lines[name].events]
        elif plane.name.startswith("/host:"):
            host += [(f"{line.name.split('/')[0]}:{e.name}"[:80],
                      e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                     for line in plane.lines for e in line.events
                     if e.duration_ns >= 1_000_000]
    if not device:
        return None
    first = min([s for s, _e in device] + [e[1] for e in host])
    last = max([e for _s, e in device] + [e[2] for e in host])
    if window_s:
        first = min(first, last - window_s)
    return name_all(device, host, (first, last))


def read(artefacts: dict) -> float | None:
    gaps = artefacts.get("xplane_all_gaps")
    if not gaps:
        return None
    return 100.0 * (1.0 - min(gaps["untraced_s"], gaps["idle_s"])
                    / gaps["idle_s"])
