"""How much of the device's idle time the capture can name.

``xplane_idle`` attributes every idle gap to the shortest host span of a
millisecond or more that covers it, and what no span covers to
``host:untraced``.  ``read`` is 100 x (1 - seconds under ``host:untraced``
/ idle seconds), idle seconds being ``window_s`` - ``busy_s`` of the same
reduction: the share of the idle time during which some span — XLA's own or
one of the program's ``avdb.*`` annotations — says what the host was doing.
It guards the program's spans: a rewrite of a hot thread that drops them
shows here at once.

The reduction keeps the ten largest names; ``host:untraced`` not among them
is under a tenth of the idle time at most and reads as 100.  No device
capture (a CPU rehearsal), or a device that was never idle, gives nothing.
"""

from __future__ import annotations

UNTRACED = "host:untraced"


def read(artefacts: dict) -> float | None:
    reduced = artefacts.get("xplane")
    if not reduced:
        return None
    idle_s = reduced["window_s"] - reduced["busy_s"]
    if idle_s <= 0:
        return None
    untraced = sum(seconds for name, seconds in reduced["idle_gaps"]
                   if name == UNTRACED)
    return 100.0 * (1.0 - min(untraced, idle_s) / idle_s)
