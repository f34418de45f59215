"""The serving event loop's own account: waiting for sockets, or running
callbacks.

An asyncio loop alternates between ``selector.select(timeout)`` and the
callbacks that call made ready.  :class:`TimedSelector` wraps the loop's
selector — the public seam ``asyncio.SelectorEventLoop(selector)`` offers —
and times that one call, so every **turn** of the loop splits into

- ``wait``: inside ``select`` — the loop had nothing to do;
- ``busy``: between two ``select``\\ s — callbacks ran.

Per turn that costs two ``perf_counter_ns`` reads and a few integer
additions on the loop's own thread: no lock, no allocation, never anything
per request.  :class:`LoopClock` holds the sums, the longest busy stretch
(an operator's loop-lag reading) and the busy seconds the server itself
accounts for — ``read``, ``reply`` (and, of it, ``write``: the coalesced
``writer.write`` alone), ``stream``, ``tick``, each measured around a
synchronous section on the loop, never across an ``await``, and ``drain``,
which the batcher tallies.  What is left of ``busy`` is ``other``:
asyncio's own machinery and the ``recv``/``send`` callbacks.

**On the profiler's clock.**  While a ``jax.profiler`` capture runs (and
only then: ``TraceAnnotation.is_enabled()``) the same split is two
annotations on the loop's thread: ``avdb.loop.wait`` around every
``select`` that may block, and ``avdb.loop.run`` over a run of consecutive
turns.  A run rides through waits shorter than :data:`RUN_RIDES_NS` (such
a wait is a system call, not idleness) and closes when a longer wait has
returned, so it encloses the waits it rode through and the one that ended
it; each of those is an ``avdb.loop.wait`` of its own, and a reader that
gives a moment to the shortest span covering it gives a real wait to
``avdb.loop.wait`` and the rest of the run to ``avdb.loop.run``.  At its
close a run carries ``turns`` and the ``wait_us`` it enclosed.
"""

from __future__ import annotations

import time

from annotatedvdb_tpu.utils import profiling

#: a ``select`` that returns sooner than this does not end a run of turns
#: on the profiler's clock: the loop asked the kernel and was answered
RUN_RIDES_NS = 100_000

#: the busy seconds the server accounts for itself (``/stats`` ``loop``
#: ``<part>_s``, ``avdb_loop_busy_seconds_total{part=...}``)
PARTS = ("read", "reply", "drain", "stream", "tick")


class LoopClock:
    """One event loop's turns, split into waiting and running.

    Written by the loop's thread alone (:meth:`TimedSelector.select` and the
    server's synchronous sections); :meth:`stats` is called on that thread
    too, inside a callback, and counts the stretch in progress as busy."""

    __slots__ = ("now", "turns", "wait_ns", "busy_ns", "t_resume",
                 "max_turn_ns", "max_turn_end_ns", "max_recent_ns",
                 "read_ns", "reply_ns", "write_ns", "stream_ns", "tick_ns")

    def __init__(self, now=time.perf_counter_ns):
        self.now = now
        self.turns = 0
        self.wait_ns = 0
        self.busy_ns = 0
        #: when the last ``select`` returned: the busy stretch began
        self.t_resume = now()
        self.max_turn_ns = 0  # since start
        self.max_turn_end_ns = 0  # when that stretch ended
        self.max_recent_ns = 0  # since the last /stats read
        self.read_ns = self.reply_ns = self.stream_ns = self.tick_ns = 0
        #: of ``reply_ns``: inside the coalesced ``writer.write`` (the copy
        #: and the transport's ``send``) — the part that is not our Python
        self.write_ns = 0

    def max_turn_note(self) -> str:
        """The longest busy stretch and how long ago it ended, for the
        slow-request log: a stall younger than the slow request's total is
        the loop parked under that request."""
        now = self.now()
        longest, ago = self.max_turn_ns, now - self.max_turn_end_ns
        if now - self.t_resume > longest:  # the stretch in progress
            longest, ago = now - self.t_resume, 0
        return (f"loop_max_turn={longest / 1e6:.2f}ms "
                f"ended={ago / 1e9:.3f}s_ago")

    def stats(self, drain_ns: int = 0, reset_recent: bool = False) -> dict:
        """The ``/stats`` ``loop`` block, computed when read.  ``drain_ns``
        is the batcher's own tally of its drains; ``reset_recent`` starts
        ``max_turn_ms``'s next window (a read of ``/stats`` does)."""
        now = self.now()
        in_progress = max(now - self.t_resume, 0)
        busy = self.busy_ns + in_progress
        recent = max(self.max_recent_ns, in_progress)
        if reset_recent:
            self.max_recent_ns = 0
        parts = dict(zip(PARTS, (self.read_ns, self.reply_ns, drain_ns,
                                 self.stream_ns, self.tick_ns)))
        return {
            "turns": self.turns,
            "wait_s": self.wait_ns / 1e9,
            "busy_s": busy / 1e9,
            "wall_s": (self.wait_ns + busy) / 1e9,
            "max_turn_ms": recent / 1e6,
            "max_turn_ms_since_start": max(self.max_turn_ns,
                                           in_progress) / 1e6,
            **{f"{part}_s": ns / 1e9 for part, ns in parts.items()},
            "other_s": (busy - sum(parts.values())) / 1e9,
            "write_s": self.write_ns / 1e9,  # inside reply_s, not beside it
        }


class TimedSelector:
    """A selector whose ``select`` is timed into a :class:`LoopClock`;
    everything else is the wrapped selector's own."""

    def __init__(self, selector, clock: LoopClock, capturing=None):
        self._select = selector.select
        self._clock = clock
        #: true while a profiler capture records annotations
        self._capturing = (profiling.trace_annotation().is_enabled
                           if capturing is None else capturing)
        clock.t_resume = clock.now()  # the loop starts with this selector
        self._run = None  # the open ``avdb.loop.run`` annotation
        self._run_turns = self._run_wait_ns = 0
        for name in ("register", "unregister", "modify", "close",
                     "get_key", "get_map"):
            setattr(self, name, getattr(selector, name))

    def select(self, timeout=None):
        clock = self._clock
        t0 = clock.now()
        busy = t0 - clock.t_resume
        clock.busy_ns += busy
        if busy > clock.max_recent_ns:
            clock.max_recent_ns = busy
            if busy > clock.max_turn_ns:
                clock.max_turn_ns = busy
                clock.max_turn_end_ns = t0
        if self._capturing():
            events = self._select_annotated(timeout)
        else:
            if self._run is not None:
                self._close_run()
            events = self._select(timeout)
        t1 = clock.t_resume = clock.now()
        clock.wait_ns += t1 - t0
        clock.turns += 1
        return events

    def _select_annotated(self, timeout):
        """``select`` under a capture: the run of turns stays open through
        a poll and through a wait under :data:`RUN_RIDES_NS`."""
        if self._run is None:
            self._open_run()
        self._run_turns += 1
        if timeout is not None and timeout <= 0:
            return self._select(timeout)  # a poll: cannot block
        now = self._clock.now
        with profiling.annotation("avdb.loop.wait"):
            t0 = now()
            events = self._select(timeout)
            waited = now() - t0
        self._run_wait_ns += waited
        if waited >= RUN_RIDES_NS:
            self._close_run()
            self._open_run()  # over the callbacks this wait made ready
        return events

    def _open_run(self) -> None:
        self._run = profiling.annotation("avdb.loop.run")
        self._run.__enter__()
        self._run_turns = self._run_wait_ns = 0

    def _close_run(self) -> None:
        run, self._run = self._run, None
        run.set_metadata(turns=self._run_turns,
                         wait_us=self._run_wait_ns // 1000)
        run.__exit__(None, None, None)
