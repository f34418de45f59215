"""Resilience primitives for the serve stack: deadlines, brownout, breaker.

PR 3 made the write path crash-safe and PR 6 made the read path fast; this
module is the read path's FAILURE response.  Production serving survives
overload and partial device failure through three mechanisms, each a small
self-contained governor wired into the front end (``serve/aio.py``)
through :class:`~annotatedvdb_tpu.serve.http.ServeContext`:

- **deadline propagation** (:class:`DeadlineExceeded`, :func:`deadline_at`)
  — requests carry ``X-Deadline-Ms`` (default
  ``AVDB_SERVE_DEFAULT_DEADLINE_MS``); admission, the batcher queue, and
  the bulk/region executors all check remaining budget and shed
  already-dead requests BEFORE device work with a 504 and one tick of
  ``avdb_deadline_shed_total{stage}``.  Work a client stopped waiting for
  is pure queue poison: executing it delays every live request behind it.

- **brownout ladder** (:class:`OverloadGovernor`) — a loop-resident
  overload governor watches batcher queue depth and the fraction of
  requests exceeding the p99 target (``AVDB_SERVE_BROWNOUT_P99_MS``) and
  steps through declared degradation levels with hysteresis:

  ========== ================= ==========================================
  level 0    ``normal``        full service
  level 1    ``limit``         region ``limit`` ceilings shrink to
                               :data:`BROWNOUT_REGION_LIMIT`
  level 2    ``cache_first``   point reads answer from the generation-
                               keyed id cache when they can (skip the
                               batcher queue entirely on a hit)
  level 3    ``shed_bulk``     bulk/region rejected 503 (+Retry-After);
                               point reads keep serving.  Readiness goes
                               false (``/readyz`` 503) so a fleet router
                               can drain traffic off this worker.
  ========== ================= ==========================================

  Saturation therefore produces BOUNDED latency on the traffic that
  matters (point reads) instead of uniform collapse; the current level is
  visible in ``/healthz`` and the ``avdb_serve_brownout_level`` gauge.

- **device-path circuit breaker** (:class:`DeviceBreaker`) — repeated
  device probe/upload failures (surfaced by the store's probe fallback
  hook, or injected at the ``engine.device_probe`` fault point) trip the
  engine to the byte-identical host path PER CHROMOSOME GROUP; after a
  cooldown one half-open probe is allowed through, and a success re-closes
  the group.  Correctness never depends on the breaker state — device and
  host probes return identical answers — so a flaky device degrades
  throughput, never bytes.

Everything here is stdlib-only and wall-clock injected (``clock=``) so the
tests drive state machines deterministically.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import OrderedDict
from annotatedvdb_tpu.utils.locks import make_lock

#: region row ceiling under brownout level >= 1 (the "limit" rung): a hot
#: serving process must bound per-request render work before it starts
#: shedding whole request classes
BROWNOUT_REGION_LIMIT = 256

#: ladder levels (names are the /healthz vocabulary)
LEVEL_NORMAL = 0
LEVEL_LIMIT = 1
LEVEL_CACHE_FIRST = 2
LEVEL_SHED_BULK = 3

LEVEL_NAMES = ("normal", "limit", "cache_first", "shed_bulk")


class DeadlineExceeded(RuntimeError):
    """The request's deadline budget ran out before (or while) it executed
    — the front end maps this to HTTP 504.  Raised for SHED work: the
    response says "we did not do this", never "we failed doing it"."""


def default_deadline_s() -> float:
    """``AVDB_SERVE_DEFAULT_DEADLINE_MS`` as seconds (0 = requests carry no
    deadline unless the client sends ``X-Deadline-Ms``)."""
    return max(
        float(os.environ.get("AVDB_SERVE_DEFAULT_DEADLINE_MS", "") or 0), 0.0
    ) / 1000.0


def deadline_at(header_value: str | None, default_s: float,
                now: float | None = None) -> float | None:
    """Absolute monotonic deadline for a request arriving ``now``.

    ``header_value`` is the raw ``X-Deadline-Ms`` header (milliseconds of
    budget from arrival); an unparseable or non-positive value falls back
    to the default budget (lenient by design: a garbled deadline header
    must not turn a degraded client's requests into 400s).  Returns None
    when neither source sets a budget."""
    budget_s = default_s
    if header_value:
        try:
            ms = float(header_value)
        except ValueError:
            ms = 0.0
        if ms > 0:
            budget_s = ms / 1000.0
    if budget_s <= 0:
        return None
    if now is None:
        now = time.monotonic()
    return now + budget_s


class PointCache:
    """Generation-keyed point-result cache by VARIANT ID — the brownout
    ladder's ``cache_first`` rung.

    The engine's render LRU is keyed by (generation, chromosome, row id),
    which only exists AFTER a probe; this cache fronts the whole lookup by
    the raw id string so a brownout-level-2 point read can answer without
    touching the batcher queue at all.  Populated on every completed point
    read (one lock + dict move per request — measured noise next to the
    render itself); entries carry the generation they were computed
    against, so a stale generation can never serve (its keys age out).
    Negative results (id not in store) cache too: absence is immutable
    per generation, exactly like presence."""

    #: ("miss" sentinel distinct from "not cached")
    _ABSENT = object()

    def __init__(self, capacity: int = 8192):
        self.capacity = int(capacity)
        self._lock = make_lock("serve.resilience.point_cache")
        #: guarded by self._lock
        self._cache: OrderedDict = OrderedDict()

    def get(self, generation: int, variant_id: str):
        """(hit, record_or_None).  ``hit`` False = not cached."""
        key = (generation, variant_id)
        with self._lock:
            v = self._cache.get(key, self._ABSENT)
            if v is self._ABSENT:
                return False, None
            self._cache.move_to_end(key)
            return True, v

    def put(self, generation: int, variant_id: str, record) -> None:
        if self.capacity <= 0:
            return
        key = (generation, variant_id)
        with self._lock:
            self._cache[key] = record
            self._cache.move_to_end(key)
            while len(self._cache) > self.capacity:
                self._cache.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)


def brownout_p99_target_s() -> float:
    """``AVDB_SERVE_BROWNOUT_P99_MS`` as seconds (default 250; 0 disables
    the latency trigger — the queue-depth trigger still governs)."""
    return max(
        float(os.environ.get("AVDB_SERVE_BROWNOUT_P99_MS", "") or 250), 0.0
    ) / 1000.0


class OverloadGovernor:
    """The brownout ladder's state machine.

    Two overload signals, evaluated at most once per ``eval_interval_s``:

    - **queue depth** — the batcher's pending-query depth as a fraction of
      its admission bound (``depth_fn()/max_queue``);
    - **latency-target exceedance** — an EWMA of the indicator
      ``latency > p99_target``: when more than ~5% of recent requests run
      over the target, the true p99 is far past it (1% exceedance == p99
      AT the target, so enter/exit at 5%/1% gives real hysteresis).

    Either signal hot steps the ladder UP one level per evaluation; both
    signals cool (below the exit thresholds) for ``hold_s`` steps it back
    DOWN one level.  One level per step means load spikes brown out in
    under a second while flapping is structurally impossible — a level
    change always out-waits the hold.

    Thread-safe; :meth:`maybe_step` runs on the loop's maintenance tick
    and rides request completion and health polls (time-gated, so
    per-request cost is one lock + compare).
    """

    EVAL_INTERVAL_S = 0.25
    HOLD_S = 1.0
    DEPTH_ENTER = 0.5
    DEPTH_EXIT = 0.125
    EXCEED_ENTER = 0.05
    EXCEED_EXIT = 0.01
    EWMA_ALPHA = 0.02

    def __init__(self, depth_fn, max_queue: int,
                 p99_target_s: float | None = None, registry=None,
                 clock=time.monotonic, eval_interval_s: float | None = None,
                 hold_s: float | None = None, on_change=None):
        self._depth_fn = depth_fn
        self._max_queue = max(int(max_queue), 1)
        self.p99_target_s = (
            brownout_p99_target_s() if p99_target_s is None
            else max(float(p99_target_s), 0.0)
        )
        self._clock = clock
        self.eval_interval_s = (
            self.EVAL_INTERVAL_S if eval_interval_s is None
            else max(float(eval_interval_s), 0.0)
        )
        self.hold_s = self.HOLD_S if hold_s is None else max(float(hold_s), 0.0)
        #: level-transition observer ``on_change(old, new)`` — the flight
        #: recorder's brownout timeline; invoked OUTSIDE the lock and
        #: never allowed to fail the evaluation that stepped the ladder
        self.on_change = on_change
        self._lock = make_lock("serve.resilience.governor")
        #: guarded by self._lock
        self._level = LEVEL_NORMAL
        #: guarded by self._lock
        self._exceed_ewma = 0.0
        #: guarded by self._lock
        self._samples = 0  # since the last evaluation
        #: guarded by self._lock
        self._next_eval = 0.0
        #: guarded by self._lock
        self._last_change = self._clock()
        #: guarded by self._lock
        self._steps = 0  # level changes since start, either way
        #: guarded by self._lock
        self._max_level = LEVEL_NORMAL
        if registry is not None:
            self._m_level = registry.gauge(
                "avdb_serve_brownout_level",
                "current brownout degradation level (0=normal..3=shed_bulk)",
            )
        else:
            self._m_level = None

    # -- signals ------------------------------------------------------------

    def note_latency(self, seconds: float) -> None:
        """Feed one completed request's latency (every kind counts: an
        overloaded executor pool shows up in region latency first)."""
        if self.p99_target_s <= 0:
            return
        exceed = 1.0 if seconds > self.p99_target_s else 0.0
        with self._lock:
            self._exceed_ewma += self.EWMA_ALPHA * (exceed - self._exceed_ewma)
            self._samples += 1

    # -- evaluation ---------------------------------------------------------

    def maybe_step(self) -> int:
        """Evaluate the ladder if the interval lapsed; returns the level."""
        now = self._clock()
        with self._lock:
            if now < self._next_eval:
                return self._level
            self._next_eval = now + self.eval_interval_s
            try:
                depth_ratio = self._depth_fn() / self._max_queue
            except Exception:
                depth_ratio = 0.0
            if self._samples == 0:
                # idle window: decay the exceedance signal toward calm —
                # a burst that ended must not pin the ladder up forever
                self._exceed_ewma *= 0.5
            self._samples = 0
            exceed = self._exceed_ewma
            hot = (depth_ratio >= self.DEPTH_ENTER
                   or exceed >= self.EXCEED_ENTER)
            cool = (depth_ratio <= self.DEPTH_EXIT
                    and exceed <= self.EXCEED_EXIT)
            level = self._level
            if hot and level < LEVEL_SHED_BULK:
                level += 1
                self._last_change = now
            elif cool and level > LEVEL_NORMAL \
                    and now - self._last_change >= self.hold_s:
                level -= 1
                self._last_change = now
            old = self._level
            changed = level != old
            if changed:
                self._steps += 1
                self._max_level = max(self._max_level, level)
            self._level = level
        if changed:
            if self._m_level is not None:
                self._m_level.set(level)
            if self.on_change is not None:
                try:
                    self.on_change(old, level)
                except Exception:  # avdb: noqa[AVDB602] -- an observer must never fail the ladder evaluation it watches
                    pass
        return level

    def force_level(self, level: int) -> None:
        """Pin the ladder to a level (tests / operator escape hatch); the
        next hot/cool evaluation moves it again."""
        level = min(max(int(level), LEVEL_NORMAL), LEVEL_SHED_BULK)
        with self._lock:
            old = self._level
            if level != old:
                self._steps += 1
                self._max_level = max(self._max_level, level)
            self._level = level
            self._last_change = self._clock()
        if self._m_level is not None:
            self._m_level.set(level)
        if old != level and self.on_change is not None:
            try:
                self.on_change(old, level)
            except Exception:  # avdb: noqa[AVDB602] -- an observer must never fail the ladder evaluation it watches
                pass

    def stats(self) -> dict:
        """The ``/stats`` ``brownout`` block: where the ladder stands, how
        far up it has been and how often it stepped since start, and the
        latency signal it steps on."""
        with self._lock:
            return {
                "level": self._level, "name": LEVEL_NAMES[self._level],
                "max_level": self._max_level, "steps": self._steps,
                "exceedance": self._exceed_ewma,
            }

    # -- level queries (the front end's contract) ---------------------------

    @property
    def level(self) -> int:
        with self._lock:
            return self._level

    @property
    def level_name(self) -> str:
        return LEVEL_NAMES[self.level]

    @property
    def exceedance(self) -> float:
        """Current latency-over-target EWMA — published through the fleet
        heartbeat slots as the maintenance daemon's p99-breach signal
        (>= EXCEED_ENTER means the ladder itself would escalate)."""
        with self._lock:
            return self._exceed_ewma

    def region_limit_cap(self) -> int | None:
        """Row ceiling to clamp region ``limit`` to, or None."""
        return BROWNOUT_REGION_LIMIT if self.level >= LEVEL_LIMIT else None

    def cache_first(self) -> bool:
        return self.level >= LEVEL_CACHE_FIRST

    def shed_bulk(self) -> bool:
        return self.level >= LEVEL_SHED_BULK


class _BreakerObservation:
    """One observed probe window: the store-side failure hook marks it
    failed so the engine knows not to double-report a success."""

    __slots__ = ("failed",)

    def __init__(self):
        self.failed = False


#: the active (breaker, observation, code) of THIS thread's probe window —
#: module-level so the store's single failure hook dispatches to whichever
#: breaker opened the window (several engines can coexist in one process;
#: a per-instance hook would misroute every instance but the last
#: installed)
_tls = threading.local()


def _probe_failure_hook(exc: BaseException) -> bool:
    """The one store-side hook: route a device-probe failure to the
    breaker observing on this thread (True = owned: the probe answers
    from the numpy path); outside any window the store raises it."""
    owner = getattr(_tls, "owner", None)
    if owner is None:
        return False
    breaker, obs, code = owner
    obs.failed = True
    breaker.record_failure(code, exc)
    return True


class DeviceBreaker:
    """Per-chromosome-group circuit breaker over the device probe path.

    States per group: ``closed`` (device allowed), ``open`` (host path
    only, until ``reopen_at``), ``half_open`` (exactly one trial probe in
    flight — success closes, failure re-opens with doubled cooldown).
    Inside an observing window the store's probe answers a device error
    from the byte-identical numpy path and reports it here; what the
    breaker adds is policy: stop paying the failing-device attempt per
    probe (open), and recover automatically when the device heals
    (half-open).  Every trip is counted
    (``avdb_serve_breaker_trips_total``) — the fallback is a guarantee of
    the server, never a silent one.
    """

    FAILURE_THRESHOLD = 3
    COOLDOWN_S = 5.0
    COOLDOWN_MAX_S = 60.0

    def __init__(self, registry=None, log=None, clock=time.monotonic,
                 cooldown_s: float | None = None,
                 failure_threshold: int | None = None):
        self.log = log if log is not None else (lambda msg: None)
        #: lifecycle-event observer ``events(name, detail)`` — the flight
        #: recorder's breaker timeline (ServeContext installs it);
        #: invoked outside the lock, failures swallowed
        self.events = None
        self._clock = clock
        self.cooldown_s = (
            self.COOLDOWN_S if cooldown_s is None else max(float(cooldown_s), 0.0)
        )
        self.failure_threshold = (
            self.FAILURE_THRESHOLD if failure_threshold is None
            else max(int(failure_threshold), 1)
        )
        self._lock = make_lock("serve.resilience.breaker")
        #: guarded by self._lock; code -> {state, failures, reopen_at, cooldown}
        self._groups: dict[int, dict] = {}
        if registry is not None:
            self._m_open = registry.gauge(
                "avdb_serve_breaker_open_groups",
                "chromosome groups currently tripped to the host path",
            )
            self._m_trips = registry.counter(
                "avdb_serve_breaker_trips_total",
                "circuit-breaker trips (group moved closed/half_open -> open)",
            )
            self._m_probes = registry.counter(
                "avdb_serve_breaker_half_open_probes_total",
                "half-open trial probes allowed through a cooled-down group",
            )
        else:
            self._m_open = self._m_trips = self._m_probes = None

    # -- store-side hook ----------------------------------------------------

    def install(self) -> None:
        """Register the module-level dispatcher as the store's
        device-probe failure observer: a REAL device error inside
        ``Segment.probe`` reports to the breaker observing on that thread
        (and the probe answers from numpy) instead of propagating.
        Idempotent across breakers."""
        from annotatedvdb_tpu.store import variant_store

        variant_store.set_device_probe_failure_hook(_probe_failure_hook)

    @contextlib.contextmanager
    def observing(self, code: int, obs: "_BreakerObservation | None" = None):
        """Attribute in-window device-probe failures to ``code`` on THIS
        breaker (the probe runs fully on the calling thread on every
        front end).  A probe that is launched in one window and collected
        in a later one hands the first window's observation to the second
        as ``obs``: between the two the thread may observe other groups."""
        if obs is None:
            obs = _BreakerObservation()
        _tls.owner = (self, obs, code)
        try:
            yield obs
        finally:
            _tls.owner = None

    # -- state machine ------------------------------------------------------

    def _group(self, code: int) -> dict:
        g = self._groups.get(code)  # avdb: noqa[AVDB201] -- helper only called with self._lock already held (record_failure)
        if g is None:
            g = self._groups[code] = {  # avdb: noqa[AVDB201] -- helper only called with self._lock already held (record_failure)
                "state": "closed", "failures": 0, "reopen_at": 0.0,
                "cooldown": self.cooldown_s,
            }
        return g

    def allow_device(self, code: int) -> bool:
        """Whether this group's probe may take the device path right now.
        An open group whose cooldown lapsed transitions to half_open and
        admits exactly ONE trial."""
        now = self._clock()
        with self._lock:
            g = self._groups.get(code)
            if g is None or g["state"] == "closed":
                return True
            if g["state"] == "open":
                if now < g["reopen_at"]:
                    return False
                g["state"] = "half_open"
                probe = True
            else:  # half_open: one trial already in flight
                probe = False
        if probe:
            if self._m_probes is not None:
                self._m_probes.inc()
            return True
        return False

    def would_allow(self, code: int) -> bool:
        """:meth:`allow_device`'s verdict WITHOUT consuming the half-open
        trial slot or transitioning state — for pre-flight gates (the
        mesh executor's ``would_dispatch``) that run BEFORE the real
        admission check; calling ``allow_device`` twice per dispatch
        would spend the single half-open trial on the pre-check and
        refuse the dispatch itself, wedging recovery."""
        now = self._clock()
        with self._lock:
            g = self._groups.get(code)
            if g is None or g["state"] == "closed":
                return True
            if g["state"] == "open":
                return now >= g["reopen_at"]
            return False  # half_open: the one trial is already in flight

    def record_failure(self, code: int, exc: BaseException) -> None:
        now = self._clock()
        tripped = False
        with self._lock:
            g = self._group(code)
            if g["state"] == "half_open":
                # the trial failed: re-open, back off harder
                g["cooldown"] = min(g["cooldown"] * 2, self.COOLDOWN_MAX_S)
                g["state"] = "open"
                g["reopen_at"] = now + g["cooldown"]
                g["failures"] = 0
                tripped = True
            elif g["state"] == "closed":
                g["failures"] += 1
                if g["failures"] >= self.failure_threshold:
                    g["state"] = "open"
                    g["reopen_at"] = now + g["cooldown"]
                    g["failures"] = 0
                    tripped = True
            open_count = self._open_count_locked()
        if tripped:
            self.log(
                f"breaker: chromosome group {code} tripped to host path "
                f"({type(exc).__name__}: {exc})"
            )
            if self._m_trips is not None:
                self._m_trips.inc()
            if self.events is not None:
                try:
                    self.events(
                        "breaker",
                        f"group {code} tripped open "
                        f"({type(exc).__name__})",
                    )
                except Exception:  # avdb: noqa[AVDB602] -- an observer must never fail the breaker transition it watches
                    pass
        if self._m_open is not None:
            self._m_open.set(open_count)

    def record_success(self, code: int) -> None:
        closed = False
        with self._lock:
            g = self._groups.get(code)
            if g is None:
                return
            if g["state"] == "half_open":
                g["state"] = "closed"
                g["cooldown"] = self.cooldown_s
                closed = True
            g["failures"] = 0
            open_count = self._open_count_locked()
        if closed:
            self.log(f"breaker: chromosome group {code} re-closed "
                     "(half-open probe succeeded)")
            if self.events is not None:
                try:
                    self.events("breaker", f"group {code} re-closed")
                except Exception:  # avdb: noqa[AVDB602] -- an observer must never fail the breaker transition it watches
                    pass
        if self._m_open is not None:
            self._m_open.set(open_count)

    def _open_count_locked(self) -> int:
        return sum(
            1 for g in self._groups.values() if g["state"] != "closed"  # avdb: noqa[AVDB201] -- _locked suffix contract: every caller holds self._lock
        )

    # -- introspection ------------------------------------------------------

    def open_groups(self) -> list[int]:
        with self._lock:
            return sorted(
                c for c, g in self._groups.items() if g["state"] != "closed"
            )

    def state(self, code: int) -> str:
        with self._lock:
            g = self._groups.get(code)
            return g["state"] if g is not None else "closed"

    def stats(self) -> dict:
        with self._lock:
            return {
                "open_groups": sorted(
                    c for c, g in self._groups.items()
                    if g["state"] != "closed"
                ),
                "groups": {
                    str(c): {"state": g["state"], "failures": g["failures"]}
                    for c, g in self._groups.items()
                },
            }
