"""Snapshot isolation for the serving read path.

A loader commit ends in ``VariantStore.save``'s atomic ``manifest.json``
swap; the files a manifest references are never mutated in place.  That
gives the serving process a clean generation boundary: loading the store
pins ONE manifest's segment set fully into memory, so an in-flight query
that captured a :class:`StoreSnapshot` keeps reading exactly that
generation no matter what a concurrent loader renames, rewrites, or prunes
on disk — the reader-side half of the store's crash-consistency contract
(MVCC by whole-store generation, the closest columnar analog of the
reference's Postgres snapshot isolation).

:class:`SnapshotManager` owns the pinned generation:

- ``current()`` hands out the snapshot (queries hold it for their whole
  execution — the swap can never tear one mid-read);
- ``refresh()`` fingerprints ``manifest.json`` (one ``stat``), loads the
  new generation OFF-lock when it changed (one load at a time), then swaps
  the pin atomically.
  The load keeps every segment of the pinned store whose files the new
  manifest lists unchanged (``VariantStore.load(reuse=...)``), with its
  device cache and warmed programs: a memtable flush reads only the
  segments it wrote.
  The ``snapshot.swap`` fault point fires between load and swap: a failure
  there must leave the old generation serving, which the fault matrix pins.
  Between the two, each registered preparer (``add_preparer``: the query
  engine's, which folds the added segments into its interval indexes) gets
  the loaded store, so what a generation's reads need is made on the
  refreshing thread before any request can pin it.
- ``maybe_refresh()`` is the front end's coalesced entry point: at serving
  QPS a per-request ``stat`` is real syscall pressure, so freshness checks
  collapse to one ``stat`` per ``AVDB_SERVE_SNAPSHOT_TTL_MS`` window
  (default 250ms — a commit becomes visible within a quarter second, not
  within one request).  ``refresh()`` keeps its always-stat semantics for
  callers that need immediacy (tests, admin paths).

Stores are opened ``readonly=True``: the serving process can never create
directories, persist empty shards, or otherwise write through a read path.
"""

from __future__ import annotations

import os
import threading
import time
import weakref

from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.utils import faults
from annotatedvdb_tpu.utils.locks import make_lock
from annotatedvdb_tpu.utils.profiling import annotation


def _ttl_from_env() -> float:
    """``AVDB_SERVE_SNAPSHOT_TTL_MS`` (default 250) as seconds."""
    return max(
        float(os.environ.get("AVDB_SERVE_SNAPSHOT_TTL_MS", "") or 250), 0.0
    ) / 1000.0


class StoreSnapshot:
    """One immutable pinned generation of a store.

    ``generation`` increments per swap (1-based); ``fingerprint`` is the
    manifest identity the generation was loaded from (None for in-memory
    stores pinned by :class:`StaticSnapshots`); ``placement`` is the
    manifest's advisory chromosome->device map (``mesh_placement``, None
    when the store was saved single-device) — the serve mesh path and
    ``doctor status`` report it."""

    __slots__ = ("store", "generation", "fingerprint", "placement")

    def __init__(self, store: VariantStore, generation: int, fingerprint):
        self.store = store
        self.generation = generation
        self.fingerprint = fingerprint
        self.placement = getattr(store, "mesh_placement", None)


def _manifest_fingerprint(store_dir: str) -> tuple:
    """Identity of the on-disk manifest: (mtime_ns, size, inode).  The save
    path replaces the manifest via rename, so any commit changes the inode
    — mtime granularity can never mask a swap."""
    st = os.stat(os.path.join(store_dir, "manifest.json"))
    return (st.st_mtime_ns, st.st_size, st.st_ino)


class SnapshotManager:
    """Pins the serving store generation; swaps are atomic under a lock."""

    def __init__(self, store_dir: str, log=None, ttl_s: float | None = None):
        self.store_dir = store_dir
        self.log = log if log is not None else (lambda msg: None)
        self.ttl_s = _ttl_from_env() if ttl_s is None else max(float(ttl_s), 0.0)
        self._lock = make_lock("serve.snapshot.pin")
        #: held by the one refresh that loads: a second refresh of the
        #: same manifest would load it again (and prepare a store that is
        #: never pinned)
        self._loading = make_lock("serve.snapshot.load")
        fingerprint = _manifest_fingerprint(store_dir)
        store = VariantStore.load(store_dir, readonly=True)
        #: guarded by self._lock
        self._snap = StoreSnapshot(store, 1, fingerprint)
        #: guarded by self._lock
        self._swaps = 0
        #: guarded by self._lock — what the swaps' loads took: seconds,
        #: backing groups kept from the pin and groups read from disk
        self._reload_s = 0.0
        self._reused = 0
        self._loaded = 0
        #: guarded by self._lock
        self._next_check = 0.0  # monotonic deadline of the next free stat
        #: ``prepare(store)`` callbacks run on a loaded store before its
        #: pin (weak: a discarded engine is not kept alive by its hook)
        self._preparers: list = []
        #: True while a NEW generation is loading (between the changed
        #: fingerprint and the pin swap) — the readiness probe reports
        #: not-ready so a fleet router drains traffic off a warming
        #: worker (plain bool: atomic to read, written by the one
        #: refreshing thread)
        self.swapping = False

    def current(self) -> StoreSnapshot:
        """The pinned generation.  Callers keep the returned snapshot for
        their whole query — a concurrent swap replaces the PIN, never the
        snapshot object they hold."""
        with self._lock:
            return self._snap

    def add_preparer(self, method) -> None:
        """Run bound ``method(store)`` on every newly loaded store before
        it is pinned, on the refreshing thread.  A preparer that raises
        is logged and the swap goes ahead: it may only make reads of the
        new generation cheaper, never possible."""
        with self._lock:
            self._preparers = [ref for ref in self._preparers
                               if ref() is not None]
            self._preparers.append(weakref.WeakMethod(method))

    def _prepare(self, store) -> None:
        with self._lock:
            methods = [ref() for ref in self._preparers]
        for method in methods:
            if method is None:
                continue
            try:
                method(store)
            except Exception as err:
                self.log(f"snapshot refresh: preparing the new generation "
                         f"failed ({type(err).__name__}: {err})")

    @property
    def swaps(self) -> int:
        with self._lock:
            return self._swaps

    def stats(self) -> dict:
        """``/stats`` ``snapshot``: swaps and what their loads took."""
        with self._lock:
            return {"swaps": self._swaps,
                    "reload_s": round(self._reload_s, 6),
                    "segments_reused": self._reused,
                    "segments_loaded": self._loaded}

    def refresh_due(self) -> bool:
        """Whether the TTL window has lapsed (no stat, no side effects) —
        the event-loop front end's cheap in-line check before it schedules
        the real refresh off-loop."""
        with self._lock:
            return time.monotonic() >= self._next_check

    def maybe_refresh(self) -> bool:
        """Coalesced freshness check: at most one manifest ``stat`` per
        TTL window across ALL request threads; within the window the
        pinned generation is served as-is.  Returns True only when this
        call performed the swap."""
        now = time.monotonic()
        with self._lock:
            if now < self._next_check:
                return False
            self._next_check = now + self.ttl_s
        return self.refresh(wait=False)

    def refresh(self, wait: bool = True) -> bool:
        """Swap to the on-disk generation if it changed; returns True on a
        swap.  The expensive load runs OFF the pin's lock (readers keep
        being served from the old pin), one refresh at a time: with
        ``wait`` a caller that must see the result (a flush's hand-over)
        waits for a refresh in flight, without it returns False at once
        (the refresh in flight pins what it finds).  Load failures — a
        commit racing the stat, a torn directory mid-repair — keep the old
        generation and report False, because a serving process must
        degrade to stale before it degrades to down."""
        if not self._loading.acquire(blocking=wait):
            return False
        try:
            return self._refresh()
        finally:
            self._loading.release()

    def _refresh(self) -> bool:
        with self._lock:
            pinned = self._snap
        try:
            fingerprint = _manifest_fingerprint(self.store_dir)
        except OSError:
            return False  # manifest mid-rename: keep serving the pin
        if fingerprint == pinned.fingerprint:
            return False
        self.swapping = True
        t0 = time.perf_counter()
        try:
            try:
                with annotation("avdb.snapshot.reload"):
                    store = VariantStore.load(self.store_dir, readonly=True,
                                              reuse=pinned.store)
            except (OSError, ValueError) as err:  # StoreCorruptError is a ValueError
                self.log(f"snapshot refresh failed, keeping generation "
                         f"{pinned.generation}: {err}")
                return False
            self._prepare(store)
            # crash point: the new generation is fully loaded, the pin has
            # not moved — a failure here must leave the old generation
            # serving (and readiness recover: the finally clears the flag)
            faults.fire("snapshot.swap")
        finally:
            self.swapping = False
        with self._lock:
            if self._snap.fingerprint == fingerprint:
                return False  # a concurrent refresh won the race
            if self._snap is not pinned:
                # the pin moved while THIS load ran (a concurrent refresh
                # installed a different — by now newer — manifest): never
                # swap content backwards; the next request re-stats
                return False
            self._snap = StoreSnapshot(
                store, self._snap.generation + 1, fingerprint
            )
            self._swaps += 1
            self._reload_s += time.perf_counter() - t0
            self._reused += store.load_counts["reused"]
            self._loaded += store.load_counts["loaded"]
            generation = self._snap.generation
        self.log(f"snapshot swapped to generation {generation} "
                 f"({store.n} rows; {store.load_counts['reused']} segment "
                 f"group(s) kept, {store.load_counts['loaded']} read)")
        return True


class _OverlayStore:
    """Read-only store view: the base generation's shards with the
    memtable's in-memory segments appended AFTER them — so every read
    path's first-wins dedup resolves collisions toward the stored (older)
    row, and upserted rows render through the exact same ``Segment``
    machinery loaded rows do.  The Segment objects are shared with the
    base store and the memtable; only the per-shard lists are fresh."""

    __slots__ = ("width", "readonly", "shards", "overlay_from")

    def __init__(self, base_store, mem_segments: dict):
        from annotatedvdb_tpu.store.variant_store import ChromosomeShard

        self.width = base_store.width
        self.readonly = True
        shards = {}
        #: {code: the first global id of the shard that is a memtable row}
        #: (every global id at or above it was upserted since the flush)
        self.overlay_from = {}
        # each shard's ``overlay_base``: how many of its segments the base
        # store holds (the rest are the memtable's) — where the query
        # engine's base interval index ends and its delta begins
        for code, bshard in base_store.shards.items():
            sh = ChromosomeShard(code, self.width)
            sh.segments = list(bshard.segments) \
                + list(mem_segments.get(code, ()))
            sh.overlay_base = len(bshard.segments)
            shards[code] = sh
            self.overlay_from[code] = bshard.n
        for code, segs in mem_segments.items():
            if code in shards or not segs:
                continue
            sh = ChromosomeShard(code, self.width)
            sh.segments = list(segs)
            sh.overlay_base = 0
            shards[code] = sh
            self.overlay_from[code] = 0
        self.shards = shards

    @property
    def n(self) -> int:
        return sum(s.n for s in self.shards.values())


class MemtableSnapshots:
    """Snapshot provider overlaying a live memtable on a base provider —
    the read-your-writes half of the online write path.

    Until the first upsert (memtable epoch 0) this is a pure pass-through:
    ``current()`` returns the base provider's snapshot object unchanged,
    so read-only serving pays nothing and generation numbering is exactly
    the historical one.  From the first upsert on, every distinct
    (base generation, memtable epoch) pair maps to a FRESH, monotonically
    increasing generation number strictly greater than any base
    generation handed out before — generation-keyed caches (point render,
    region LRU, interval indexes, cursor walks, the brownout point cache)
    can therefore never serve pre-upsert bytes for a post-upsert view,
    and ordering-aware consumers (residency govern) keep their invariant.
    """

    def __init__(self, base, memtable):
        self.base = base
        self.memtable = memtable
        self._lock = make_lock("serve.snapshot.overlay")
        #: guarded by self._lock
        self._last_key = None
        #: guarded by self._lock
        self._last_snap: StoreSnapshot | None = None
        #: guarded by self._lock — the remapped generation counter (kept
        #: strictly above every base generation observed)
        self._gen = 0
        #: guarded by self._lock — bumps per reset_memtable swap so view
        #: keys from different memtable incarnations can never collide
        self._mt_ver = 0
        #: guarded by self._lock — overlay generations handed out
        #: (``/stats`` ``overlay.generations``)
        self._created = 0

    def current(self) -> StoreSnapshot:
        base = self.base.current()
        with self._lock:
            mt = self.memtable
            ver = self._mt_ver
        epoch, segs, _rows, _bytes = mt.view()
        if epoch == 0 and ver == 0:
            return base  # pristine: exact legacy behavior, zero overhead
        key = (base.generation, epoch, ver)
        with self._lock:
            if key == self._last_key:
                return self._last_snap
        overlay = _OverlayStore(base.store, segs)
        with self._lock:
            if key == self._last_key:  # a racing builder won; take its snap
                return self._last_snap
            self._gen = max(self._gen + 1, base.generation + 1)
            self._created += 1
            snap = StoreSnapshot(overlay, self._gen, base.fingerprint)
            self._last_key = key
            self._last_snap = snap
            return snap

    def reset_memtable(self, memtable) -> None:
        """Swap in a fresh overlay memtable — the replication follower's
        re-sync path: rows now covered by a freshly installed base cut
        leave the overlay, so a long-running follower's memory stays
        bounded by one flush interval.  Generation numbering stays
        strictly monotone across the swap: once any overlay generation
        was handed out, even an epoch-0 (empty) view keeps being
        remapped above it, so generation-keyed caches can never see the
        same number twice with different content."""
        with self._lock:
            self.memtable = memtable
            self._mt_ver += 1
            self._last_key = None
            self._last_snap = None

    def maybe_refresh(self) -> bool:
        return self.base.maybe_refresh()

    def refresh(self) -> bool:
        return self.base.refresh()

    def refresh_due(self) -> bool:
        return self.base.refresh_due() \
            if hasattr(self.base, "refresh_due") else False

    @property
    def swaps(self) -> int:
        return self.base.swaps

    @property
    def generations(self) -> int:
        with self._lock:
            return self._created

    def stats(self) -> dict:
        return self.base.stats() if hasattr(self.base, "stats") else {}

    @property
    def swapping(self) -> bool:
        return bool(getattr(self.base, "swapping", False))


class StaticSnapshots:
    """Snapshot provider over an in-memory store (tests, bench) — one fixed
    generation, ``refresh`` is a no-op."""

    def __init__(self, store: VariantStore, generation: int = 1):
        self._snap = StoreSnapshot(store, generation, None)

    def current(self) -> StoreSnapshot:
        return self._snap

    def refresh(self) -> bool:
        return False

    def maybe_refresh(self) -> bool:
        return False

    @property
    def swaps(self) -> int:
        return 0
