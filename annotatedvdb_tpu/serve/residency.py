"""HBM hot-set residency for the serving read path.

The loaders' device membership cache (``Segment._device``) treats HBM as
free: ``pin_device_lookup`` materializes every large segment and the
ski-rental rule in ``Segment.probe`` only ever ADDS caches.  A serving
process fronting a store larger than device memory cannot do that —
annbatch's lesson (PAPERS.md, arXiv 2604.01949) is that a working set in
fast memory plus streaming for the cold tail serves at full rate while
the whole store does not fit.

:class:`ResidencyManager` owns the decision instead:

- every segment of the serving snapshot is marked ``residency="managed"``
  (``Segment.probe`` then never auto-uploads — it uses whatever cache the
  manager installed, and falls back to the host ``searchsorted`` path,
  which is byte-identical, when there is none);
- each bulk/point probe window **touches** the segments it overlaps
  (the same key-range pruning rule ``ChromosomeShard.lookup`` applies),
  feeding an exponentially-decayed per-segment hit score;
- under an ``AVDB_SERVE_HBM_BUDGET`` byte budget the manager keeps the
  hottest segments device-resident (upload through the *retrying*
  ``utils.retry.device_put`` path, the same one dispatch uses) and evicts
  the cold tail back to host (drop the cache; the numpy path keeps
  serving).  An evicted segment that turns hot again faults back in on a
  later maintain pass.

Correctness never depends on residency: device and host probes return
identical answers (pinned by the serve parity suite), so the budget only
moves WHERE the probe runs.  A store 4x the budget serves region and bulk
reads byte-identical to the unbounded path.
"""

from __future__ import annotations

import os
import threading
import time

from annotatedvdb_tpu.utils.arrays import next_pow2
from annotatedvdb_tpu.utils.locks import make_lock

#: score decay per DECAY_REF_S of ELAPSED time (half-life ~0.7s): an
#: untouched segment ages out on a wall-clock schedule — the same at
#: 100 QPS as at 10k — instead of decaying once per plan pass, which
#: would tie the aging rate to the request mix
DECAY = 0.95

#: elapsed seconds over which one DECAY factor applies
DECAY_REF_S = 0.05

#: seconds between plan passes under sustained traffic: touches between
#: passes accumulate cheaply (one score add under the lock) and the
#: decay + rank + pack runs at most once per interval — a bulk spanning
#: 24 chromosome groups is 24 touches but at most ONE plan
PLAN_INTERVAL_S = 0.05

#: a challenger must beat a resident's score by this factor to displace it
#: (hysteresis: near-tied segments must not thrash the upload path)
HYSTERESIS = 1.1


# the shared byte-size parser (also used by the store's spill tier and the
# serve CLI) — re-exported here so existing call sites keep their import
from annotatedvdb_tpu.utils.strings import parse_bytes  # noqa: F401


def budget_from_env() -> int | None:
    """The configured ``AVDB_SERVE_HBM_BUDGET`` in bytes, or None when the
    knob is unset/empty (= unmanaged: the store's own ski-rental rule)."""
    spec = os.environ.get("AVDB_SERVE_HBM_BUDGET", "").strip()
    return parse_bytes(spec) if spec else None


def device_cache_bytes(seg, width: int) -> int:
    """Size of the segment's identity-column HBM cache as
    ``Segment._ensure_device_cache`` builds it: pow2-padded pos/h (4B
    each), ref/alt (width B each), ref_len/alt_len (4B each)."""
    return next_pow2(seg.n) * (16 + 2 * int(width))


def _key_bounds(seg):
    """O(1) combined-key bounds for one segment.  Rows are sorted by
    combined key, so the first and last rows bound the range — computing
    them directly avoids ``seg.key_min``'s lazy materialization of the
    full O(n) key array, which govern() must never trigger: on the aio
    front end the first lookup after a generation swap runs ON the event
    loop, and a store-wide key build there stalls every connection."""
    if seg._key is not None:
        return seg._key[0], seg._key[-1]
    from annotatedvdb_tpu.store.variant_store import combined_key

    pos, h = seg.cols["pos"], seg.cols["h"]
    return (
        combined_key(pos[:1], h[:1])[0],
        combined_key(pos[-1:], h[-1:])[0],
    )


class _Entry:
    """Tracking state for one managed segment (one snapshot generation).
    Key bounds are captured at govern time: reading them off the segment
    on a touch path would lazily materialize its full combined-key array
    under the manager lock."""

    __slots__ = ("seg", "nbytes", "score", "resident", "pending",
                 "key_min", "key_max", "device", "code", "shard")

    def __init__(self, seg, nbytes: int, device: int | None = None,
                 code: int | None = None, shard=None):
        self.seg = seg
        #: the chromosome shard the segment belongs to (its interval index
        #: is warmed with the segment's upload)
        self.code = code
        self.shard = shard
        self.nbytes = nbytes
        self.score = 0.0
        self.resident = False
        #: planned resident, its upload (and probe-program warm, and its
        #: chromosome's interval-index warm) not landed yet: counted by
        #: the budget, not reported resident
        self.pending = False
        self.key_min, self.key_max = _key_bounds(seg)
        #: placement device index (None = default device / no placement)
        self.device = device


class ResidencyManager:
    """Keeps the hot working set of serving segments HBM-resident under a
    byte budget; everything else serves from host memory.

    ``upload=None`` (default) materializes device caches only when the
    store's device-lookup path is actually usable (a CPU-pinned serving
    process keeps pure bookkeeping — no duplicate host arrays); tests pass
    ``upload=True`` to exercise the real cache lifecycle on any backend.
    ``min_rows`` filters segments below the device break-even
    (``DEVICE_SEGMENT_MIN`` — tiny segments probe faster on host no matter
    how hot they run)."""

    def __init__(self, budget_bytes: int | None = None, registry=None,
                 log=None, upload: bool | None = None,
                 min_rows: int | None = None,
                 async_upload: bool | None = None,
                 plan_interval_s: float | None = None,
                 placement: dict | None = None, devices=None,
                 max_batch: int | None = None):
        if budget_bytes is None:
            budget_bytes = budget_from_env() or 0
        self.budget = max(int(budget_bytes), 0)
        #: chromosome code -> device index (parallel.mesh
        #: chromosome_placement).  With a placement installed the byte
        #: budget is PER DEVICE — each device packs its own hottest
        #: segments up to ``budget`` — and uploads pin to the placed
        #: device instead of the default one.  None keeps the historical
        #: single-device plan (one bucket, default device).
        self.placement = placement
        #: jax device objects indexed by placement value; resolved lazily
        #: (tests with upload=True on any backend pass their own)
        self._devices = devices
        self.log = log if log is not None else (lambda msg: None)
        self._upload = upload
        # uploads run on a dedicated worker thread by default: touch_window
        # fires on the probing thread — under the aio front end that IS the
        # event loop, and a multi-hundred-MB host->device transfer must
        # never stall it.  Tests pass async_upload=False for determinism.
        self._async_upload = True if async_upload is None else bool(async_upload)
        # the most ids a point microbatch's probe can hold (the batcher's
        # ``max_batch``, resolved where its env default lives): every query
        # shape up to it is run once against an uploaded segment BEFORE the
        # segment is installed and reported resident, so no reader's
        # request pays a compile
        from annotatedvdb_tpu.serve.batcher import resolve_batch_knobs

        self._max_probe_queries = resolve_batch_knobs(max_batch, None, None)[0]
        self._uploader = None  # lazily-built single-thread executor
        #: ``warm(generation, chromosome code, shard)``, set by the query
        #: engine that owns this manager: builds, uploads and warms the
        #: shard's interval index, on the thread that uploaded the segment
        self.index_warmer = None
        if min_rows is None:
            from annotatedvdb_tpu.store.variant_store import DEVICE_SEGMENT_MIN

            min_rows = DEVICE_SEGMENT_MIN
        self.min_rows = int(min_rows)
        # plan cadence: 0 plans on every touched window (tests want the
        # deterministic old behavior); the default bounds plan cost to
        # ~20/s no matter the offered load or chromosome spread
        self.plan_interval_s = (
            PLAN_INTERVAL_S if plan_interval_s is None
            else max(float(plan_interval_s), 0.0)
        )
        self._lock = make_lock("serve.residency.manager")
        #: guarded by self._lock
        self._last_plan = time.monotonic()
        #: guarded by self._lock
        self._generation: int | None = None
        #: guarded by self._lock
        self._entries: dict[int, _Entry] = {}  # id(segment) -> entry
        #: guarded by self._lock — ``/stats`` ``residency``: generations
        #: adopted, entries carried into a newer one, uploads landed
        self.governs = 0
        self.carried = 0
        self.uploads = 0
        if registry is not None:
            self._m_resident = registry.gauge(
                "avdb_serve_resident_bytes",
                "estimated bytes of serving segments HBM-resident",
            )
            self._m_evictions = registry.counter(
                "avdb_serve_residency_evictions_total",
                "segment caches evicted from HBM by the residency budget",
            )
            self._m_uploads = registry.counter(
                "avdb_serve_residency_uploads_total",
                "segment caches made HBM-resident (incl. fault-backs)",
            )
        else:
            self._m_resident = self._m_evictions = self._m_uploads = None

    # -- wiring -------------------------------------------------------------

    def _upload_enabled(self) -> bool:
        if self._upload is None:
            from annotatedvdb_tpu.store.variant_store import (
                _device_lookup_enabled,
            )

            self._upload = bool(_device_lookup_enabled())
        return self._upload

    def govern(self, snap) -> None:
        """Adopt the snapshot's segments (idempotent per generation).  A
        segment object the manager already governs carries its entry into
        the newer generation — resident, warmed, with its device cache and
        heat: an upsert's overlay generation and a flush's refresh (which
        keeps unchanged segments, ``VariantStore.load(reuse=...)``) upload
        and warm nothing again.  Every other entry of the previous
        generation is dropped — its device cache dies with the snapshot
        object once in-flight readers release it — and the new segments
        are marked managed and planned like any others."""
        with self._lock:
            # ordering-aware, not equality: a request still holding a
            # pre-swap snapshot must not re-install a RETIRED generation's
            # state over the current one (its entries would displace the
            # live set and strand accounted device caches)
            if (self._generation is not None
                    and snap.generation <= self._generation):
                return
            known = set(self._entries)
        # candidate scan runs OFF the lock: concurrent touch_window
        # callers must not serialize behind the per-segment bound and
        # byte-size computation.  (segment, code, shard, device, the entry
        # of a segment not governed yet.)  An entry holds its segment, so
        # a governed segment's id names no other object
        found = []
        for code, shard in snap.store.shards.items():
            device = (
                self.placement.get(code) if self.placement is not None
                else None
            )
            for seg in shard.segments:
                seg.residency = "managed"
                if seg.n < self.min_rows:
                    continue
                found.append((seg, code, shard, device, None
                              if id(seg) in known else _Entry(
                                  seg, device_cache_bytes(seg, shard.width))))
        with self._lock:
            if (self._generation is not None
                    and snap.generation <= self._generation):
                return  # another thread governed this (or a newer) one
            entries: dict[int, _Entry] = {}
            for seg, code, shard, device, fresh in found:
                e = self._entries.get(id(seg)) or fresh or _Entry(
                    seg, device_cache_bytes(seg, shard.width))
                # the newer generation's shard: whose interval index a
                # later upload of the segment warms
                e.device, e.code, e.shard = device, code, shard
                entries[id(seg)] = e
            # a queued upload batch on the uploader thread still holds the
            # displaced _Entry objects and gates on e.resident — a retired
            # generation must never spend transfers/HBM or queue ahead of
            # the new hot set.  A carried entry is the same object: it
            # keeps its state
            carried = 0
            for key, e in self._entries.items():
                if entries.get(key) is e:
                    carried += 1
                else:
                    e.resident = False
            self._entries = entries
            self._generation = snap.generation
            self.governs += 1
            self.carried += carried
            candidates = len(self._entries)
        self.log(
            f"residency: governing generation {snap.generation} "
            f"({candidates} candidate segments, {carried} carried, "
            f"budget {self.budget} bytes)"
        )

    # -- probe accounting ---------------------------------------------------

    def touch_window(self, shard, qlo, qhi, nq: int) -> None:
        """Record one probe window: every candidate segment whose key range
        overlaps [qlo, qhi] gains heat proportional to the batch size.
        A touch is cheap — one score add per overlapped segment under the
        lock; the decay + rank + budget plan runs at most once per
        ``plan_interval_s``, with the decay computed from ELAPSED time.
        Plan cost and aging rate are therefore functions of the wall
        clock, not of how many chromosome groups each request spans."""
        now = time.monotonic()
        with self._lock:
            touched = False
            for seg in shard.segments:
                entry = self._entries.get(id(seg))
                if (entry is None or entry.key_max < qlo
                        or entry.key_min > qhi):
                    continue
                entry.score += float(nq)
                touched = True
            if not touched:
                return
            elapsed = now - self._last_plan
            if elapsed < self.plan_interval_s:
                return
            self._last_plan = now
            plan = self._plan(
                list(self._entries.values()),
                DECAY ** (elapsed / DECAY_REF_S),
            )
        self._apply(plan)

    # -- budget enforcement -------------------------------------------------

    def _plan(self, entries: list, decay: float = 1.0) -> tuple[list, list]:
        """(to_evict, to_upload) under the budget; applies ``decay`` (the
        elapsed-time factor the caller computed) to every score.  Called
        under the lock (entries handed in); the actual uploads/evictions
        happen outside it (device transfers must never serialize probe
        threads)."""
        for e in entries:
            e.score *= decay
        if self.budget <= 0:
            # budget 0: nothing may be resident (the degenerate case tests
            # pin — all traffic serves from host)
            evict = [e for e in entries if e.resident]
            for e in evict:
                e.resident = e.pending = False
            return evict, []
        # greedy hottest-first pack into the budget; residents rank with a
        # HYSTERESIS bonus so a near-tied challenger never thrashes the
        # upload path, and the packed set respects the budget by
        # construction.  With a placement map the budget is PER DEVICE:
        # each device's bucket packs independently (a cold device never
        # donates its headroom to a hot one — the bytes live in different
        # HBMs)
        ranked = sorted(
            entries,
            key=lambda e: (
                -e.score * (HYSTERESIS if e.resident else 1.0), e.nbytes,
            ),
        )
        want_ids = set()
        used: dict = {}
        for e in ranked:
            spent = used.get(e.device, 0)
            if e.score <= 0.0 or e.nbytes > self.budget - spent:
                continue
            want_ids.add(id(e))
            used[e.device] = spent + e.nbytes
        evict, upload = [], []
        # planned resident and reported resident part here, in one lock
        # hold: a segment whose upload is ahead of it is ``pending`` from
        # the moment the budget counts it (_do_uploads clears it)
        uploads = self._upload_enabled()
        for e in entries:
            if e.resident and id(e) not in want_ids:
                e.resident = e.pending = False
                evict.append(e)
            elif not e.resident and id(e) in want_ids:
                e.resident = True
                e.pending = uploads
                upload.append(e)
        return evict, upload

    def _apply(self, plan: tuple[list, list]) -> None:
        evict, upload = plan
        for e in evict:
            with self._lock:
                # a newer plan may have re-uploaded e between this plan
                # and its apply — dropping the cache then would strand
                # resident=True with no device bytes behind it
                if e.resident:
                    continue
                e.seg._device = None
            if self._m_evictions is not None:
                self._m_evictions.inc()
        if upload and self._upload_enabled():
            if self._async_upload:
                with self._lock:
                    # _apply runs off-lock on concurrent probe threads:
                    # unguarded lazy init could build two executors and
                    # lose the one-at-a-time upload ordering
                    if self._uploader is None:
                        from concurrent.futures import ThreadPoolExecutor

                        self._uploader = ThreadPoolExecutor(
                            max_workers=1,
                            thread_name_prefix="avdb-residency-upload",
                        )
                self._uploader.submit(self._do_uploads, upload)
            else:
                self._do_uploads(upload)
        if self._m_resident is not None:
            self._m_resident.set(self.resident_bytes())

    def _device_for(self, index: int | None):
        """The jax device object a placement index names (None = default
        device).  The pool resolves lazily and is cached — govern/touch
        paths must never pay a backend query."""
        if index is None:
            return None
        if self._devices is None:
            import jax

            self._devices = jax.devices()
        if index >= len(self._devices):
            return None  # placement wider than this process's pool
        return self._devices[index]

    def _do_uploads(self, upload: list) -> None:
        landed = []
        for i, e in enumerate(upload):
            with self._lock:
                if not e.resident:
                    continue  # a newer plan evicted it before we got here
            try:
                # the retrying device_put path (utils.retry) rides
                # inside _build_device_cache
                dev = e.seg._device or e.seg._build_device_cache(
                    device=self._device_for(e.device))
                # every query shape a point microbatch can probe with,
                # compiled and run once here, against the copy no request
                # can see yet — never inside a request
                e.seg.warm_device_probe(self._max_probe_queries, dev)
                with self._lock:
                    # a plan may have evicted e WHILE the transfer ran: a
                    # cache installed on a resident=False entry would be
                    # invisible to every future plan — it is dropped here,
                    # never installed
                    if not e.resident:
                        continue
                    e.seg._device = dev
                    generation = self._generation
                    self.uploads += 1
                landed.append((e, generation))
                if self._m_uploads is not None:
                    self._m_uploads.inc()
            except Exception as err:
                # HBM pressure / dead backend: the host path keeps
                # serving; EVERY not-yet-uploaded entry of this plan must
                # drop residency, or the accounting claims device bytes
                # that never landed and no future plan re-uploads them
                with self._lock:
                    for stale in upload[i:]:
                        stale.resident = stale.pending = False
                self.log(f"residency: upload failed, serving from "
                         f"host ({err})")
                break
        # the probes of every uploaded segment run on the device from
        # here; each segment is REPORTED resident once its chromosome's
        # interval index is ready for panels too, so a server that says
        # its segments are resident compiles, builds and uploads nothing
        # more for either kind of read
        for e, generation in landed:
            self._warm_index(e, generation)
            with self._lock:
                e.pending = False
        if self._m_resident is not None:
            self._m_resident.set(self.resident_bytes())

    def _warm_index(self, e: _Entry, generation) -> None:
        warm = self.index_warmer
        with self._lock:
            current = e.resident and generation == self._generation
        if warm is None or not current:
            return
        try:
            warm(generation, e.code, e.shard)
        except Exception as err:
            # the segment's own copy landed; region reads of this
            # chromosome keep building and uploading lazily
            self.log(f"residency: interval index of chromosome code "
                     f"{e.code} not warmed ({err})")

    # -- introspection ------------------------------------------------------

    def resident_bytes(self) -> int:
        """Bytes the plan holds on the device, uploads in flight included
        (the budget's own count; the gauge)."""
        with self._lock:
            return sum(e.nbytes for e in self._entries.values() if e.resident)

    def candidate_chromosomes(self) -> tuple:
        """(governed generation, sorted codes of the chromosomes that hold
        a candidate segment): whose interval indexes the uploader warms."""
        with self._lock:
            return self._generation, sorted(
                {e.code for e in self._entries.values()
                 if e.code is not None}
            )

    def stats(self) -> dict:
        """Summary for ``/stats`` and tests."""
        with self._lock:
            entries = list(self._entries.values())
            out = {
                "budget_bytes": self.budget,
                "candidates": len(entries),
                # landed: uploaded and its probe programs compiled
                "resident": sum(
                    1 for e in entries if e.resident and not e.pending
                ),
                "resident_bytes": sum(
                    e.nbytes for e in entries if e.resident
                ),
                "generation": self._generation,
                "governs": self.governs,
                "carried": self.carried,
                "uploads": self.uploads,
            }
            if self.placement is not None:
                per_device: dict = {}
                for e in entries:
                    if e.resident:
                        key = str(e.device)
                        per_device[key] = per_device.get(key, 0) + e.nbytes
                out["per_device_bytes"] = per_device
            return out
