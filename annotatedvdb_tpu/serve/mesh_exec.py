"""Mesh execution for the serving read path.

Single-device serving answers a bulk lookup with one probe per chromosome
group (N python-loop device/host calls per drain) and a region panel with
one BITS call per touched group.  On a multi-device mesh both collapse to
ONE sharded program each:

- **bulk lookup** — the snapshot's identity columns live device-resident,
  chromosome→device placed (``parallel.device_store.DeviceShardStore``
  committed batch-sharded: each device holds exactly the chromosome
  groups ``parallel.mesh.chromosome_placement`` assigns it), and every
  drain runs ``parallel.distributed.distributed_serve_lookup_step``: one
  ``all_to_all`` routes each query to its owner, the owner probes its
  resident slice, and materializing the outputs is the cross-device
  gather.  Row ids come back as host-store global ids, so rendering is
  EXACTLY the single-device path's — first-wins across segments included
  (the device slices are stable-sorted over segment age).
- **region panels** — every chromosome group's deduplicated interval
  index stacks into one ``[device-rows, R]`` position array, committed
  batch-sharded once per generation; a panel is ONE
  ``ops.intervals.bits_spans_stacked`` call answering every group's
  intervals on the device that owns them.

Failure policy is the PR-7 breaker contract: the ``mesh.dispatch`` fault
point fires before each sharded call, any failure feeds the
:class:`~annotatedvdb_tpu.serve.resilience.DeviceBreaker` under the
reserved group key :data:`MESH_GROUP` (0 — never a real chromosome) and
the caller falls back to the single-device path, whose answers are
byte-identical (pinned by ``tests/test_mesh.py`` and the fault matrix).
An open mesh group stops paying the sharded attempt per drain; half-open
re-probes re-close it.

Knob resolution lives HERE, once (the ``resolve_batch_knobs``
convention): ``AVDB_SERVE_MESH`` gates the path (``auto`` engages only
with >1 device on a non-CPU backend; ``1`` forces — the CPU mesh tests
and bench; ``0`` disables), ``AVDB_MESH_BULK_MIN`` is the smallest bulk
that pays a mesh dispatch.
"""

from __future__ import annotations

import os

import numpy as np

from annotatedvdb_tpu.utils import faults
from annotatedvdb_tpu.utils.arrays import POS_SENTINEL, next_pow2
from annotatedvdb_tpu.utils.locks import make_lock

#: the DeviceBreaker group key for the mesh dispatch as a whole (0 is
#: never a real chromosome code, so it can't collide with per-group state)
MESH_GROUP = 0


def resolve_serve_mesh() -> str:
    """``AVDB_SERVE_MESH`` as one of ``auto``/``1``/``0`` (default
    ``auto``); anything else fails loudly (the spill-tier precedent: a
    typo'd knob must never silently pick a different serving layout)."""
    mode = os.environ.get("AVDB_SERVE_MESH", "").strip().lower() or "auto"
    if mode not in ("auto", "1", "0"):
        raise ValueError(
            f"AVDB_SERVE_MESH must be auto, 1, or 0, not {mode!r}"
        )
    return mode


def resolve_mesh_bulk_min(bulk_min: int | None = None) -> int:
    """Smallest bulk-lookup batch that pays a mesh dispatch (default 64:
    below it the per-group host probes win; 0 sends every batch)."""
    if bulk_min is None:
        spec = os.environ.get("AVDB_MESH_BULK_MIN", "").strip()
        if spec:
            try:
                bulk_min = int(spec)
            except ValueError:
                raise ValueError(
                    f"AVDB_MESH_BULK_MIN must be an integer, not {spec!r}"
                ) from None
        else:
            bulk_min = 64
    return max(int(bulk_min), 0)


def serve_mesh_on():
    """The mesh serving resolution shared by every consumer: the
    :class:`jax.sharding.Mesh` the serve path will execute over, or None
    when mesh serving is off.  ``auto`` requires BOTH a >1-device mesh
    and a non-CPU backend — on CPU the per-segment numpy probes are the
    production path and the mesh is a test/bench surface forced with
    ``AVDB_SERVE_MESH=1``.  The serve CLI's residency split consults
    THIS (not the bare device count), so a mesh-off server keeps the
    historical single-bucket budget plan."""
    from annotatedvdb_tpu.parallel.mesh import global_mesh

    mode = resolve_serve_mesh()
    if mode == "0":
        return None
    mesh = global_mesh()
    if mesh is None:
        return None
    if mode == "auto":
        import jax

        if jax.default_backend() == "cpu":
            return None
    return mesh


def serve_mesh_executor(registry=None, breaker=None, log=None,
                        budget_bytes: int | None = None):
    """The server builder's one construction point: a :class:`MeshExecutor`
    when :func:`serve_mesh_on` resolves a mesh, else None (single-device
    serving pays nothing).  ``budget_bytes`` is the caller's PER-DEVICE
    resident budget — the builders pass the residency manager's already-
    split share, so the fleet's per-worker division and an explicit
    ``--hbmBudget`` flag govern the mesh state too (never the raw env)."""
    mesh = serve_mesh_on()
    if mesh is None:
        return None
    return MeshExecutor(mesh, registry=registry, breaker=breaker, log=log,
                        budget_bytes=budget_bytes)


class _BulkState:
    """One generation's device-resident identity columns (committed
    batch-sharded) — or a tombstone (``store is None``) when the
    generation's resident bytes exceed the per-device budget."""

    __slots__ = ("generation", "store", "nbytes")

    def __init__(self, generation: int, store, nbytes: int):
        self.generation = generation
        self.store = store
        self.nbytes = nbytes


class _SpanState:
    """One generation's stacked interval-index positions (committed
    batch-sharded) plus the code→stack-row placement."""

    __slots__ = ("generation", "pos_stack", "row_of", "b_pad", "nbytes")

    def __init__(self, generation: int, pos_stack, row_of: dict,
                 b_pad: int, nbytes: int):
        self.generation = generation
        self.pos_stack = pos_stack
        self.row_of = row_of
        self.b_pad = b_pad
        self.nbytes = nbytes


class MeshExecutor:
    """Owns the serving mesh: placement, per-generation device state, the
    two sharded call sites, and the breaker/fallback policy."""

    #: minimum seconds between device-state rebuilds: a generation that
    #: churns faster than this (the live write path mints one per
    #: memtable epoch) serves from the byte-identical single-device path
    #: instead of re-sorting and re-uploading the whole store per epoch
    #: — rebuild cost is bounded by the wall clock, not the write rate
    REBUILD_MIN_S = 2.0

    def __init__(self, mesh, registry=None, breaker=None, log=None,
                 bulk_min: int | None = None,
                 budget_bytes: int | None = None,
                 rebuild_min_s: float | None = None):
        from annotatedvdb_tpu.parallel.mesh import chromosome_placement

        self.mesh = mesh
        self.n_devices = int(mesh.devices.size)
        self.placement = chromosome_placement(self.n_devices)
        self.breaker = breaker
        self.log = log if log is not None else (lambda msg: None)
        self.bulk_min = resolve_mesh_bulk_min(bulk_min)
        #: per-DEVICE resident byte budget, handed down the SAME chain
        #: the segment caches use (env/flag -> fleet per-worker split ->
        #: per-device split in cli/serve -> residency.budget -> here);
        #: 0/None = unmanaged, nothing is refused
        self.budget = int(budget_bytes or 0)
        self.rebuild_min_s = (
            self.REBUILD_MIN_S if rebuild_min_s is None
            else max(float(rebuild_min_s), 0.0)
        )
        self._lock = make_lock("serve.mesh.state")
        #: serializes device-state BUILDS (not lookups): after a swap
        #: every concurrent drain misses the generation check at once,
        #: and an O(store) sort + upload per caller would be an N-fold
        #: memory/transfer spike for identical state (the engine's
        #: _index_build_lock precedent) — losers wait and take the
        #: winner's state
        self._build_lock = make_lock("serve.mesh.build")
        #: guarded by self._lock
        self._bulk: _BulkState | None = None
        #: guarded by self._lock
        self._spans: _SpanState | None = None
        #: monotonic stamp of the last started build per state kind, the
        #: rebuild rate limiter's input (per kind: a fresh generation
        #: builds BOTH states back to back).  -inf, not 0.0: monotonic
        #: time starts near zero on a freshly booted machine, and the
        #: FIRST build must never be declined.
        #: guarded by self._lock
        self._last_build = {"bulk": float("-inf"), "spans": float("-inf")}
        if registry is not None:
            self._m_devices = registry.gauge(
                "avdb_mesh_devices",
                "devices in the serving mesh (0 = single-device path)",
            )
            self._m_devices.set(self.n_devices)
            self._m_groups = registry.gauge(
                "avdb_mesh_groups_placed",
                "chromosome groups placed onto mesh devices this generation",
            )
            self._m_resident = registry.gauge(
                "avdb_mesh_resident_bytes",
                "bytes of mesh-resident serving state (identity columns + "
                "interval stacks, all devices)",
            )
            self._m_dispatch = {
                kind: registry.counter(
                    "avdb_mesh_dispatch_total",
                    "sharded mesh calls issued", {"kind": kind},
                )
                for kind in ("bulk", "spans")
            }
            self._m_fallback = {
                kind: registry.counter(
                    "avdb_mesh_fallback_total",
                    "mesh calls that fell back to the single-device path",
                    {"kind": kind},
                )
                for kind in ("bulk", "spans")
            }
        else:
            self._m_devices = self._m_groups = self._m_resident = None
            self._m_dispatch = self._m_fallback = None

    # -- state builds -------------------------------------------------------

    def _resident_bytes(self) -> int:
        with self._lock:
            return sum(
                s.nbytes for s in (self._bulk, self._spans) if s is not None
            )

    def _note_resident(self) -> None:
        if self._m_resident is not None:
            self._m_resident.set(self._resident_bytes())

    def _rebuild_allowed(self, kind: str) -> bool:
        """Whether a ``kind`` state rebuild may run now (the rate limiter
        above: between allowed rebuilds a churning generation serves
        single-device — byte-identical, just not mesh-accelerated)."""
        import time

        with self._lock:
            return (
                time.monotonic() - self._last_build[kind]
                >= self.rebuild_min_s
            )

    def _stamp_build(self, kind: str) -> None:
        import time

        with self._lock:
            self._last_build[kind] = time.monotonic()

    def _bulk_state(self, snap) -> _BulkState | None:
        with self._lock:
            state = self._bulk
            if state is not None and state.generation == snap.generation:
                return state if state.store is not None else None
        if not self._rebuild_allowed("bulk"):
            return None
        with self._build_lock:
            # double-checked: the winner of a concurrent miss built it
            # while this thread waited.  Ordering-aware, not equality:
            # a drain still holding a PRE-swap snapshot must neither
            # overwrite the newer installed state with a stale rebuild
            # nor burn the rebuild window on one (residency.govern's
            # invariant) — it serves single-device and drains away.
            with self._lock:
                state = self._bulk
                if state is not None:
                    if state.generation == snap.generation:
                        return state if state.store is not None else None
                    if state.generation > snap.generation:
                        return None
            if not self._rebuild_allowed("bulk"):
                return None
            return self._build_bulk_state(snap)

    def _build_bulk_state(self, snap) -> _BulkState | None:
        """The O(store) sort + device upload, under the build lock."""
        from annotatedvdb_tpu.parallel.device_store import (
            build_device_shard_store,
        )
        from annotatedvdb_tpu.parallel.mesh import batch_sharding

        import jax

        self._stamp_build("bulk")
        host = build_device_shard_store(snap.store, self.n_devices)
        nbytes = sum(
            np.asarray(getattr(host, f)).nbytes
            for f in host._fields if f != "n_rows"
        )
        # ONE budget pool covers BOTH mesh states: the identity columns
        # and the interval stack live in the same per-device HBM, so
        # each build charges the other's resident bytes before its own
        with self._lock:
            other = self._spans.nbytes if self._spans is not None else 0
        if self.budget \
                and (nbytes + other) // self.n_devices > self.budget:
            self.log(
                f"mesh: generation {snap.generation} identity columns "
                f"({nbytes} bytes + {other} stack bytes / "
                f"{self.n_devices} devices) exceed the per-device "
                f"budget {self.budget}; bulk lookups stay on the "
                "single-device path"
            )
            state = _BulkState(snap.generation, None, 0)
            with self._lock:
                self._bulk = state
            self._note_resident()
            return None
        sharding = batch_sharding(self.mesh)
        committed = type(host)(*(
            jax.device_put(np.asarray(getattr(host, f)), sharding)
            if f != "n_rows" else host.n_rows
            for f in host._fields
        ))
        state = _BulkState(snap.generation, committed, nbytes)
        with self._lock:
            if self._bulk is not None \
                    and self._bulk.generation > state.generation:
                return None  # a newer build won while we uploaded
            self._bulk = state
        if self._m_groups is not None:
            self._m_groups.set(
                sum(1 for c, sh in snap.store.shards.items() if sh.n)
            )
        self._note_resident()
        self.log(
            f"mesh: generation {snap.generation} placed over "
            f"{self.n_devices} devices ({nbytes} resident bytes)"
        )
        return state

    def _span_state(self, snap, index_of) -> _SpanState | None:
        with self._lock:
            state = self._spans
            if state is not None and state.generation == snap.generation:
                return state if state.pos_stack is not None else None
        if not self._rebuild_allowed("spans"):
            return None
        with self._build_lock:
            # same ordering-aware double-check as the bulk state
            with self._lock:
                state = self._spans
                if state is not None:
                    if state.generation == snap.generation:
                        return state if state.pos_stack is not None \
                            else None
                    if state.generation > snap.generation:
                        return None
            if not self._rebuild_allowed("spans"):
                return None
            return self._build_span_state(snap, index_of)

    def _build_span_state(self, snap, index_of) -> _SpanState | None:
        """The stacked-index build + device upload, under the build
        lock."""
        from annotatedvdb_tpu.parallel.mesh import (
            batch_sharding,
            groups_per_device,
        )

        self._stamp_build("spans")

        import jax

        codes = [c for c, sh in snap.store.shards.items() if sh.n]
        per_dev = groups_per_device(self.placement, codes)
        g_max = max((len(v) for v in per_dev.values()), default=0)
        if g_max == 0:
            return None
        b_pad = self.n_devices * g_max
        indexes = {}
        r_cap = 1
        for code in codes:
            index = index_of(code)
            if index is None or index.n == 0:
                continue
            indexes[code] = index
            r_cap = max(r_cap, next_pow2(index.n))
        if not indexes:
            return None
        stack = np.full((b_pad, r_cap), POS_SENTINEL, np.int32)
        row_of: dict = {}
        for dev, dev_codes in per_dev.items():
            for k, code in enumerate(dev_codes):
                index = indexes.get(code)
                if index is None:
                    continue
                row = dev * g_max + k
                row_of[code] = row
                stack[row, : index.n] = index.pos
        nbytes = stack.nbytes
        with self._lock:
            other = self._bulk.nbytes if self._bulk is not None else 0
        if self.budget \
                and (nbytes + other) // self.n_devices > self.budget:
            self.log(
                f"mesh: generation {snap.generation} interval stack "
                f"({nbytes} bytes + {other} identity bytes) exceeds the "
                f"per-device budget {self.budget}; panels stay on the "
                "single-device path"
            )
            state = _SpanState(snap.generation, None, {}, b_pad, 0)
            with self._lock:
                self._spans = state
            self._note_resident()
            return None
        committed = jax.device_put(stack, batch_sharding(self.mesh))
        state = _SpanState(snap.generation, committed, row_of, b_pad,
                           nbytes)
        with self._lock:
            if self._spans is not None \
                    and self._spans.generation > state.generation:
                return None  # a newer build won while we uploaded
            self._spans = state
        self._note_resident()
        return state

    def _drop_states(self) -> None:
        """Forget device state after a failed dispatch — the next attempt
        (post-breaker-cooldown) rebuilds and re-uploads cleanly."""
        with self._lock:
            self._bulk = None
            self._spans = None
            # the breaker's cooldown is the retry gate after a failure —
            # the rebuild rate limiter must not ALSO delay the recovery
            self._last_build = {
                "bulk": float("-inf"), "spans": float("-inf"),
            }
        self._note_resident()

    # -- dispatch policy ----------------------------------------------------

    def _allow(self) -> bool:
        return self.breaker is None or self.breaker.allow_device(MESH_GROUP)

    def _failed(self, kind: str, exc: Exception) -> None:
        if self.breaker is not None:
            self.breaker.record_failure(MESH_GROUP, exc)
        if self._m_fallback is not None:
            self._m_fallback[kind].inc()
        self._drop_states()
        self.log(f"mesh: {kind} dispatch failed, serving single-device "
                 f"({exc})")

    def _succeeded(self, kind: str) -> None:
        if self.breaker is not None:
            self.breaker.record_success(MESH_GROUP)
        if self._m_dispatch is not None:
            self._m_dispatch[kind].inc()

    # -- bulk lookup --------------------------------------------------------

    def would_dispatch(self, snap) -> bool:
        """Cheap pre-encode gate for the engine: whether a bulk dispatch
        for this snapshot could possibly run (breaker closed, state
        present or a rebuild window open, not tombstoned/stale).  The
        engine checks this BEFORE paying the full-batch identity encode
        + hash — a permanently declined executor (over-budget store,
        churning generations, open breaker) must not cost the hot path
        a wasted encode per drain.  The breaker check is the
        NON-consuming one: the real admission (and the half-open trial
        slot) belongs to :meth:`bulk_lookup`."""
        if self.breaker is not None \
                and not self.breaker.would_allow(MESH_GROUP):
            return False
        with self._lock:
            state = self._bulk
            if state is not None:
                if state.generation == snap.generation:
                    return state.store is not None
                if state.generation > snap.generation:
                    return False
        return self._rebuild_allowed("bulk")

    def bulk_lookup(self, snap, chrom, pos, h, ref, alt, ref_len, alt_len):
        """(found [Q] bool, global row id [Q] int64) for host-hashed query
        identities, via ONE sharded call — or ``None``, meaning the caller
        must take the single-device path (mesh off/ tripped/ over budget/
        failed; the fallback's answers are byte-identical)."""
        if not self._allow():
            return None
        state = self._bulk_state(snap)
        if state is None:
            return None
        from annotatedvdb_tpu.ops.dedup import CHROM_MIX
        from annotatedvdb_tpu.parallel.distributed import (
            distributed_serve_lookup_step,
        )
        from annotatedvdb_tpu.parallel.mesh import pad_rows

        nq = int(np.asarray(pos).shape[0])
        m = pad_rows(next_pow2(max(nq, self.n_devices)), self.mesh)
        chrom_p = np.zeros(m, np.int8)
        chrom_p[:nq] = np.asarray(chrom, np.int8)
        pos_p = np.full(m, POS_SENTINEL, np.int32)
        pos_p[:nq] = np.asarray(pos, np.int32)
        hm_p = np.zeros(m, np.uint32)
        hm_p[:nq] = np.asarray(h, np.uint32) ^ (
            np.asarray(chrom, np.uint32) * np.uint32(CHROM_MIX)
        )
        width = np.asarray(ref).shape[1]
        ref_p = np.zeros((m, width), np.uint8)
        ref_p[:nq] = ref
        alt_p = np.zeros((m, width), np.uint8)
        alt_p[:nq] = alt
        rl_p = np.ones(m, np.int32)
        rl_p[:nq] = np.asarray(ref_len, np.int32)
        al_p = np.ones(m, np.int32)
        al_p[:nq] = np.asarray(alt_len, np.int32)
        try:
            # crash point: models a device failure inside the sharded
            # gather — the breaker must absorb it on the byte-identical
            # single-device path, never wrong bytes
            faults.fire("mesh.dispatch")
            rid_out, found, store_row = distributed_serve_lookup_step(
                self.mesh, chrom_p, pos_p, hm_p, ref_p, alt_p, rl_p, al_p,
                state.store,
            )
            rid_out = np.asarray(rid_out)
            found = np.asarray(found)
            store_row = np.asarray(store_row)
        except Exception as exc:
            self._failed("bulk", exc)
            return None
        self._succeeded("bulk")
        out_found = np.zeros(nq, np.bool_)
        out_gid = np.full(nq, -1, np.int64)
        take = rid_out >= 0
        src = rid_out[take]
        out_found[src] = found[take]
        out_gid[src] = store_row[take]
        return out_found, out_gid

    # -- region panels ------------------------------------------------------

    def panel_spans(self, snap, queries: dict, index_of):
        """``{code: (lo, hi, level, leaf)}`` for a panel's per-group query
        arrays (``{code: (starts, ends)}``, pre-clamped ints), via ONE
        sharded stacked-BITS call — or ``None`` (single-device fallback).
        Codes without an interval index are absent from the result (the
        caller keeps its unloaded-chromosome handling)."""
        if not queries or not self._allow():
            return None
        state = self._span_state(snap, index_of)
        if state is None:
            return None
        from annotatedvdb_tpu.ops.intervals import bits_spans_stacked_jit
        from annotatedvdb_tpu.parallel.mesh import shard_rows

        rows = {
            code: q for code, q in queries.items() if code in state.row_of
        }
        if not rows:
            return None
        q_cap = next_pow2(max(len(q[0]) for q in rows.values()))
        starts = np.zeros((state.b_pad, q_cap), np.int32)
        ends = np.zeros((state.b_pad, q_cap), np.int32)
        for code, (q_starts, q_ends) in rows.items():
            r = state.row_of[code]
            starts[r, : len(q_starts)] = q_starts
            ends[r, : len(q_ends)] = q_ends
        try:
            # crash point: the spans twin of the bulk dispatch above
            faults.fire("mesh.dispatch")
            d_starts, d_ends = shard_rows(self.mesh, starts, ends)
            lo, hi, level, leaf = bits_spans_stacked_jit(
                state.pos_stack, d_starts, d_ends
            )
            lo, hi = np.asarray(lo), np.asarray(hi)
            level, leaf = np.asarray(level), np.asarray(leaf)
        except Exception as exc:
            self._failed("spans", exc)
            return None
        self._succeeded("spans")
        out = {}
        for code, (q_starts, _q_ends) in rows.items():
            r = state.row_of[code]
            k = len(q_starts)
            out[code] = (lo[r, :k], hi[r, :k], level[r, :k], leaf[r, :k])
        return out

    # -- introspection ------------------------------------------------------

    def stats(self) -> dict:
        """Mesh block for ``/stats`` and ``doctor status``."""
        from annotatedvdb_tpu.parallel.mesh import groups_per_device

        with self._lock:
            bulk = self._bulk
            spans = self._spans
        placed = groups_per_device(self.placement, self.placement.keys())
        # what each device really holds, from the committed arrays' own
        # shards — state that all landed on the first device would show
        # here, whatever the host-side byte count says
        per_device = {str(d.id): 0 for d in self.mesh.devices.flat}
        arrays = []
        if bulk is not None and bulk.store is not None:
            arrays += [getattr(bulk.store, f) for f in bulk.store._fields
                       if f != "n_rows"]
        if spans is not None and spans.pos_stack is not None:
            arrays.append(spans.pos_stack)
        for arr in arrays:
            for shard in arr.addressable_shards:
                per_device[str(shard.device.id)] += int(shard.data.nbytes)
        return {
            "devices": self.n_devices,
            "bulk_min": self.bulk_min,
            "budget_bytes": self.budget,
            "resident_bytes": (
                (bulk.nbytes if bulk is not None else 0)
                + (spans.nbytes if spans is not None else 0)
            ),
            "per_device_bytes": per_device,
            "generation": bulk.generation if bulk is not None else None,
            "groups_per_device": {
                str(dev): len(codes) for dev, codes in placed.items()
            },
        }
