"""Chromosome-sharded columnar variant store with log-structured segments.

TPU-native replacement for the reference's ``AnnotatedVDB.Variant`` Postgres
table (UNLOGGED, LIST-partitioned by chromosome, JSONB annotation columns,
``Load/lib/sql/annotatedvdb_schema/tables/createVariant.sql:4-50``):

- one shard per chromosome (the partition invariant that lets loads of
  different chromosomes proceed without contention — the property the
  reference engineers around Postgres locks, ``cadd_updater.py:105-107``);
- each shard is a list of **sorted segments** (LSM-style): a flush appends
  one new segment in O(batch) and a size-tiered cascade merge keeps the
  segment count logarithmic, so per-batch flush cost is flat — the columnar
  analog of Postgres appending heap pages + the occasional VACUUM, instead
  of rewriting the whole partition per COPY;
- membership checks and annotation joins are searchsorted merges against
  each segment (replacing per-row SQL round-trips,
  ``database/variant.py:287-309``); large segment × large batch joins run
  the device kernel (``ops/dedup.lookup_in_sorted``) against an HBM-resident
  copy of the segment's identity columns;
- annotation columns are object arrays of per-row dicts (the JSONB analog),
  updated with deep-merge semantics mirroring the server-side
  ``jsonb_merge()`` the reference leans on (``vep_variant_loader.py:227``);
- every row carries ``row_algorithm_id`` for undo
  (``undo_variant_load.py:21-67``);
- persistence is incremental: ``save`` writes only new/dirty segments
  (one npz + sparse-JSONL pair each), so a per-checkpoint persist costs
  O(new rows), not O(store).

Row addressing: ``lookup`` returns **global row ids** — a row's offset in
segment-list order.  Ids stay valid until the next ``append``/``compact``/
``delete`` on the shard (merges renumber rows); callers must re-lookup after
mutating.  Whole-shard passes (CADD join, Postgres egress, VCF export) call
``compact()`` once up front, after which ids are position-sorted and the
flat ``cols``/``ref``/``alt``/``annotations`` views are available.
"""

from __future__ import annotations

import itertools
import json
import operator
import os
import zlib
from typing import Iterable, NamedTuple

import numpy as np

from annotatedvdb_tpu.types import chromosome_label, decode_allele
from annotatedvdb_tpu.utils import faults
from annotatedvdb_tpu.utils import io as tio
from annotatedvdb_tpu.utils.arrays import next_pow2
from annotatedvdb_tpu.utils.strings import deep_update


class StoreCorruptError(ValueError):
    """The on-disk store is internally inconsistent (torn/missing/mismatched
    segment files, unreadable manifest).  The message always names
    ``tools/store_fsck.py`` — the diagnosis/repair entry point — so an
    operator hitting this at 3am knows the next command to run."""


def _fsck_hint(path: str) -> str:
    return (
        f"run `python -m annotatedvdb_tpu doctor --storeDir {path}` "
        "(tools/store_fsck.py) to diagnose, and add --repair to prune "
        "orphans / roll back to the last consistent state"
    )

# The ten JSONB annotation columns of AnnotatedVDB.Variant
# (createVariant.sql:4-24).
JSONB_COLUMNS = [
    "display_attributes",
    "allele_frequencies",
    "cadd_scores",
    "adsp_most_severe_consequence",
    "adsp_ranked_consequences",
    "loss_of_function",
    "vep_output",
    "adsp_qc",
    "gwas_flags",
    "other_annotation",
]

# Non-JSONB per-row object columns (host-side tails).
_DIGEST_PK = "_digest_pk"
_LONG_ALLELES = "_long_alleles"
OBJECT_COLUMNS = JSONB_COLUMNS + [_DIGEST_PK, _LONG_ALLELES]

_NUMERIC_COLUMNS = [
    ("pos", np.int32),
    ("h", np.uint32),
    ("ref_len", np.int32),
    ("alt_len", np.int32),
    ("ref_snp", np.int64),          # rs number; -1 = NULL
    ("is_multi_allelic", np.bool_),
    ("is_adsp_variant", np.int8),   # -1 NULL / 0 false / 1 true
    ("bin_level", np.int8),
    ("leaf_bin", np.int32),
    ("needs_digest", np.bool_),
    ("row_algorithm_id", np.int32),
]

# Identity columns: immutable after append; everything else may be updated
# in place without invalidating lookups or device caches.
_IDENTITY_COLUMNS = ("pos", "h", "ref_len", "alt_len")

# Device-kernel lookup thresholds.  Below these, host numpy wins: the query
# columns (~120B/row) must ship to the device per probe, so the kernel pays
# off only once the segment is far too large for host cache-resident
# searchsorted (and never on CPU backends — see _device_lookup_enabled).
DEVICE_SEGMENT_MIN = 1 << 18
DEVICE_QUERY_MIN = 1 << 12

# Smallest query capacity a device probe is padded to.  A point microbatch
# holds whatever the moment gives it — 1..max_batch ids a chromosome group —
# and each power-of-two bucket is a program of its own, so the buckets a
# server's microbatches can take are few and known: from this floor up to
# the batcher's ``max_batch`` (32, 64, 128, 256 at the default), and the
# residency manager runs each once before it reports a segment resident
# (``Segment.warm_device_probe``): no reader's request compiles.  All of
# them, eagerly: a server does not know how many readers it will get, a
# group over 32 ids takes a larger bucket, and one compiled after
# ``resident`` would be compiled inside somebody's request.  Where the
# floor sits is measured (one v5e, 2^22 rows).  With 32 concurrent point
# readers and the six-array probe (PR 28): every probe padded to 256, the
# program ran 0.61 ms a probe and a drain's three probes took 9.1-9.2 ms;
# padded to 32, 0.17 ms and 7.7-8.0 ms.  Under 32 a bucket saves nothing:
# a lone probe's wall — pack, one upload, one dispatch, one fetch — is
# 1.27-1.38 ms at 1 to 32 queries (PR 29's microbenchmark of this form;
# 1.36 at 64, 1.83-1.91 at 256 and 512, 4.44 at 2,048), of it 0.35 ms the
# launch (0.40 at 2,048) and 0.20 ms the program (0.31 at 256, 0.61 at
# 512, 2.62 at 2,048); the rest is the host waiting out this machine's
# round trips — an upload waited for 0.56 ms, the fetch of a finished
# 128-byte answer 0.40 ms.  The six-array form read 2.43 ms at 32 queries
# in the same run: the count of transfers is what a small probe costs, so
# a lookup launches all its probes before it waits for one
# (``Segment.probe_launch``).  Sentinel queries cannot match a real row.
DEVICE_QUERY_FLOOR = 1 << 5

# The device probe must first UPLOAD the segment's identity columns
# (~110B/row); that transfer has to amortize before the kernel beats a
# numpy searchsorted.  Ski-rental rule: each segment counts
# the query volume its numpy probes have served, and uploads once
# cumulative volume reaches 1/AMORTIZE of the segment size — by then the
# forgone device work would have paid for the transfer, so total cost is
# within a constant factor of either pure strategy.  Mid-load segments are
# replaced by merges before reaching the threshold (write-heavy loads stay
# numpy); static stores probed repeatedly (update loads) cross it and ride
# HBM.  ``ChromosomeShard.pin_device_lookup`` forces the upload up front;
# AVDB_DEVICE_LOOKUP=always|auto|off overrides the rule entirely.
DEVICE_UPLOAD_AMORTIZE = 4

# Cascade merges stop once the older segment exceeds this row count:
# beyond it, re-merging (and re-persisting) the biggest segment every few
# flushes costs more than probing a handful of extra segments.  Big
# segments become effectively immutable — written to disk once — and
# read paths that need a single flat view call compact() explicitly.
MERGE_SEGMENT_CAP = 1 << 20

# Segments whose key ranges are DISJOINT are never cascade-merged: a
# position-sorted load appends strictly-ascending runs, and membership
# probes skip non-overlapping segments entirely (range pruning in
# ``ChromosomeShard.lookup`` / the loader's pending-segment loop), so
# merging them buys nothing and costs an O(n) copy per flush.  The shard
# therefore accumulates one segment per flush on sorted input; once the
# count passes this bound, ``maintain`` collapses consecutive runs back
# into MERGE_SEGMENT_CAP-sized segments (amortized O(1) copies per row).
MAX_SEGMENTS = 512


_DEVICE_LOOKUP_MODE: str | None = None


def _fsync_wanted() -> bool:
    """AVDB_FSYNC opt-in: full power-loss durability for segment data and
    rename metadata (see ``VariantStore.save``).  '0'/'false' disable.
    Canonical definition lives in ``utils.io`` (the traced-I/O layer needs
    it without importing the store)."""
    return tio.fsync_wanted()


def _verify_mode() -> str:
    """AVDB_VERIFY load-time integrity checking: ``size`` (default) checks
    byte counts against the manifest's integrity records — free, catches
    truncation; ``deep`` additionally checksums every segment file —
    catches bit rot, costs one crc32 pass per load; ``off`` disables both
    (forensic loads of known-damaged stores via fsck)."""
    mode = os.environ.get("AVDB_VERIFY", "size").lower()
    return mode if mode in ("off", "size", "deep") else "size"


def _spill_bytes() -> int:
    """AVDB_STORE_SPILL_BYTES: segment containers at or above this size
    load as copy-on-write memmaps instead of materialized arrays (the
    out-of-core tier — see ``_read_segment``).  Accepts ``512m`` / ``2g``
    suffixes (the shared ``utils.strings.parse_bytes`` grammar; malformed
    values raise rather than silently disabling the tier); unset/0/off
    disables (every segment materializes, the historical behavior)."""
    raw = os.environ.get("AVDB_STORE_SPILL_BYTES", "").strip().lower()
    if not raw or raw in ("0", "off"):
        return 0
    from annotatedvdb_tpu.utils.strings import parse_bytes

    try:
        return parse_bytes(raw)
    except ValueError as err:
        raise ValueError(f"AVDB_STORE_SPILL_BYTES: {err}") from None


def crc32_file(path: str) -> int:
    """Chunked crc32 of a whole file — the read-side twin of the write-time
    integrity records (shared by load-time deep verify and fsck)."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(1 << 20)
            if not buf:
                break
            crc = zlib.crc32(buf, crc)
    return crc


#: sidecar lines joined into one write by ``_write_segment``
_SIDECAR_BLOCK = 4096


class _CrcWriter:
    """File-object wrapper accumulating crc32 + byte count over every write
    — the integrity record is computed on the bytes ALREADY IN HAND on the
    way to disk (one C-speed crc pass), never by re-reading the file (the
    npz-era per-member crc re-reads were ~45% of persist CPU and were
    removed for throughput; this must not reintroduce them)."""

    __slots__ = ("_f", "crc", "nbytes")

    def __init__(self, f):
        self._f = f
        self.crc = 0
        self.nbytes = 0

    def write(self, b):
        self.crc = zlib.crc32(b, self.crc)
        self.nbytes += len(b)
        return self._f.write(b)

    def __getattr__(self, name):  # flush/tell/truncate/fileno passthrough
        return getattr(self._f, name)


def _device_lookup_mode() -> str:
    global _DEVICE_LOOKUP_MODE
    if _DEVICE_LOOKUP_MODE is None:
        _DEVICE_LOOKUP_MODE = os.environ.get("AVDB_DEVICE_LOOKUP", "auto")
    return _DEVICE_LOOKUP_MODE


# Minimum measured host->device bandwidth for 'auto' device lookups: every
# probe call must also UPLOAD its query identity columns (~110B/row), so
# below this rate the query transfer alone costs more than a numpy
# searchsorted no matter how the segment cache amortizes.
DEVICE_MIN_BANDWIDTH = 1e9  # bytes/sec
_TRANSFER_FAST: bool | None = None


def _transfer_fast() -> bool:
    """One-time 1MB upload timing; latched per process.  A device error
    propagates to the first caller — it is never read as a slow link."""
    global _TRANSFER_FAST
    if _TRANSFER_FAST is None:
        import time

        import jax

        buf = np.zeros(1 << 20, np.uint8)
        dev = jax.device_put(buf)          # warm the path once
        dev.block_until_ready()
        t0 = time.perf_counter()
        dev = jax.device_put(buf)
        dev.block_until_ready()
        dt = max(time.perf_counter() - t0, 1e-9)
        _TRANSFER_FAST = (len(buf) / dt) >= DEVICE_MIN_BANDWIDTH
    return _TRANSFER_FAST

# Latch: None = not yet asked; False on a CPU backend (numpy searchsorted
# beats per-shape XLA compiles there).  Decided by the backend's NAME only:
# a backend that fails to initialize raises at first use, and a failing
# device probe never flips it (see Segment.probe).
_DEVICE_LOOKUP_OK = None

# Serve-side device-probe failure observer (serve/resilience.DeviceBreaker):
# the server guarantees an answer, so with an observer installed a device
# error inside Segment.probe is handed to it (per-group breaker state,
# half-open re-probes, counted in avdb_serve_breaker_trips_total) and the
# probe answers from the byte-identical numpy path.  Without an observer
# that owns the failure — every loader — the error propagates.
_DEVICE_PROBE_FAILURE_HOOK = None

#: cumulative device membership probes of this process (the
#: ``utils.retry.stats`` pattern): load summaries report their session's
#: delta, so a reader can tell whether a load reached the device lookup
#: path at all; ``padded_queries`` is what the device was asked after
#: padding (:func:`probe_query_capacity`) and ``transfers`` the host<->
#: device array transfers those probes made (one upload, one fetch), all
#: four added once per probe that was collected clean;
#: ``overlapped_probes`` counts probes launched while an earlier one of
#: the same lookup was not yet collected (:func:`count_overlapped`)
probe_stats = {"device_probes": 0, "device_queries": 0, "padded_queries": 0,
               "transfers": 0, "overlapped_probes": 0}


#: cumulative work of the annotation-sidecar writer in this process (the
#: ``probe_stats`` pattern; a load's run record reports its session's
#: delta as ``execution.sidecar``): ``rows`` of the segments (or gathered
#: chunks) handed to :func:`sidecar_lines`, ``visited`` the rows it looked
#: at — those that hold a value — and ``lines`` the lines it gave; all
#: three added once a call, never a row
sidecar_stats = {"rows": 0, "visited": 0, "lines": 0}


def sidecar_state(base: dict | None = None) -> dict:
    """:data:`sidecar_stats` relative to ``base`` (an earlier copy)."""
    base = base or {}
    return {k: v - base.get(k, 0) for k, v in sidecar_stats.items()}


def probe_query_capacity(nq: int) -> int:
    """The query shape a device probe of ``nq`` queries runs at: the next
    power of two, never under :data:`DEVICE_QUERY_FLOOR`.  The ONE place
    the shapes of ``lookup_in_sorted`` programs are decided."""
    return max(next_pow2(nq), DEVICE_QUERY_FLOOR)


def count_overlapped(in_flight: int) -> None:
    """``in_flight`` device probes of one lookup were launched before the
    first of them was collected: all but the first overlapped an earlier
    one.  Called once per lookup by whoever launched them — a shard for
    its segments, the serving engine for its chromosome groups."""
    if in_flight > 1:
        probe_stats["overlapped_probes"] += in_flight - 1


def device_lookup_state(base: dict | None = None) -> dict:
    """Where the device-lookup policy stands in this process — reported by
    load summaries and the server's ``/stats``; asks nothing of the
    backend.  ``enabled``/``transfer_fast`` are the two latches (None =
    never asked); the probe counts are taken relative to ``base`` (an
    earlier copy of :data:`probe_stats`)."""
    base = base or {}
    return {
        "mode": _device_lookup_mode(),
        "enabled": _DEVICE_LOOKUP_OK,
        "transfer_fast": _TRANSFER_FAST,
        **{k: v - base.get(k, 0) for k, v in probe_stats.items()},
    }


def set_device_probe_failure_hook(hook) -> None:
    """Install (or clear, with None) the device-probe failure observer."""
    global _DEVICE_PROBE_FAILURE_HOOK
    _DEVICE_PROBE_FAILURE_HOOK = hook


def _probe_failure_owned(exc: BaseException) -> bool:
    """Whether an installed failure observer takes a device probe's error
    (the serving circuit breaker: per-group trip + half-open re-probe,
    counted), so that the probe may answer from numpy; a loader, which
    installs none, sees the error."""
    hook = _DEVICE_PROBE_FAILURE_HOOK
    return hook is not None and bool(hook(exc))


class _Probe:
    """One segment's membership probe between :meth:`Segment.probe_launch`
    and :meth:`Segment.probe_collect`: answered already (``answer``, the
    host path) or in flight on the device (``out``, the program's
    un-fetched index array); ``query`` is kept for the host's retry of a
    fetch that fails."""

    __slots__ = ("query", "answer", "out")

    def __init__(self, query: tuple, answer=None, out=None):
        self.query = query
        self.answer = answer
        self.out = out


def _device_lookup_enabled() -> bool:
    global _DEVICE_LOOKUP_OK
    if _device_lookup_mode() == "off":
        return False
    if _DEVICE_LOOKUP_OK is None:
        import jax

        _DEVICE_LOOKUP_OK = jax.default_backend() != "cpu"
    return _DEVICE_LOOKUP_OK


def combined_key(pos: np.ndarray, h: np.ndarray) -> np.ndarray:
    """uint64 (pos << 32 | hash) — host-side sort/join key."""
    return (pos.astype(np.uint64) << np.uint64(32)) | h.astype(np.uint64)


class RawJson:
    """A JSONB column value held as raw JSON TEXT instead of parsed dicts.

    The native VEP transformer emits store-bound values as ready JSON; at
    100k+ results/sec, building their dict trees on ingest is the dominant
    cost and almost always wasted (the common consumer is the persistence
    writer, which wants text anyway).  A RawJson is immutable — sharing one
    instance across rows is safe, unlike dicts under deep-merge — and
    behaves as a read-only mapping for consumers that index into it (the
    parse is cached).  Store-side mutation sites (deep-merge targets,
    ``get_ann`` write-back) materialize a FRESH object per row via
    :meth:`fresh` so no parsed tree is ever shared between rows."""

    __slots__ = ("text", "_obj")

    def __init__(self, text: str):
        self.text = text
        self._obj = None

    def fresh(self):
        """A newly parsed (never shared) Python object of this value."""
        return json.loads(self.text)

    def _cached(self):
        if self._obj is None:
            self._obj = json.loads(self.text)
        return self._obj

    # -- read-only mapping/sequence protocol (cached parse) -----------------

    def __getitem__(self, k):
        return self._cached()[k]

    def get(self, k, default=None):
        obj = self._cached()
        return obj.get(k, default) if isinstance(obj, dict) else default

    def __contains__(self, k):
        return k in self._cached()

    def __iter__(self):
        return iter(self._cached())

    def __len__(self):
        return len(self._cached())

    def keys(self):
        return self._cached().keys()

    def values(self):
        return self._cached().values()

    def items(self):
        return self._cached().items()

    def __eq__(self, other):
        if isinstance(other, RawJson):
            other = other._cached()
        return self._cached() == other

    def __bool__(self):
        return bool(self._cached())

    def __repr__(self):
        return f"RawJson({self.text!r})"


def jsonb_dumps(value) -> str:
    """Serialize a stored JSONB value — raw text splices straight through."""
    if isinstance(value, RawJson):
        return value.text
    return json.dumps(value)


def sidecar_line(named_values, i: int) -> str | None:
    """One annotation-sidecar JSONL line for row ``i`` (None when the row
    carries no values) — the SINGLE serializer shared by ``save()``'s
    segment writer and the compactor (``store/compact.py``): byte parity
    between freshly saved and compacted sidecars depends on both writers
    splicing identically.  ``named_values`` yields (column, value) pairs;
    RawJson values write their text verbatim (no parse/re-serialize)."""
    parts = []
    for c, v in named_values:
        if v is None:
            continue
        if isinstance(v, RawJson):
            parts.append(f'"{c}":{v.text}')
        elif c == _LONG_ALLELES:
            parts.append(f'"{c}":{json.dumps(list(v))}')
        else:
            parts.append(f'"{c}":{json.dumps(v)}')
    if not parts:
        return None
    parts.append(f'"i":{i}')
    return "{" + ",".join(parts) + "}\n"


def holds_value(col: np.ndarray) -> np.ndarray:
    """[n] bool: which rows of an object column hold a value.  One C-level
    identity pass (``is not None``), no Python frame a row — and never
    ``!=``, which would ask ``RawJson.__eq__`` and parse every value."""
    return np.fromiter(
        map(operator.is_not, col, itertools.repeat(None)),
        np.bool_, col.shape[0],
    )


def sidecar_lines(named_cols, n: int, base: int = 0):
    """The annotation-sidecar lines of ``n`` rows — a segment, or a chunk
    of gathered rows whose first is row ``base`` of its file — in row
    order, each through :func:`sidecar_line`.  ``named_cols`` is (column,
    [n] object array or None) pairs.  Only rows that hold a value in some
    column are visited (the file is sparse, so the walk is too): a column
    of a value a row costs what a walk of every row would, a column with
    one row in ten a tenth.  The ONE row walk shared by ``save()``'s
    segment writer and the compactor."""
    present = [(c, col) for c, col in named_cols if col is not None]
    sidecar_stats["rows"] += n
    if not present or not n:
        return
    held = holds_value(present[0][1])
    for _, col in present[1:]:
        held |= holds_value(col)
    rows = np.flatnonzero(held)
    sidecar_stats["visited"] += int(rows.size)
    names = [c for c, _ in present]
    values = [col[rows].tolist() for _, col in present]
    for i, *vs in zip(rows.tolist(), *values):
        yield sidecar_line(zip(names, vs), base + i)  # a value: never None
    sidecar_stats["lines"] += int(rows.size)


class SparseValues(NamedTuple):
    """An object column given only where it holds a value — the form
    :meth:`Segment.build` takes beside a per-row list: ``rows`` are
    ascending positions in the build's input order, ``values`` one value
    each, a list or an object array (a None among them is one more row
    without a value)."""

    rows: np.ndarray
    values: list | np.ndarray


class Segment:
    """One sorted run of rows: numeric columns + packed alleles + object cols.

    Rows are sorted by (pos, hash); within equal keys, original append order
    is preserved (first-wins duplicate semantics).

    ``backing`` is the on-disk identity: the ordered list of saved segment
    ids whose files, merged left-to-right, reproduce this segment exactly.
    A fresh/mutated segment has ``backing=None`` (nothing on disk matches);
    a clean merge of clean segments CONCATENATES their backings — which is
    what makes persistence append-only (``VariantStore.save`` never rewrites
    a merged segment's rows, it just references the constituent files)."""

    __slots__ = ("n", "cols", "ref", "alt", "obj", "backing", "dirty",
                 "_key", "_device", "_numpy_query_volume", "residency")

    def __init__(self, cols, ref, alt, obj, backing=None):
        self.n = int(ref.shape[0])
        self.cols = cols
        self.ref = ref
        self.alt = alt
        self.obj = obj
        self.backing: list[int] | None = backing  # None = never saved
        self.dirty = True
        self._key = None
        self._device = None
        self._numpy_query_volume = 0  # ski-rental accumulator (see probe)
        # None = the segment decides its own HBM cache (ski-rental below);
        # "managed" = an external residency manager (serve/residency.py)
        # owns upload/evict under a byte budget — probe never auto-uploads,
        # it uses whatever cache the manager installed
        self.residency: str | None = None

    @property
    def key(self) -> np.ndarray:
        if self._key is None:
            self._key = combined_key(self.cols["pos"], self.cols["h"])
        return self._key

    @property
    def key_min(self) -> np.uint64:
        return self.key[0]

    @property
    def key_max(self) -> np.uint64:
        return self.key[-1]

    def overlaps(self, other: "Segment") -> bool:
        """Whether this segment's key range intersects ``other``'s.
        Disjoint segments cannot share an identity, so probes and merges
        may skip the pair entirely."""
        if self.n == 0 or other.n == 0:
            return False
        return not (self.key_max < other.key_min
                    or other.key_max < self.key_min)

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, rows: dict, ref, alt, annotations=None, digest_pk=None,
              long_alleles=None) -> "Segment":
        """Create a sorted segment from one flush's rows (any input order).

        Already-sorted input (the insert loader pre-sorts each flush by
        identity key) skips the argsort AND the per-column gather — the
        arrays are owned as-is, so build is O(n) dtype checks.

        Each object column (``annotations[c]``, ``digest_pk``,
        ``long_alleles``) is a per-row list or, from a caller that knows
        which rows hold a value, a :class:`SparseValues`."""
        k = rows["pos"].shape[0]
        cols = {}
        for name, dtype in _NUMERIC_COLUMNS:
            if name in rows:
                cols[name] = np.asarray(rows[name], dtype)
            elif name in ("ref_snp", "is_adsp_variant"):
                cols[name] = np.full((k,), -1, dtype)
            else:
                cols[name] = np.zeros((k,), dtype)
        key = combined_key(cols["pos"], cols["h"])
        if k <= 1 or bool((key[1:] >= key[:-1]).all()):
            order = None
        else:
            order = np.argsort(key, kind="stable")
            key = key[order]
            cols = {name: col[order] for name, col in cols.items()}

        obj = {}
        for c in JSONB_COLUMNS:
            src = annotations.get(c) if annotations else None
            obj[c] = _obj_array(src, order, k)
        obj[_DIGEST_PK] = _obj_array(digest_pk, order, k)
        obj[_LONG_ALLELES] = _obj_array(long_alleles, order, k)
        ref = np.asarray(ref)
        alt = np.asarray(alt)
        seg = cls(
            cols,
            ref if order is None else ref[order],
            alt if order is None else alt[order],
            obj,
        )
        seg._key = key
        return seg

    @classmethod
    def merge(cls, older: "Segment", newer: "Segment") -> "Segment":
        """Stable two-way merge (older rows first on equal keys).

        Position-sorted loads append monotonically, so the newer segment's
        keys usually all sort after the older's — that case is a pure
        concatenation (sequential memcpy, no gather)."""
        ka, kb = older.key, newer.key
        n = older.n + newer.n
        if older.n == 0 or newer.n == 0 or kb[0] > ka[-1]:
            def merge_col(a, b):
                return np.concatenate([a, b])
        else:
            pos_a = np.searchsorted(kb, ka, side="left") + np.arange(older.n)
            pos_b = np.searchsorted(ka, kb, side="right") + np.arange(newer.n)

            def merge_col(a, b):
                out = np.empty((n,) + a.shape[1:], a.dtype)
                out[pos_a] = a
                out[pos_b] = b
                return out

        cols = {name: merge_col(older.cols[name], newer.cols[name])
                for name, _ in _NUMERIC_COLUMNS}
        obj = {}
        for c in OBJECT_COLUMNS:
            a, b = older.obj[c], newer.obj[c]
            obj[c] = None if a is None and b is None else merge_col(
                _dense(a, older.n), _dense(b, newer.n)
            )
        seg = cls(cols, merge_col(older.ref, newer.ref),
                  merge_col(older.alt, newer.alt), obj)
        # both inputs' keys are already materialized for the guard/scatter:
        # hand the merged key to the new segment so its next probe skips
        # the O(n) recompute
        seg._key = merge_col(ka, kb)
        # two CLEAN segments merge into a clean segment whose on-disk
        # identity is the concatenation of their files (stable merge is
        # associative, so loading [a..., b...] left-to-right reproduces
        # this exact row order) — the append-only persistence invariant
        if not older.dirty and not newer.dirty and older.backing and newer.backing:
            seg.backing = older.backing + newer.backing
            seg.dirty = False
        return seg

    @classmethod
    def merge_many(cls, parts: list["Segment"]) -> "Segment":
        """Merge an ordered list of segments in one pass.

        The common shape — consecutive ascending DISJOINT runs, which is
        what a position-sorted load accumulates and what a backing group
        persists — is a single multi-way ``np.concatenate`` per column
        (each row copied once).  Anything else falls back to a balanced
        pairwise tree, O(n log k) instead of the O(n·k) a left fold pays."""
        if not parts:
            raise ValueError("merge_many of an empty part list")
        if len(parts) == 1:
            return parts[0]
        live = [p for p in parts if p.n > 0]
        chain = all(
            live[i].key_max < live[i + 1].key_min
            for i in range(len(live) - 1)
        )
        if not chain or len(live) < 2:
            merged = parts
            while len(merged) > 1:  # balanced pairwise tree
                merged = [
                    cls.merge(merged[i], merged[i + 1])
                    if i + 1 < len(merged) else merged[i]
                    for i in range(0, len(merged), 2)
                ]
            return merged[0]
        cols = {
            name: np.concatenate([p.cols[name] for p in live])
            for name, _ in _NUMERIC_COLUMNS
        }
        obj = {}
        for c in OBJECT_COLUMNS:
            if all(p.obj[c] is None for p in live):
                obj[c] = None
            else:
                obj[c] = np.concatenate(
                    [_dense(p.obj[c], p.n) for p in live]
                )
        seg = cls(
            cols,
            np.concatenate([p.ref for p in live]),
            np.concatenate([p.alt for p in live]),
            obj,
        )
        seg._key = np.concatenate([p.key for p in live])
        # backing/dirty propagate over ALL parts (an empty persisted part
        # still owns its on-disk files and must stay referenced)
        if all(not p.dirty and p.backing for p in parts):
            seg.backing = [sid for p in parts for sid in p.backing]
            seg.dirty = False
        return seg

    # -- membership ---------------------------------------------------------

    def probe(self, qkey, pos, h, ref, alt, ref_len, alt_len,
              host_only: bool = False):
        """(found [N] bool, local index [N] int32; -1 when absent):
        :meth:`probe_launch`, then :meth:`probe_collect` at once.

        ``host_only=True`` skips the device branch outright — the serving
        circuit breaker's open-state path (byte-identical answers, no
        failing-device attempt paid per probe)."""
        return self.probe_collect(self.probe_launch(
            qkey, pos, h, ref, alt, ref_len, alt_len, host_only=host_only
        ))

    def probe_launch(self, qkey, pos, h, ref, alt, ref_len, alt_len,
                     host_only: bool = False) -> "_Probe":
        """The first half of :meth:`probe`.  Where the probe takes the
        device it is packed, uploaded and dispatched here and this returns
        at once, the program still running: the caller may launch other
        segments' probes, hash the next group or render the last one
        before :meth:`probe_collect` waits for the answer.  A probe that
        takes the host path (numpy has nothing to wait for) is answered
        here."""
        query = (qkey, pos, h, ref, alt, ref_len, alt_len)
        if self.n == 0:
            return _Probe(query, answer=(
                np.zeros(pos.shape, np.bool_), np.full(pos.shape, -1, np.int32)
            ))
        nq = pos.shape[0]
        # an existing HBM cache is sunk cost — use it at any size; otherwise
        # upload once the ski-rental accumulator says the transfer has paid
        # for itself in forgone device work (see DEVICE_UPLOAD_AMORTIZE)
        # capture the cache tuple ONCE: a residency manager may evict
        # (`_device = None`) from another thread between this gate and
        # the collect — the captured tuple stays valid (the arrays
        # live as long as the reference), and a managed segment whose
        # cache vanished falls back to numpy instead of re-uploading
        dev = self._device
        if (not host_only
                and _device_lookup_enabled()
                and (
                     # an existing cache (auto-built, pinned, or installed
                     # by a residency manager) is sunk cost — honor it
                     # regardless of link speed
                     dev is not None
                     # auto-upload decisions belong to the segment only
                     # when no residency manager governs it
                     or (self.residency is None
                         and (_device_lookup_mode() == "always"
                              or (_transfer_fast()
                                  and self.n >= DEVICE_SEGMENT_MIN
                                  and nq >= DEVICE_QUERY_MIN
                                  and (self._numpy_query_volume + nq)
                                  * DEVICE_UPLOAD_AMORTIZE >= self.n))))):
            try:
                return _Probe(query, out=self._launch_device(
                    pos, h, ref, alt, ref_len, alt_len, dev=dev
                ))
            except Exception as exc:
                if not _probe_failure_owned(exc):
                    raise
        return _Probe(query, answer=self._probe_host(*query))

    def probe_collect(self, launched: "_Probe"):
        """The second half of :meth:`probe`: a launched probe's (found,
        index), waiting for the device where the probe is still there.  A
        device error that surfaces only now — asynchronous dispatch makes
        that the likely place — is handled as one at launch is."""
        if launched.answer is not None:
            return launched.answer
        nq = launched.query[1].shape[0]
        try:
            out = self._collect_device(launched.out, nq)
        except Exception as exc:
            if not _probe_failure_owned(exc):
                raise
            return self._probe_host(*launched.query)
        probe_stats["device_probes"] += 1
        probe_stats["device_queries"] += nq
        probe_stats["padded_queries"] += probe_query_capacity(nq)
        probe_stats["transfers"] += 2
        return out

    def _probe_host(self, qkey, pos, h, ref, alt, ref_len, alt_len):
        """The numpy probe: every host-path answer, and the retry of a
        device probe whose failure an observer owns."""
        nq = pos.shape[0]
        self._numpy_query_volume += nq
        lo = np.searchsorted(self.key, qkey, side="left")
        found = np.zeros(nq, np.bool_)
        index = np.full(nq, -1, np.int32)
        # equal-(pos,hash) runs are length 1 barring 2^-32 collisions; probe
        # up to 4 — but gather/compare the wide allele rows ONLY where the
        # key matches (typical chunks match almost nowhere, and runs are
        # contiguous so a no-match round ends the scan)
        for k in range(4):
            i = np.clip(lo + k, 0, self.n - 1)
            keyeq = (lo + k < self.n) & (self.key[i] == qkey)
            if not keyeq.any():
                break
            rows_q = np.where(keyeq & ~found)[0]
            if rows_q.size == 0:
                continue
            ii = i[rows_q]
            cand = (
                (self.cols["ref_len"][ii] == ref_len[rows_q])
                & (self.cols["alt_len"][ii] == alt_len[rows_q])
                & (self.ref[ii] == ref[rows_q]).all(axis=1)
                & (self.alt[ii] == alt[rows_q]).all(axis=1)
            )
            sel = rows_q[cand]
            index[sel] = ii[cand]
            found[sel] = True
        return found, index

    def _ensure_device_cache(self, device=None) -> None:
        """Upload this segment's identity columns to HBM (once; pow2-padded
        so compile count stays O(log n) — the sentinel position sorts last
        and can't match a real query).  ``device`` pins the destination
        (the residency manager's chromosome->device placement); None keeps
        the default device — the historical single-device layout."""
        if self._device is None:
            self._device = self._build_device_cache(device)

    def _build_device_cache(self, device=None) -> tuple:
        """The device copy :meth:`_ensure_device_cache` installs, built and
        handed back: the residency manager installs it itself, once the
        probe programs have run against it."""
        from annotatedvdb_tpu.utils.arrays import POS_SENTINEL, pad_pow2
        from annotatedvdb_tpu.utils.retry import device_put

        return tuple(
            device_put(x, device=device) for x in (
                pad_pow2(self.cols["pos"], POS_SENTINEL),
                pad_pow2(self.cols["h"], 0),
                pad_pow2(self.ref, 0), pad_pow2(self.alt, 0),
                pad_pow2(self.cols["ref_len"], 0),
                pad_pow2(self.cols["alt_len"], 0),
            )
        )

    def _launch_device(self, pos, h, ref, alt, ref_len, alt_len, dev=None):
        """Large-batch membership on device, launched: the queries padded
        to :func:`probe_query_capacity` (sentinel positions can't match a
        real row's; compile count stays logarithmic in batch size and the
        small probes share one program), packed into ONE host buffer
        (``ops/dedup.pack_queries``), and handed to
        ``lookup_in_sorted_packed`` against an HBM-resident cache of this
        segment's identity columns (``dev``: the caller-captured tuple —
        eviction-race-safe; None builds the cache, which managed segments
        never request).  One upload and one dispatch; what comes back is
        the program's un-fetched index array, its copy to the host already
        asked for, so the fetch travels while the caller works.  What the
        round trips cost is in the comment at :data:`DEVICE_QUERY_FLOOR`."""
        from annotatedvdb_tpu.ops.dedup import (
            lookup_in_sorted_packed_jit,
            pack_queries,
        )

        if dev is None:
            self._ensure_device_cache()
            dev = self._device
        out = lookup_in_sorted_packed_jit(*dev, pack_queries(
            pos, h, ref, alt, ref_len, alt_len,
            probe_query_capacity(pos.shape[0]),
        ))
        out.copy_to_host_async()
        return out

    @staticmethod
    def _collect_device(out, nq: int):
        """A launched device probe's (found, index): the one fetch."""
        index = np.asarray(out)[:nq]
        return index >= 0, index

    def _probe_device(self, pos, h, ref, alt, ref_len, alt_len, dev=None):
        """One device probe run to its end: (found, index)."""
        return self._collect_device(
            self._launch_device(pos, h, ref, alt, ref_len, alt_len, dev=dev),
            pos.shape[0],
        )

    def warm_device_probe(self, max_queries: int, dev: tuple) -> None:
        """Run the probe program once at every query capacity a probe of
        1..``max_queries`` queries can take (:func:`probe_query_capacity`
        says which), on all-zero queries, against the device copy ``dev``
        — so the programs a point microbatch can need are compiled and
        loaded before traffic asks for them."""
        cols = self.cols
        like = (cols["pos"], cols["h"], self.ref, self.alt,
                cols["ref_len"], cols["alt_len"])
        cap = probe_query_capacity(1)
        while True:  # position 0 is no row's: nothing matches
            self._probe_device(
                *(np.zeros((cap,) + a.shape[1:], a.dtype) for a in like),
                dev=dev,
            )
            if cap >= max_queries:
                return
            cap = probe_query_capacity(cap + 1)

    # -- mutation -----------------------------------------------------------

    def filter(self, keep: np.ndarray) -> "Segment":
        seg = Segment(
            {name: col[keep] for name, col in self.cols.items()},
            self.ref[keep], self.alt[keep],
            {c: (None if a is None else a[keep]) for c, a in self.obj.items()},
        )
        return seg

    def obj_dense(self, name: str) -> np.ndarray:
        """Object column, materialized into the segment if still all-None."""
        if self.obj[name] is None:
            self.obj[name] = np.full((self.n,), None, object)
        return self.obj[name]


def _obj_array(values, order: np.ndarray | None, n: int) -> np.ndarray | None:
    """Object column from a per-row list or :class:`SparseValues`; None
    when no row holds a value (lazily-materialized columns keep
    annotation-free segments free).  ``order=None`` means the rows are
    already in sorted order.  No Python frame a row in either form, and
    values are never handed to numpy as a LIST: its shape sniffing asks
    each element's ``__len__``, and ``RawJson.__len__`` parses."""
    if values is None:
        return None
    if isinstance(values, SparseValues):
        rows = np.asarray(values.rows, np.intp)
        vals = np.fromiter(values.values, object, rows.size)
    else:
        rows = None
        vals = (values.astype(object) if isinstance(values, np.ndarray)
                else np.fromiter(values, object, n))
    held = holds_value(vals)
    if not held.any():
        return None
    if rows is None:  # per-row form: already the dense column
        return vals if order is None else vals[order]
    if not held.all():
        rows, vals = rows[held], vals[held]
    if order is not None:  # input position -> sorted position
        inverse = np.empty((n,), np.intp)
        inverse[order] = np.arange(n)
        rows = inverse[rows]
    out = np.full((n,), None, object)
    out[rows] = vals
    return out


def _dense(arr: np.ndarray | None, n: int) -> np.ndarray:
    return np.full((n,), None, object) if arr is None else arr


class _ShardLookup:
    """One :meth:`ChromosomeShard.lookup` between its launch and its
    collect: the answer so far, and the probes not yet folded into it
    (segment, its first global id, its :class:`_Probe`), oldest first —
    empty unless a device probe is in flight."""

    __slots__ = ("found", "index", "waiting")

    def __init__(self, shape):
        self.found = np.zeros(shape, np.bool_)
        self.index = np.full(shape, -1, np.int64)
        self.waiting: list = []

    def take(self, f, idx, start: int) -> None:
        """Fold one segment's answer in: an id found earlier keeps the
        older segment's row."""
        take = f & ~self.found
        self.index = np.where(take, idx.astype(np.int64) + start, self.index)
        self.found |= f


class ChromosomeShard:
    """One chromosome's rows: a list of sorted segments, oldest first."""

    def __init__(self, chrom_code: int, width: int):
        self.chrom_code = int(chrom_code)
        self.width = width
        self.segments: list[Segment] = []
        self._starts_cache: np.ndarray | None = None

    @property
    def n(self) -> int:
        return sum(s.n for s in self.segments)

    def _starts(self) -> np.ndarray:
        """Global-id base offset of each segment (segment-list order)."""
        if self._starts_cache is None:
            self._starts_cache = np.concatenate(
                [[0], np.cumsum([s.n for s in self.segments])]
            ).astype(np.int64)
        return self._starts_cache

    def _locate(self, ids) -> tuple[np.ndarray, np.ndarray]:
        """Global ids -> (segment index, local offset), vectorized."""
        ids = np.asarray(ids, np.int64)
        starts = self._starts()
        seg = np.searchsorted(starts, ids, side="right") - 1
        return seg, ids - starts[seg]

    # -- flat single-segment views (whole-shard passes) ---------------------
    # CADD join, Postgres egress, and VCF export iterate the shard in
    # position-sorted order; they call compact() once, after which global ids
    # coincide with sorted order and these views are O(1).  Accessing a flat
    # view COMPACTS the shard, which renumbers global ids — never hold ids
    # from a previous lookup across a flat-view access (the per-id
    # get_col/set_col/get_ann accessors are the safe interleaving API).

    def _single(self) -> Segment:
        if len(self.segments) != 1:
            self.compact()
        if not self.segments:  # empty shard: materialize one empty segment
            self.segments.append(Segment.build(
                {"pos": np.empty((0,), np.int32)},
                np.empty((0, self.width), np.uint8),
                np.empty((0, self.width), np.uint8),
            ))
            self._starts_cache = None
        return self.segments[0]

    @property
    def cols(self) -> dict:
        return self._single().cols

    @property
    def ref(self) -> np.ndarray:
        return self._single().ref

    @property
    def alt(self) -> np.ndarray:
        return self._single().alt

    @property
    def annotations(self) -> dict:
        seg = self._single()
        return {c: seg.obj_dense(c) for c in JSONB_COLUMNS}

    @property
    def digest_pk(self) -> np.ndarray:
        return self._single().obj_dense(_DIGEST_PK)

    @property
    def long_alleles(self) -> np.ndarray:
        return self._single().obj_dense(_LONG_ALLELES)

    def compact(self) -> None:
        """Merge all segments into one (position-sorted global ids)."""
        if len(self.segments) > 1:
            # single splice AFTER the merge completes — same atomic-splice
            # discipline as maintain()
            self.segments[:] = [Segment.merge_many(list(self.segments))]
        self._starts_cache = None

    # -- whole-column views (any segment count, global-id order) ------------

    def column(self, name: str) -> np.ndarray:
        """Full numeric column concatenated in global-id order."""
        if not self.segments:
            return np.empty((0,), dict(_NUMERIC_COLUMNS)[name])
        return np.concatenate([s.cols[name] for s in self.segments])

    def object_column(self, name: str) -> np.ndarray:
        """Full object column concatenated in global-id order (a copy —
        mutate through :meth:`update_annotation`, not this view)."""
        if not self.segments:
            return np.empty((0,), object)
        return np.concatenate([_dense(s.obj[name], s.n) for s in self.segments])

    # -- per-row access by global id ----------------------------------------

    def locate_row(self, gid: int) -> tuple[Segment, int]:
        """(segment, local offset) for one global row id — the per-row read
        accessor the serving path renders records through (no compaction, no
        mutation; valid until the shard is appended/merged/deleted).  Scalar
        fast path: one searchsorted, no temporaries (the vectorized
        ``_locate`` costs ~4x per single row)."""
        gid = int(gid)
        starts = self._starts()
        si = int(starts.searchsorted(gid, side="right")) - 1
        return self.segments[si], gid - int(starts[si])

    def locate_rows(self, gids) -> tuple[np.ndarray, np.ndarray]:
        """(segment index, local offset) int64 arrays for many global row
        ids, in the ids' order — :meth:`locate_row` for a batch: one
        searchsorted for the whole call (the serving path's columnar
        renderer gathers each touched segment's columns through these)."""
        return self._locate(gids)

    def get_col(self, name: str, ids):
        seg, off = self._locate(ids)
        out = np.empty(seg.shape, dtype=dict(_NUMERIC_COLUMNS)[name])
        for si in np.unique(seg):
            m = seg == si
            out[m] = self.segments[si].cols[name][off[m]]
        return out

    def set_col(self, name: str, ids, values) -> None:
        if name in _IDENTITY_COLUMNS:
            raise ValueError(f"identity column {name} is immutable")
        seg, off = self._locate(ids)
        values = np.broadcast_to(np.asarray(values), seg.shape)
        for si in np.unique(seg):
            m = seg == si
            s = self.segments[si]
            s.cols[name][off[m]] = values[m]
            s.dirty = True

    def get_ann(self, column: str, i):
        seg, off = self._locate([i])
        col = self.segments[int(seg[0])].obj[column]
        if col is None:
            return None
        v = col[int(off[0])]
        if isinstance(v, RawJson):
            # materialize ON THE ROW (fresh parse, never the shared cached
            # object — the same RawJson instance may back several rows)
            v = col[int(off[0])] = v.fresh()
        return v

    def primary_key(self, i: int) -> str:
        """Row's record PK: retained digest PK for the long-allele tail, else
        literal ``chr:pos:ref:alt[:rs]`` (``primary_key_generator.py:99-122``).
        The scalar definition; the vectorized egress assembly
        (``io.egress.shard_strings``) is parity-pinned against it by
        ``tests/test_egress_vectorized.py``."""
        seg, off = self._locate([i])
        s, j = self.segments[int(seg[0])], int(off[0])
        if s.obj[_DIGEST_PK] is not None and s.obj[_DIGEST_PK][j] is not None:
            return s.obj[_DIGEST_PK][j]
        ref, alt = self.alleles(int(i))
        parts = [
            chromosome_label(self.chrom_code),
            str(int(s.cols["pos"][j])), ref, alt,
        ]
        rs = int(s.cols["ref_snp"][j])
        if rs >= 0:
            parts.append(f"rs{rs}")
        return ":".join(parts)

    def alleles(self, i: int) -> tuple[str, str]:
        """True (ref, alt) strings for row i — exact even for the long-allele
        tail whose device arrays are width-truncated."""
        seg, off = self._locate([i])
        s, j = self.segments[int(seg[0])], int(off[0])
        if s.obj[_LONG_ALLELES] is not None and s.obj[_LONG_ALLELES][j] is not None:
            return tuple(s.obj[_LONG_ALLELES][j])
        ref_len = int(s.cols["ref_len"][j])
        alt_len = int(s.cols["alt_len"][j])
        if ref_len > self.width or alt_len > self.width:
            # a store written before long-allele retention existed: returning
            # the truncated prefix would silently corrupt joins/exports
            raise ValueError(
                f"row {i}: allele length {max(ref_len, alt_len)} exceeds device "
                f"width {self.width} but the original strings were not retained "
                "(store predates long-allele retention; reload from source)"
            )
        return (
            decode_allele(s.ref[j], ref_len),
            decode_allele(s.alt[j], alt_len),
        )

    # -- membership ---------------------------------------------------------

    def pin_device_lookup(self) -> int:
        """Build the HBM membership cache for every current segment.

        For read-mostly workloads (update loads over a static store) the
        one-time identity-column upload amortizes across many query
        batches; inserts invalidate the cache (merges replace segments), so
        the insert path never calls this.  Returns the number of segments
        pinned; a CPU backend pins none (lookups keep the numpy path)."""
        if not _device_lookup_enabled():
            return 0
        pinned = 0
        for seg in self.segments:
            # only segments past the numpy break-even — pinning smaller
            # ones routes probes through kernel dispatch where a
            # cache-resident searchsorted wins
            if seg.n >= DEVICE_SEGMENT_MIN:
                try:
                    seg._ensure_device_cache()
                    pinned += 1
                except Exception:
                    # likely HBM pressure: stop pinning MORE (already
                    # pinned caches stay useful) but leave the global
                    # lookup latch alone — the lazy ski-rental path in
                    # probe() keeps working within whatever fits
                    break
        return pinned

    def lookup(self, pos, h, ref, alt, ref_len, alt_len,
               host_only: bool = False):
        """Vectorized membership: (found [N] bool, global id [N] int64).

        Oldest segment wins when an identity appears in several segments
        (first-wins duplicate policy).  Returned ids are invalidated by the
        next ``append``/``compact``/``delete``.  ``host_only=True`` pins
        every segment probe to the numpy path (circuit-breaker open
        state — byte-identical answers).  :meth:`lookup_launch`, then
        :meth:`lookup_collect` at once."""
        return self.lookup_collect(self.lookup_launch(
            pos, h, ref, alt, ref_len, alt_len, host_only=host_only
        ))

    def lookup_launch(self, pos, h, ref, alt, ref_len, alt_len,
                      host_only: bool = False) -> "_ShardLookup":
        """The first half of :meth:`lookup`: every segment the queries can
        touch is probed — answered at once where the probe takes the host
        path, launched and left running where it takes the device
        (:meth:`Segment.probe_launch`) — and nothing is waited for.  Host
        answers that come before the first launch are folded in here, so a
        lookup no device takes part in is whole when this returns."""
        out = _ShardLookup(pos.shape)
        if not self.segments:
            return out
        qkey = combined_key(pos, h)
        if qkey.size == 0:
            return out
        # range pruning: a segment whose key range misses the query range
        # entirely cannot match — on position-sorted loads (many disjoint
        # segments, see maintain) this reduces the probe set to O(1)
        # segments per batch
        qlo, qhi = qkey.min(), qkey.max()
        starts = self._starts()
        for si, seg in enumerate(self.segments):
            if seg.n == 0 or seg.key_max < qlo or seg.key_min > qhi:
                continue
            if not out.waiting and out.found.all():
                break
            probe = seg.probe_launch(qkey, pos, h, ref, alt, ref_len,
                                     alt_len, host_only=host_only)
            if out.waiting or probe.answer is None:
                # first-wins is decided oldest segment first: behind a
                # probe still in flight every later answer waits its turn
                out.waiting.append((seg, int(starts[si]), probe))
            else:
                out.take(*probe.answer, int(starts[si]))
        count_overlapped(sum(
            probe.answer is None for _seg, _start, probe in out.waiting
        ))
        return out

    def lookup_collect(self, launched: "_ShardLookup"):
        """The second half of :meth:`lookup`: (found, global id), after
        collecting what :meth:`lookup_launch` left in flight, oldest
        segment first."""
        for seg, start, probe in launched.waiting:
            launched.take(*seg.probe_collect(probe), start)
        launched.waiting.clear()
        return launched.found, launched.index

    # -- mutation -----------------------------------------------------------

    def append(self, rows: dict, ref: np.ndarray, alt: np.ndarray,
               annotations: dict[str, list] | None = None,
               digest_pk: list | None = None,
               long_alleles: list | None = None) -> None:
        """Flush new (already deduplicated, not-present) rows as one segment.

        O(batch) plus an amortized-logarithmic cascade merge — never an O(n)
        rewrite of the shard (the ``np.insert``-per-flush scale wall this
        replaces).  ``rows`` maps numeric column names -> [K] arrays (missing
        columns filled with NULL defaults)."""
        if rows["pos"].shape[0] == 0:
            return
        self.append_segment(
            Segment.build(rows, ref, alt, annotations, digest_pk, long_alleles)
        )
        self.maintain()

    def append_segment(self, seg: Segment) -> None:
        """O(1) append of a prebuilt sorted segment, no cascade merge.

        The async insert pipeline appends here, persists, and runs
        :meth:`maintain` afterwards — merging clean (persisted) segments
        keeps their backing files referenced instead of rewriting them, so
        per-checkpoint disk writes stay O(new rows)."""
        if seg.n == 0:
            return
        self.segments.append(seg)
        self._starts_cache = None

    def maintain(self) -> None:
        """Keep membership-probe cost flat without paying merge copies.

        Two-part policy (Postgres analog: append heap pages, defer vacuum,
        ``createVariant.sql:4`` / ``alterAutoVacuum.sql:2-19``):

        - OVERLAPPING tail segments cascade-merge size-tiered (geometric
          sizes, O(log n) count, O(n log n) total work) — range pruning
          cannot skip them, so their count must stay logarithmic;
        - DISJOINT tail segments are left alone: a position-sorted load
          appends strictly-ascending runs, probes skip them by range
          (``lookup``), and merging would copy every row O(log n) times
          for no probe savings.  Only when the count passes MAX_SEGMENTS
          does ``_collapse`` concatenate consecutive runs back into
          MERGE_SEGMENT_CAP-sized segments (amortized O(1) copies/row).
        """
        while (len(self.segments) >= 2
               and self.segments[-2].n <= 2 * self.segments[-1].n
               and self.segments[-2].n <= MERGE_SEGMENT_CAP
               and self.segments[-2].overlaps(self.segments[-1])):
            merged = Segment.merge(self.segments[-2], self.segments[-1])
            # single splice AFTER the merge completes: a concurrent reader
            # snapshotting the list (the loader's membership probe) must
            # never observe a window where the older rows are in neither
            # the list nor the in-flight set — pop-then-merge would open
            # one for the whole O(n) merge
            self.segments[-2:] = [merged]
        if len(self.segments) > MAX_SEGMENTS:
            self._collapse()
        self._starts_cache = None

    def _collapse(self) -> None:
        """Merge consecutive segments into ~MERGE_SEGMENT_CAP-row groups.

        Runs every ~MAX_SEGMENTS flushes at most, so each row is copied
        amortized O(1) times between collapses.  Same atomic-splice
        discipline as ``maintain`` — the list is rewritten group by group,
        never holding rows outside it."""
        i = 0
        while i < len(self.segments) - 1:
            j = i + 1
            total = self.segments[i].n
            while (j < len(self.segments)
                   and total + self.segments[j].n <= MERGE_SEGMENT_CAP):
                total += self.segments[j].n
                j += 1
            if j - i >= 2:
                merged = Segment.merge_many(self.segments[i:j])
                self.segments[i:j] = [merged]
            i += 1

    def update_annotation(self, index: np.ndarray, column: str,
                          values: Iterable, merge: bool = True) -> int:
        """Set/merge a JSONB column at given global ids; returns update count.

        ``merge=True`` applies jsonb_merge deep-merge semantics (patch wins);
        ``merge=False`` replaces, matching plain-assignment UPDATEs.
        Fresh rows (no stored value — the bulk of any first-pass update
        load) are assigned with one fancy-index scatter per segment; only
        rows that actually merge pay per-row work.  Duplicate ids within
        one call keep strict in-order semantics (the second occurrence
        merges into the first's result) via the ordered fallback."""
        index = np.asarray(index, np.int64)
        if index.size == 0:
            return 0
        vals = np.empty(index.shape, object)
        if isinstance(values, np.ndarray) and values.dtype == object:
            vals[:] = values  # array->array copy: elements not probed
        else:
            # element-wise on purpose: bulk list->object-array assignment
            # probes each element's __len__ (numpy sniffing for nested
            # sequences), and RawJson.__len__ parses its JSON — one hidden
            # json.loads per row
            for k, v in enumerate(values):
                vals[k] = v
        valid = index >= 0
        count = int(valid.sum())
        if count == 0:
            return 0
        if not valid.all():
            index, vals = index[valid], vals[valid]
        seg_idx, off = self._locate(index)
        for si in np.unique(seg_idx):
            s = self.segments[int(si)]
            fresh_col = s.obj[column] is None  # never materialized: every
            col = s.obj_dense(column)          # target row is fresh, no
            m = seg_idx == si                  # per-row merge check needed
            offs, vs = off[m], vals[m]
            s.dirty = True
            has_dups = np.unique(offs).size != offs.size
            if fresh_col and not has_dups:
                col[offs] = vs
                continue
            if has_dups:
                # duplicate rows in one call: order is observable (later
                # values merge into earlier results) — per-row loop
                for j, v in zip(offs, vs):
                    j = int(j)
                    cur = col[j]
                    if merge and cur is not None and (
                            isinstance(cur, (dict, RawJson))
                            and isinstance(v, (dict, RawJson))):
                        if isinstance(cur, RawJson):
                            cur = col[j] = cur.fresh()
                        deep_update(
                            cur, v.fresh() if isinstance(v, RawJson) else v
                        )
                    else:
                        col[j] = v
                continue
            cur = col[offs]
            if merge:
                replace = np.fromiter(
                    (c is None
                     or not isinstance(c, (dict, RawJson))
                     or not isinstance(v, (dict, RawJson))
                     for c, v in zip(cur, vs)),
                    bool, offs.size,
                )
            else:
                replace = np.ones(offs.size, bool)
            col[offs[replace]] = vs[replace]
            if not replace.all():
                km = ~replace
                for j, c, v in zip(offs[km], cur[km], vs[km]):
                    # deep-merge: materialize raw values per row (fresh —
                    # a RawJson may back several rows) before mutating
                    if isinstance(c, RawJson):
                        c = col[int(j)] = c.fresh()
                    deep_update(c, v.fresh() if isinstance(v, RawJson) else v)
        return count

    def set_flag(self, index: np.ndarray, column: str, values) -> None:
        index = np.asarray(index, np.int64)
        mask = index >= 0
        self.set_col(
            column, index[mask],
            np.asarray(values)[mask] if np.ndim(values) else values,
        )

    def delete_by_algorithm(self, alg_id: int) -> int:
        removed = 0
        kept: list[Segment] = []
        for s in self.segments:
            keep = s.cols["row_algorithm_id"] != alg_id
            k = int((~keep).sum())
            if k == 0:
                kept.append(s)
                continue
            removed += k
            if k < s.n:
                kept.append(s.filter(keep))
        if removed:
            self.segments = kept
            self._starts_cache = None
        return removed


class VariantStore:
    """All chromosome shards + incremental persistence."""

    def __init__(self, width: int):
        self.width = width
        #: read-only stores (``load(..., readonly=True)``) refuse ``save``
        #: and never materialize shards on access — the serving read path
        #: must not create directories or persist empty shards as a side
        #: effect of a lookup (the foot-gun ``loaders/lookup.py`` documents)
        self.readonly = False
        self.shards: dict[int, ChromosomeShard] = {}
        self._next_seg_id = 1
        # per-stem write-time integrity records ({stem: {npz: {bytes, crc32},
        # jsonl: {...}}}), carried in the manifest so load/fsck can detect
        # torn or bit-rotted segment files; populated by _write_segment and
        # inherited from the manifest on load (clean segments keep theirs)
        self._integrity: dict[str, dict] = {}
        #: advisory chromosome->device placement block read back from the
        #: manifest (written by save() when a >1-device mesh is
        #: configured; ``doctor status`` and the serve mesh path report
        #: it) — None for single-device stores
        self.mesh_placement: dict | None = None
        # identity of THIS store's on-disk lineage: save() only trusts
        # pre-existing segment files in a directory whose manifest carries
        # this uid — a same-stem file left by a DIFFERENT store must be
        # rewritten, not silently adopted as this segment's data.  The
        # manifest is re-read every save (no cache): another store may
        # overwrite the directory between our saves.
        import uuid

        self._uid = uuid.uuid4().hex
        # cooperative-writer adoption state (see save()): seg ids below
        # the floor existed when this store loaded (ours to manage,
        # including dropping them on undo); ids at/above it that we did
        # not allocate ourselves belong to ANOTHER writer that committed
        # into this directory since — a memtable flush or compaction —
        # and save() must carry their groups forward, never clobber or
        # orphan them.  None = fresh store (no on-disk lineage to adopt).
        self._sid_floor: int | None = None
        self._my_sids: set[int] = set()

    def shard(self, chrom_code: int) -> ChromosomeShard:
        code = int(chrom_code)
        if code not in self.shards:
            if self.readonly:
                raise RuntimeError(
                    f"readonly store: shard {code} does not exist and must "
                    "not be created by a read path (use store.shards.get)"
                )
            self.shards[code] = ChromosomeShard(code, self.width)
        return self.shards[code]

    def pin_for_updates(self) -> int:
        """Upload every shard's membership cache to HBM when that pays:
        update loads (VEP/CADD/QC) probe a STATIC store many times, so the
        one-time identity-column upload amortizes across the whole file.
        No-op where the measured upload rate is below
        ``DEVICE_MIN_BANDWIDTH`` (query transfers would cost more than
        numpy saves) and on CPU backends.  Returns segments pinned."""
        if not (_device_lookup_enabled() and _transfer_fast()):
            return 0
        return sum(s.pin_device_lookup() for s in self.shards.values())

    @property
    def n(self) -> int:
        return sum(s.n for s in self.shards.values())

    def delete_by_algorithm(self, alg_id: int) -> int:
        """Undo a load: drop every row stamped with ``alg_id``
        (``undo_variant_load.py:21-67`` semantics, minus the chunked
        DELETE back-off which a columnar mask doesn't need)."""
        return sum(s.delete_by_algorithm(alg_id) for s in self.shards.values())

    def compact(self) -> None:
        for s in self.shards.values():
            s.compact()

    # -- persistence --------------------------------------------------------
    #
    # Layout v3: manifest.json lists each shard's segments in order, each as
    # a GROUP of saved segment ids — an in-memory segment merged from
    # already-persisted segments is manifested as the list of its
    # constituents' ids (merged left-to-right on load), so merges never
    # rewrite rows on disk.  Every segment file is one npz (numeric cols +
    # alleles) plus one sparse JSONL (object columns, only rows that have
    # any).  ``save`` writes only segments that are new or mutated and
    # prunes orphaned files: a per-checkpoint persist is O(rows appended or
    # updated since the last save) — the reference's analog is the WAL-less
    # UNLOGGED-table commit, not a full table rewrite.

    def _dir_manifest(self, path: str) -> dict | None:
        """The directory's CURRENT manifest when it belongs to THIS
        store's lineage (carries our uid), else None.  Untrusted
        directories get every segment rewritten — stale same-stem files
        from another/older store must never be adopted as this segment's
        data."""
        try:
            with open(os.path.join(path, "manifest.json")) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            return None
        if not isinstance(manifest, dict) \
                or manifest.get("store_uid") != self._uid:
            return None
        return manifest

    def _adoptable_groups(self, on_disk: dict | None) -> dict:
        """{label: [group, ...]} of backing groups ANOTHER cooperative
        writer (a serve worker's memtable flush, or a compaction pass)
        committed into this directory since this store loaded — detected
        by seg id: at/above the load-time floor and not allocated by
        this store.  save() carries these forward verbatim: dropping
        them would silently destroy rows this store never held (for a
        flush, ACKNOWLEDGED upserts whose WAL was already truncated),
        and re-deriving them fresh from the live manifest every save
        keeps us consistent if a later pass (compaction) replaces them.
        Groups below the floor are ours to manage — including NOT
        carrying them when an undo dropped their rows."""
        if self._sid_floor is None or on_disk is None:
            return {}
        if int(on_disk.get("next_seg_id", 1)) <= self._sid_floor:
            return {}  # no id at/above the floor can exist in it
        floor = self._sid_floor
        fmt2 = on_disk.get("format") == 2
        adopted: dict[str, list] = {}
        for label, groups in (on_disk.get("shards") or {}).items():
            norm = [[g] for g in groups] if fmt2 else groups
            keep = [
                list(group) for group in norm
                if group and all(
                    isinstance(sid, int) and sid >= floor
                    and sid not in self._my_sids for sid in group
                )
            ]
            if keep:
                adopted[label] = keep
        return adopted

    @staticmethod
    def _peek_segment_rows(path: str, stem: str) -> int:
        """Row count of one on-disk segment from its container header
        alone (no column data read) — the stats entry for adopted
        groups.  Best-effort: stats are advisory, a parse failure
        reports 0 rather than failing the save."""
        fp = os.path.join(path, stem + ".npz")
        try:
            with open(fp, "rb") as f:
                head = f.readline()
                if not head.startswith(b"{"):
                    with open(fp, "rb") as zf:  # legacy zip npz
                        with np.load(zf) as z:
                            return int(z["ref"].shape[0])
                meta = json.loads(head)
                if "rows" in meta:  # seg: 2 (compaction) records it
                    return int(meta["rows"])
                version = np.lib.format.read_magic(f)
                if version == (1, 0):
                    shape, _f, _d = np.lib.format.read_array_header_1_0(f)
                elif version == (2, 0):
                    shape, _f, _d = np.lib.format.read_array_header_2_0(f)
                else:
                    return 0
                return int(shape[0])
        except (OSError, ValueError, KeyError):
            return 0

    def save(self, path: str) -> None:
        if self.readonly:
            raise RuntimeError(
                "readonly store: save() is forbidden (opened with "
                "readonly=True — reload without it to mutate)"
            )
        os.makedirs(path, exist_ok=True)
        on_disk = self._dir_manifest(path)
        trusted = on_disk is not None
        # cooperative-writer sync: a memtable flush (or compaction)
        # committed since this store loaded or last saved — its groups
        # are carried forward below, and its seg ids must NEVER be
        # reallocated here (writing chr<L>.<sid> would clobber its files
        # before the rename even races anything)
        adopted = self._adoptable_groups(on_disk)
        if trusted:
            self._next_seg_id = max(
                self._next_seg_id, int(on_disk.get("next_seg_id", 1))
            )
        live_files = {"manifest.json"}
        manifest = {
            "format": 3, "width": self.width, "store_uid": self._uid,
            "shards": {},
        }
        from annotatedvdb_tpu.parallel.mesh import placement_hint

        placement = placement_hint()
        adopted_rows: dict[str, int] = {}
        # ---- decision pass: walk shards in the LEGACY sorted-code order,
        # allocating seg ids and manifest groups exactly as the historical
        # single-pass save did (the manifest stays byte-identical), but
        # DEFER the physical writes so the write pass below can reorder
        # them by mesh placement without perturbing id allocation
        pending_writes: list[tuple[int, str, int, "Segment"]] = []
        for code, shard in sorted(self.shards.items()):
            label = chromosome_label(code)
            groups = []
            for seg in shard.segments:
                stems = (
                    [f"chr{label}.{sid:06d}" for sid in seg.backing]
                    if seg.backing else []
                )
                ids = list(seg.backing) if seg.backing else []
                if (seg.dirty or not stems or not trusted
                        # a clean segment saved to a DIFFERENT directory
                        # earlier: its files aren't here (or are another
                        # store's — both npz AND jsonl must exist), rewrite
                        or not all(
                            os.path.exists(os.path.join(path, s + ".npz"))
                            and os.path.exists(
                                os.path.join(path, s + ".ann.jsonl"))
                            for s in stems)):
                    # EVERY (re-)write takes a fresh seg id, so a
                    # manifested segment's files are never touched in
                    # place — the manifest swap below is the single
                    # commit point (a crash between the two per-segment
                    # renames can otherwise tear an npz/jsonl pair)
                    sid = self._next_seg_id
                    self._next_seg_id += 1
                    self._my_sids.add(sid)
                    stems = [f"chr{label}.{sid:06d}"]
                    ids = [sid]
                    pending_writes.append((int(code), stems[0], sid, seg))
                for stem in stems:
                    live_files.update({stem + ".npz", stem + ".ann.jsonl"})
                groups.append(ids)
            manifest["shards"][label] = groups
        # ---- write pass: the physical segment writes.  With a mesh
        # configured (AVDB_MESH_SHAPE) they run in PLACEMENT order —
        # grouped by owning device, chromosomes in code order within a
        # device — so a bulk save streams each device's working set
        # contiguously (sequential layout for the per-device readers that
        # mmap these files, and a natural prefix order for device-at-a-
        # time restores).  Without a mesh this is exactly the legacy
        # sorted-code order.  Either way the decision pass already fixed
        # ids and manifest bytes, so the READ path sees a byte-identical
        # store regardless of write order (tests/test_ingest_spine.py).
        if pending_writes and placement is not None:
            dev_of = placement["groups"]
            n_dev = int(placement["devices"])
            pending_writes.sort(key=lambda t: (
                dev_of.get(chromosome_label(t[0]), n_dev), t[0]
            ))  # stable: within a chromosome, segment order is preserved
        for _code, stem, sid, seg in pending_writes:
            self._integrity[stem] = self._write_segment(path, stem, seg)
            seg.backing = [sid]
            seg.dirty = False
        # append adopted groups AFTER this store's own (they are the
        # NEWER writes: first-wins ordering on disk matches the overlay
        # their writer served), carrying their integrity records
        for label, groups in sorted(adopted.items()):
            manifest["shards"].setdefault(label, [])
            rows = 0
            for group in groups:
                manifest["shards"][label].append(list(group))
                for sid in group:
                    stem = f"chr{label}.{sid:06d}"
                    live_files.update(
                        {stem + ".npz", stem + ".ann.jsonl"}
                    )
                    rec = (on_disk.get("integrity") or {}).get(stem)
                    if rec is not None:
                        self._integrity[stem] = rec
                    rows += self._peek_segment_rows(path, stem)
            adopted_rows[label] = rows
        manifest["next_seg_id"] = self._next_seg_id
        # write-time integrity records for every LIVE segment file (size +
        # crc32 of the exact bytes handed to the OS).  Stems with no record
        # (clean segments inherited from a pre-integrity manifest) are
        # simply absent — load skips their checks, the next rewrite records
        # them.  Sorted for the deterministic-manifest invariant.
        live_stems = sorted({
            f[: -len(".npz")] for f in live_files if f.endswith(".npz")
        })
        manifest["integrity"] = {
            stem: self._integrity[stem]
            for stem in live_stems if stem in self._integrity
        }
        # residency stats for ops tooling (the obs layer exports these as
        # avdb_store_rows gauges without loading any segment data).
        # DETERMINISTIC on store content only — no timestamps/host data:
        # serial and overlapped loads of the same input must stay
        # byte-identical, manifest included (tests/test_pipeline_modes.py)
        stats_rows = {
            chromosome_label(code): int(shard.n)
            for code, shard in sorted(self.shards.items())
        }
        for label, rows in sorted(adopted_rows.items()):
            stats_rows[label] = stats_rows.get(label, 0) + rows
        manifest["stats"] = {
            "rows": stats_rows,
            "segments": {
                label: len(groups)
                for label, groups in manifest["shards"].items()
            },
        }
        # advisory mesh placement: which device each chromosome group
        # would serve from under the configured AVDB_MESH_SHAPE (absent on
        # single-device resolutions — the historical manifest byte-for-
        # byte).  Deterministic on env + content only, never on jax state:
        # save() must not initialize a backend.  Compaction and the flush
        # writer copy the whole manifest dict, so the block survives both.
        # (``placement`` was resolved above — it also ordered the segment
        # write pass.)
        if placement is not None:
            manifest["mesh_placement"] = placement
        # atomic swap: a PROCESS crash mid-save must leave the previous
        # manifest intact (segments are also written via tmp+rename, so the
        # old manifest's files are never mutated in place) — the store is
        # always loadable, possibly one checkpoint behind.  Process death
        # needs only the atomic rename (the page cache survives it).  The
        # MANIFEST's flush+fsync is unconditional: it is one tiny file per
        # checkpoint and it is what keeps a power-loss rename from landing
        # a zero-length/corrupt manifest.json on filesystems that don't
        # order rename after data — without it the store could become
        # unloadable instead of "at most one checkpoint behind".  The
        # expensive fsyncs — segment data and directory metadata — remain
        # the power-loss opt-in (AVDB_FSYNC=1), because on journaling
        # filesystems one data fsync per checkpoint forces the whole
        # preceding segment write to disk and costs real throughput.  The
        # survivable default matches the reference's own bulk loads
        # (UNLOGGED tables are truncated by Postgres crash recovery,
        # createVariant.sql:4).
        # crash point: every segment of this checkpoint is on disk, the
        # commit (manifest swap) has not happened — a death here must leave
        # the PREVIOUS manifest fully consistent (new files are orphans)
        faults.fire("store.save.pre_manifest")
        # tmp -> flush -> fsync -> atomic replace -> dir fsync under
        # AVDB_FSYNC (one directory fsync after the manifest swap covers
        # every segment rename above — they share the directory)
        tio.replace_manifest(os.path.join(path, "manifest.json"), manifest)
        for fname in os.listdir(path):
            if fname not in live_files and (
                    fname.endswith(".npz") or fname.endswith(".ann.jsonl")
                    # orphaned tmp files from crashed saves (any pid)
                    or (fname.startswith(".") and ".tmp" in fname)):
                tio.unlink(os.path.join(path, fname))
        # drop integrity records for files the cleanup just removed
        self._integrity = {
            stem: rec for stem, rec in self._integrity.items()
            if stem + ".npz" in live_files
        }

    @staticmethod
    def _write_segment(path: str, stem: str, seg: Segment) -> dict:
        # uncompressed: segments are rewritten on every cascade merge, and
        # deflate CPU dominates the persist stage at load throughput (the
        # reference's Postgres heap is uncompressed for the same reason).
        # tmp+rename: a re-persisted dirty segment (e.g. updated
        # annotations) must never corrupt the file the current manifest
        # references if the process dies mid-write
        fsync_data = _fsync_wanted()
        tmp = os.path.join(path, f".{stem}.tmp{os.getpid()}.npz")
        # width-trim the allele matrices to this segment's longest allele:
        # dbSNP/gnomAD-shaped data stores <=8-byte alleles in width-49
        # arrays, so ~85% of segment bytes would be zero padding (load
        # inflates back to the store width)
        ref, alt = seg.ref, seg.alt
        if seg.n and ref.shape[1] > 1:
            # clamp to the array width: over-width rows store full lengths
            # but only width bytes, so one 300bp indel must not forfeit the
            # whole segment's trim
            width = ref.shape[1]
            w = int(max(
                np.minimum(seg.cols["ref_len"], width).max(),
                np.minimum(seg.cols["alt_len"], width).max(), 1,
            ))
            if w < ref.shape[1]:
                ref = np.ascontiguousarray(ref[:, :w])
                alt = np.ascontiguousarray(alt[:, :w])
        # flat sequential container, NOT an npz: np.savez's zipfile
        # machinery (per-member seek-back size patching, 8KB buffered
        # writes, crc32 passes) was ~45% of checkpoint-persist CPU on
        # syscall-expensive filesystems.  Layout: one JSON name line, then
        # one raw .npy stream per column in that order.  The extension
        # stays .npz for manifest compatibility; _read_segment sniffs the
        # leading byte ('{' here vs zip's 'P'), so stores persisted by
        # older builds keep loading.
        arrays = {
            "ref": ref, "alt": alt,
            **{name: seg.cols[name] for name, _ in _NUMERIC_COLUMNS},
        }
        with tio.open(tmp, "wb", buffering=1 << 20) as raw_f:
            # integrity record accumulates on the bytes in hand (see
            # _CrcWriter) — no post-hoc re-read pass
            f = _CrcWriter(raw_f)
            f.write(
                (json.dumps({"seg": 1, "names": list(arrays)}) + "\n")
                .encode()
            )
            first = True
            for arr in arrays.values():
                np.lib.format.write_array(f, arr, allow_pickle=False)
                if first:
                    # crash point: the container body is part-written (the
                    # tmp file tears, the manifested store must not notice)
                    faults.fire("store.save.mid_segment", raw_f)
                    first = False
            if fsync_data:
                f.flush()
                tio.fsync(raw_f)
        npz_rec = {"bytes": f.nbytes, "crc32": f.crc}
        tio.replace(tmp, os.path.join(path, stem + ".npz"))
        atmp = os.path.join(path, f".{stem}.tmp{os.getpid()}.ann.jsonl")
        with tio.open(atmp, "wb") as raw_f:
            f = _CrcWriter(raw_f)
            # lines go out in blocks: one encode, crc32 and write a block
            # (the same bytes in the same order as one a line)
            lines = sidecar_lines(
                ((c, seg.obj[c]) for c in OBJECT_COLUMNS), seg.n
            )
            while block := list(itertools.islice(lines, _SIDECAR_BLOCK)):
                f.write("".join(block).encode())
            if fsync_data:
                f.flush()
                tio.fsync(raw_f)
        tio.replace(atmp, os.path.join(path, stem + ".ann.jsonl"))
        return {"npz": npz_rec, "jsonl": {"bytes": f.nbytes, "crc32": f.crc}}

    @classmethod
    def load(cls, path: str, readonly: bool = False) -> "VariantStore":
        """Load a persisted store.  ``readonly=True`` marks the result as a
        pure read replica: ``save`` raises, and ``shard()`` refuses to
        materialize missing shards — a query for an unloaded chromosome can
        never create directories or persist empty shards as a side effect
        (the serving path's open mode; see ``serve/snapshot.py``)."""
        mpath = os.path.join(path, "manifest.json")
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except FileNotFoundError:
            raise FileNotFoundError(
                f"{mpath}: no store manifest — {path!r} is not a variant "
                "store directory, or its first save never completed; "
                + _fsck_hint(path)
            ) from None
        except (ValueError, OSError) as err:
            raise StoreCorruptError(
                f"{mpath}: unreadable store manifest ({err}); "
                + _fsck_hint(path)
            ) from err
        if not isinstance(manifest, dict):
            raise StoreCorruptError(
                f"{mpath}: manifest is not a JSON object; " + _fsck_hint(path)
            )
        fmt = manifest.get("format")
        if fmt not in (2, 3):
            raise ValueError(
                "unsupported store format (pre-segment layout); reload from "
                "source VCFs"
            )
        store = cls(manifest["width"])
        store._next_seg_id = manifest.get("next_seg_id", 1)
        # adoption floor (see save()): everything below this id is this
        # manifest's own lineage; a cooperative writer committing later
        # allocates at/above it
        store._sid_floor = int(store._next_seg_id)
        uid = manifest.get("store_uid")
        if uid:
            # resume this store's on-disk lineage: saves back into this
            # directory may trust its existing segment files.  Manifests
            # predating store_uid keep the fresh uid — the first save into
            # their directory rewrites segments once, then records the uid.
            store._uid = uid
        store._integrity = dict(manifest.get("integrity") or {})
        placement = manifest.get("mesh_placement")
        if isinstance(placement, dict):
            store.mesh_placement = placement
        verify = _verify_mode()
        from annotatedvdb_tpu.types import chromosome_code

        for label, groups in manifest["shards"].items():
            if fmt == 2:  # v2: flat id list, one file per segment
                groups = [[sid] for sid in groups]
            shard = store.shard(chromosome_code(label))
            for group in groups:
                parts = [
                    cls._read_segment(
                        path, label, sid, store.width,
                        integrity=store._integrity.get(
                            f"chr{label}.{sid:06d}"
                        ),
                        verify=verify,
                    )
                    for sid in group
                ]
                # multi-way (concat for the common ascending-disjoint
                # chain, balanced tree otherwise) — a frozen group built
                # from many small checkpoints loads with each row copied
                # once, not O(parts) times
                seg = Segment.merge_many(parts)
                # merge propagated backing == group for clean inputs;
                # verify the invariant rather than trusting it (an
                # explicit raise — asserts vanish under ``python -O`` and
                # a violation here would persist wrong backing metadata
                # on the next save)
                if seg.backing != list(group) or seg.dirty:
                    raise ValueError(
                        f"store load: backing group {group} did not "
                        f"reassemble cleanly (got {seg.backing}, "
                        f"dirty={seg.dirty}); store files are inconsistent"
                    )
                shard.segments.append(seg)
            shard._starts_cache = None
        # flip LAST: the loop above materializes shards via store.shard()
        store.readonly = bool(readonly)
        return store

    @staticmethod
    def _check_file(fp: str, rec: dict | None, verify: str,
                    store_path: str) -> None:
        """Integrity gate for one segment file: size check whenever a record
        exists (free — one stat), full crc32 under ``AVDB_VERIFY=deep``."""
        if rec is None or verify == "off":
            return
        try:
            actual = os.path.getsize(fp)
        except OSError as err:
            raise StoreCorruptError(
                f"{fp}: unreadable segment file ({err}); "
                + _fsck_hint(store_path)
            ) from err
        if actual != rec["bytes"]:
            raise StoreCorruptError(
                f"{fp}: segment file is {actual} bytes, manifest integrity "
                f"record says {rec['bytes']} (torn or truncated write); "
                + _fsck_hint(store_path)
            )
        if verify == "deep":
            crc = crc32_file(fp)
            if crc != rec["crc32"]:
                raise StoreCorruptError(
                    f"{fp}: crc32 mismatch (stored {rec['crc32']:#010x}, "
                    f"computed {crc:#010x}) — bit rot or partial overwrite; "
                    + _fsck_hint(store_path)
                )

    @classmethod
    def _read_segment(cls, path: str, label: str, seg_id: int,
                      width: int, integrity: dict | None = None,
                      verify: str = "size") -> Segment:
        stem = f"chr{label}.{seg_id:06d}"
        fp = os.path.join(path, stem + ".npz")
        ap = os.path.join(path, stem + ".ann.jsonl")
        for p, key in ((fp, "npz"), (ap, "jsonl")):
            if not os.path.exists(p):
                raise StoreCorruptError(
                    f"{p}: segment file referenced by the manifest is "
                    f"missing; " + _fsck_hint(path)
                )
            cls._check_file(
                p, (integrity or {}).get(key), verify, path
            )
        try:
            spill = _spill_bytes()
            spill_this = bool(spill and os.path.getsize(fp) >= spill)
            with open(fp, "rb") as f:
                head = f.read(1)
                if head == b"{":
                    # flat container (see _write_segment): JSON name line +
                    # sequential raw .npy streams.  ``seg: 2`` (written by
                    # store/compact.py) additionally dictionary-codes the
                    # allele matrices (ref_dict/ref_codes streams).
                    f.seek(0)
                    names = json.loads(f.readline())["names"]
                    data = {
                        name: cls._read_stream(f, fp, spill_this)
                        for name in names
                    }
                    # dict-coded alleles decode to the plain matrices (the
                    # dictionary is small by construction; the decode is
                    # the bounded materialization a spilled segment pays
                    # for coded columns — the numeric bulk stays mmapped)
                    for col in ("ref", "alt"):
                        if col + "_dict" in data:
                            data[col] = data.pop(col + "_dict")[
                                data.pop(col + "_codes")
                            ]
                else:  # legacy zip-backed npz from older builds
                    f.seek(0)
                    with np.load(f) as z:
                        data = {name: z[name] for name in z.files}
        except StoreCorruptError:
            raise
        except Exception as err:
            # a torn file with no integrity record (pre-integrity store)
            # still must not surface as a bare numpy/zip parse error
            raise StoreCorruptError(
                f"{fp}: segment container failed to parse ({err}); "
                + _fsck_hint(path)
            ) from err
        cols = {name: data[name] for name, _ in _NUMERIC_COLUMNS}
        n = data["ref"].shape[0]
        ref, alt = data["ref"], data["alt"]
        if ref.shape[1] < width:
            # width-trimmed on save: inflate back to the store width
            # (trailing pad bytes are zeros by construction)
            full = np.zeros((n, width), np.uint8)
            full[:, :ref.shape[1]] = ref
            ref = full
            full = np.zeros((n, width), np.uint8)
            full[:, :alt.shape[1]] = alt
            alt = full
        obj: dict = {c: None for c in OBJECT_COLUMNS}
        try:
            for k, line in enumerate(cls._iter_sidecar(ap), start=1):
                try:
                    row = json.loads(line)
                    i = row.pop("i")
                except (ValueError, KeyError) as err:
                    raise StoreCorruptError(
                        f"{ap}:{k}: unparseable annotation row ({err}); "
                        + _fsck_hint(path)
                    ) from err
                for c, v in row.items():
                    if obj[c] is None:
                        obj[c] = np.full((n,), None, object)
                    obj[c][i] = tuple(v) if c == _LONG_ALLELES else v
        except zlib.error as err:
            # a bit-flipped compressed sidecar (compaction's format) must
            # surface with the same actionable contract as every other
            # torn/corrupt segment file — never a bare zlib.error
            raise StoreCorruptError(
                f"{ap}: compressed annotation sidecar failed to inflate "
                f"({err}); " + _fsck_hint(path)
            ) from err
        seg = Segment(cols, ref, alt, obj, backing=[seg_id])
        seg.dirty = False
        return seg

    @staticmethod
    def _read_stream(f, fp: str, spill: bool) -> np.ndarray:
        """One raw .npy stream from a flat container: materialized by
        default; when ``spill`` (the out-of-core tier, see
        AVDB_STORE_SPILL_BYTES) the array is a copy-on-write memmap view
        of the file — reads page from disk on demand, and the update
        loaders' in-place mutations land in private pages (a dirty
        segment is rewritten wholesale on save, never written back
        through the map)."""
        if not spill:
            return np.lib.format.read_array(f, allow_pickle=False)
        start = f.tell()
        version = np.lib.format.read_magic(f)
        if version == (1, 0):
            shape, fortran, dtype = np.lib.format.read_array_header_1_0(f)
        elif version == (2, 0):
            shape, fortran, dtype = np.lib.format.read_array_header_2_0(f)
        else:  # unknown header rev: stay correct, give up laziness
            shape = fortran = dtype = None
        if shape is None or fortran or dtype.hasobject:
            f.seek(start)
            return np.lib.format.read_array(f, allow_pickle=False)
        offset = f.tell()
        nbytes = int(dtype.itemsize * int(np.prod(shape, dtype=np.int64)))
        arr = np.memmap(fp, dtype=dtype, mode="c", shape=shape,
                        offset=offset) if nbytes else np.empty(shape, dtype)
        f.seek(offset + nbytes)
        return arr

    @staticmethod
    def _iter_sidecar(ap: str):
        """Annotation-sidecar lines: plain JSONL ('{' leading byte, the
        save() format) or the zlib-compressed variant compaction writes
        (0x78 leading byte) — streamed, never fully buffered."""
        with open(ap, "rb") as f:
            head = f.read(1)
            if not head:
                return
            f.seek(0)
            if head == b"{":
                for raw in f:
                    yield raw.decode()
                return
            d = zlib.decompressobj()
            buf = b""
            while True:
                block = f.read(1 << 20)
                if not block:
                    break
                buf += d.decompress(block)
                lines = buf.split(b"\n")
                buf = lines.pop()
                for ln in lines:
                    if ln:
                        yield ln.decode()
            buf += d.flush()
            for ln in buf.split(b"\n"):
                if ln:
                    yield ln.decode()
