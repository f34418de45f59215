"""VEP result load: update-only annotation of existing store rows.

Reference flow (``Load/bin/load_vep_result.py`` +
``Util/lib/python/loaders/vep_variant_loader.py``): stream VEP JSON lines;
per line, rank+sort the consequence blocks, re-parse the embedded VCF
``input`` entry, and per alt allele — PK lookup (SQL), skip/update existing
``vep_output``, match frequencies and consequences via the **left-normalized**
allele ('-' placeholder for emptied alleles, the VEP convention), then batch
``jsonb_merge`` UPDATEs.

Here the per-alt rows accumulate into device batches: one annotate-kernel
call yields the normalized-allele split points for the whole batch, one
sorted-merge lookup per chromosome shard resolves PK rows, and updates apply
with deep-merge semantics into the store's JSONB columns.  Consequence
ranking rides the memoized host ranker (novel combos re-rank and are logged,
``load_vep_result.py:190-191``).
"""

from __future__ import annotations

import gzip
import io as _io
import json
import time

import numpy as np

import os

from annotatedvdb_tpu.conseq import ConsequenceRanker
from annotatedvdb_tpu.io.vep import VepResultParser
from annotatedvdb_tpu.models.pipeline import annotate_fn
from annotatedvdb_tpu.native import vep as native_vep
from annotatedvdb_tpu.ops.hashing import allele_hash_jit

from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore
from annotatedvdb_tpu.store.variant_store import RawJson
from annotatedvdb_tpu.types import VariantBatch, chromosome_code
from annotatedvdb_tpu.utils.profiling import bulk_load_gc


# pending-row tuple layout (see _parse_result)
R_CODE, R_POS, R_REF, R_ALT, R_ANN, R_FREQ, R_CLEANED, R_SHARED = range(8)


def _pyfast():
    """The C column-assembly binding, or None (pure-Python fallback)."""
    from annotatedvdb_tpu.native import pyfast

    return pyfast if pyfast.available() else None


def _np_scalar(obj):
    """json.dumps ``default`` hook: numpy scalars (a future rank field that
    skips prefetch_ranks' int()/bool() coercion) degrade to their Python
    value instead of crashing the load mid-file with a TypeError."""
    item = getattr(obj, "item", None)
    if item is not None:
        return item()
    raise TypeError(
        f"non-JSON value of type {type(obj).__name__} in a store update"
    )


def _fresh(obj):
    """Deep, un-aliased copy of JSON-pure data via one C-level round trip
    (~5-10x cheaper than ``copy.deepcopy`` for small nested dicts)."""
    return json.loads(json.dumps(obj, default=_np_scalar))


def _open_text(path: str):
    if path.endswith(".gz"):
        return _io.TextIOWrapper(gzip.open(path, "rb"), encoding="utf-8")
    return open(path, "r", encoding="utf-8")


def _open_bytes(path: str):
    if path.endswith(".gz"):
        return gzip.open(path, "rb")
    return open(path, "rb")


class TpuVepLoader:
    """Update-only loader: annotates variants already present in the store."""

    def __init__(
        self,
        store: VariantStore,
        ledger: AlgorithmLedger,
        ranker: ConsequenceRanker,
        datasource: str | None = None,
        skip_existing: bool = False,
        batch_size: int = 1 << 14,
        log=print,
        log_after: int | None = None,
        mesh=None,
        quarantine=None,
        max_errors: int = -1,
    ):
        """``mesh``: optional multi-device :class:`jax.sharding.Mesh`; the
        per-chunk identity resolution then runs as ONE sharded program
        (chromosome re-shard + in-mesh lookup against a device-resident
        store snapshot, ``parallel.distributed.distributed_update_step``) —
        the TPU replacement for the reference's 10-process VEP update
        fan-out (``load_vep_result.py:304-311``)."""
        self.store = store
        self.ledger = ledger
        self.parser = VepResultParser(ranker)
        self.datasource = datasource.lower() if datasource else None
        self.skip_existing = skip_existing
        self.batch_size = batch_size
        self.mesh = mesh if (mesh is not None and mesh.devices.size > 1) else None
        self._dev_snapshot = None
        self.log = log
        from annotatedvdb_tpu.utils.logging import ProgressCadence
        from annotatedvdb_tpu.utils.profiling import StageTimer

        self._cadence = ProgressCadence(log, log_after, unit="results")
        #: same observability surface as TpuVcfLoader: ingest (file read) /
        #: process (transform + store apply) busy seconds + load wall
        self.timer = StageTimer()
        #: chunk-granularity metrics hook (ObsSession.attach)
        self.obs = None
        #: backpressure accounting for the ingest-prefetch boundary
        #: (utils.pipeline.merge_stage_stats; exported by ObsSession)
        self.queue_stalls: dict = {}
        self._blob: bytes | None = None      # native rank-table serialization
        self._blob_version = -1
        from annotatedvdb_tpu.utils.quarantine import ErrorBudget

        # quarantine sink + --maxErrors budget: malformed JSON lines and
        # structurally broken result docs are preserved replayably instead
        # of killing the whole-batch decode (utils.quarantine)
        self.quarantine = quarantine
        self._budget = (
            quarantine.budget if quarantine is not None
            else ErrorBudget(max_errors)
        )
        self.counters = {
            "line": 0, "variant": 0, "skipped": 0, "duplicates": 0,
            "update": 0, "not_found": 0,
        }

    def _reject(self, raw, reason: str) -> None:
        """Quarantine one rejected VEP result line (line numbers are not
        tracked through the block reader; the raw line is what replay
        needs).  Raises ErrorBudgetExceeded past --maxErrors."""
        self.counters["rejected"] = self.counters.get("rejected", 0) + 1
        if isinstance(raw, bytes):
            raw = raw.decode("utf-8", "replace")
        if self.quarantine is not None:
            self.quarantine.reject(None, raw, reason)
        else:
            self._budget.add(1, context=reason)

    def _ranking_blob(self) -> bytes:
        """Serialized rank table for the native transformer, refreshed when
        a learn-on-miss re-rank bumps the ranker version."""
        v = self.parser.ranker.version
        if self._blob is None or self._blob_version != v:
            self._blob = native_vep.ranking_blob(self.parser.ranker)
            self._blob_version = v
        return self._blob

    #: metric label / run-ledger script name (obs.ObsSession)
    obs_name = "load-vep"

    @property
    def is_adsp(self) -> bool:
        return self.datasource == "adsp"

    @property
    def is_dbsnp(self) -> bool:
        return self.datasource == "dbsnp"

    def warmup(self) -> None:
        """Pre-compile the annotate + hash kernels for this loader's padded
        batch shape (``_apply_batch`` pads every flush to
        ``next_pow2(batch_size)`` or its double, so two compiles cover a
        whole load).  Optional — the first flush compiles lazily without it."""
        from annotatedvdb_tpu.io.synth import synthetic_batch
        from annotatedvdb_tpu.utils.arrays import next_pow2

        from annotatedvdb_tpu.ops.pack import (
            pack_vep_outputs_jit,
            transport_verified,
        )
        from annotatedvdb_tpu.store.variant_store import _transfer_fast

        # build the C RawJson assembler outside the measured stream (the
        # first _apply_native call otherwise pays its compile)
        from annotatedvdb_tpu.native import pyfast

        pyfast.warm()
        if not _transfer_fast():
            return  # slow link: _apply_batch computes on host, no kernels
        p = next_pow2(self.batch_size)
        for shape in {p, next_pow2(p + 1)}:
            b = synthetic_batch(shape, width=self.store.width)
            ann = annotate_fn()(
                b.chrom, b.pos, b.ref, b.alt, b.ref_len, b.alt_len
            )
            h = allele_hash_jit(b.ref, b.alt, b.ref_len, b.alt_len)
            if transport_verified() and self.store.width <= 255:
                np.asarray(
                    pack_vep_outputs_jit(h, ann.prefix_len, ann.host_fallback)
                )
            else:
                np.asarray(ann.prefix_len), np.asarray(h)

    @bulk_load_gc()
    def load_file(self, path: str, commit: bool = False, test: bool = False) -> dict:
        alg_id = self.ledger.begin(
            "TpuVepLoader.load_file",
            {"file": path, "datasource": self.datasource, "test": test},
            commit,
        )
        # update loads probe a static store per flush: pin membership
        # caches in HBM where the link makes that a win (no-op otherwise)
        self.store.pin_for_updates()
        n_added_before = len(self.parser.ranker.added)
        use_native = (
            os.environ.get("AVDB_NATIVE_VEP", "1") != "0"
            and native_vep.available()
        )
        if self.mesh is not None and use_native:
            # freeze the per-shard device snapshot once (the store is
            # static for the whole update load); every native chunk then
            # resolves identities in ONE sharded program.  Only the native
            # path consumes it — copying/sorting the whole store for the
            # Python fallback path would be pure waste.
            from annotatedvdb_tpu.parallel.device_store import (
                build_device_shard_store,
            )

            # position-block partition: VEP files arrive chromosome-
            # sorted, so chromosome routing would land every flush on one
            # shard — position blocks spread each flush across the mesh
            self._dev_snapshot = build_device_shard_store(
                self.store, self.mesh.devices.size, routing="position"
            )

        def flush_python(batch_lines: list[bytes]) -> None:
            # ONE json.loads over the whole flush (lines joined into a JSON
            # array) — the C decoder amortizes per-call setup and allocator
            # churn across the batch, ~2x a per-line loads loop
            try:
                raw = json.loads(b"[" + b",".join(batch_lines) + b"]")
            except ValueError:
                raw = None
            if raw is not None and len(raw) == len(batch_lines):
                pairs = list(zip(raw, batch_lines))
            else:
                # a malformed line poisons the whole-batch decode, and a
                # line carrying several comma-joined docs desyncs the
                # doc<->line pairing: fall back per line so only bad lines
                # quarantine (under --maxErrors), every good doc still
                # loads, and each doc is attributed to its OWN line
                pairs = []
                for ln in batch_lines:
                    try:
                        pairs.append((json.loads(ln), ln))
                    except ValueError:
                        try:
                            docs_on_line = json.loads(b"[" + ln + b"]")
                        except ValueError as err:
                            self._reject(ln, f"invalid VEP JSON: {err}")
                            continue
                        pairs.extend((d, ln) for d in docs_on_line)
            docs = []
            for ann, ln in pairs:
                if isinstance(ann, dict):
                    docs.append((ann, ln))
                else:
                    self._reject(
                        ln, "VEP result line is not a JSON object"
                    )
            # batched combo->rank resolution through the compiled rank-table
            # snapshot first (device path for large batches); the per-row
            # parse below then hits the memo, and only novel combos take the
            # host ranker's learn-on-miss path
            self.parser.prefetch_ranks([d for d, _ in docs])
            pending: list[tuple] = []
            extend = pending.extend
            parse = self._parse_result
            for ann, ln in docs:
                try:
                    extend(parse(ann))
                except (KeyError, ValueError, TypeError, IndexError,
                        AttributeError) as err:
                    # structurally broken doc (missing 'input', bad POS...)
                    self._reject(ln, f"unparseable VEP result: {err!r}")
            if pending:
                self._apply_batch(pending, alg_id, commit)

        def count_native(res, doc_lo, doc_hi, row_lo, row_hi) -> None:
            # per-applied-range accounting ('.'-alt skips, skipped contigs,
            # per-alt rows) — rows of docs that are re-transformed after a
            # mid-flush re-rank must not be counted twice
            self.counters["variant"] += row_hi - row_lo
            self.counters["skipped"] += int(
                res.doc_skipped[doc_lo:doc_hi].sum()
            ) + int((res.doc_fallback[doc_lo:doc_hi] == 2).sum())

        def flush_python_text(sub: bytes, count: bool) -> None:
            batch_lines = [ln for ln in sub.split(b"\n") if ln.strip()]
            if count:
                self.counters["line"] += len(batch_lines)
            if batch_lines:
                flush_python(batch_lines)

        def flush_text(text: bytes) -> None:
            # one raw byte block of complete lines straight into the C++
            # transformer — no per-line Python list, no join.  Docs the
            # native parser cannot transform faithfully (novel combos,
            # escapes, malformed inputs) re-run through the pure-Python
            # path, INTERLEAVED in document order so same-row update/merge
            # ordering matches the all-Python path exactly.  A fallback doc
            # that LEARNS a novel combo renumbers the whole rank table, so
            # the remaining docs re-transform with the fresh table —
            # exactly the version-mix point the Python path has.
            start_off = 0
            restarts = 0
            counted = False  # input lines are counted once per flush: by
            # the FIRST transform (its out_docs covers every doc of the
            # block; restarts re-scan tails) or by the whole-block Python
            # path when the native engine is off
            while start_off < len(text):
                sub = text[start_off:] if start_off else text
                res = (
                    native_vep.transform_text(
                        sub, self._ranking_blob(), self.is_dbsnp,
                        self.store.width,
                    )
                    # novel-combo-dense input (first load against a stale
                    # table) would otherwise re-transform the tail once per
                    # learned combo; past a few restarts the Python path is
                    # cheaper AND exact by definition
                    if use_native and restarts < 4 else None
                )
                if res is None:
                    flush_python_text(sub, count=not counted)
                    break
                n_docs = int(res.doc_fallback.size)
                if not counted:
                    self.counters["line"] += n_docs
                    counted = True
                doc_of_row = res.doc_of_row
                fb_docs = np.where(res.doc_fallback == 1)[0]
                lo_row, lo_doc = 0, 0
                restart = None
                for f in fb_docs.tolist():
                    hi_row = int(np.searchsorted(doc_of_row, f))
                    count_native(res, lo_doc, f, lo_row, hi_row)
                    if hi_row > lo_row:
                        self._apply_native(res, alg_id, commit, lo_row, hi_row)
                    v0 = self.parser.ranker.version
                    o = int(res.doc_off[f])
                    e = sub.find(b"\n", o)
                    flush_python([sub[o:] if e < 0 else sub[o:e]])
                    lo_row = int(
                        np.searchsorted(doc_of_row, f, side="right")
                    )
                    lo_doc = f + 1
                    if self.parser.ranker.version != v0:
                        # resume from the doc AFTER the fallback one
                        if f + 1 < n_docs:
                            restart = start_off + int(res.doc_off[f + 1])
                        else:
                            restart = len(text)  # fallback doc was last
                        break
                if restart is not None:
                    start_off = restart
                    restarts += 1
                    continue
                count_native(res, lo_doc, n_docs, lo_row, res.n_rows)
                if res.n_rows > lo_row:
                    self._apply_native(res, alg_id, commit, lo_row, res.n_rows)
                break
            self._cadence.maybe_log(self.counters["line"], self.counters)

        def timed_flush(text: bytes) -> None:
            # one "process" span + one chunk observation per flushed block
            # (results-per-flush = the counters' line delta)
            lines_before = self.counters["line"]
            t0 = time.perf_counter() if self.obs is not None else 0.0
            with self.timer.stage("process"):
                flush_text(text)
            if self.obs is not None:
                self.obs.chunk(
                    self.counters["line"] - lines_before,
                    seconds=time.perf_counter() - t0,
                )

        # binary chunked read, flushed per block of complete lines (the
        # transformer takes raw bytes; only rare Python-fallback docs are
        # ever re-materialized as line strings).  The read + line-split
        # runs on the ingest-prefetch spine (io/prefetch.py): the scanner
        # stays AVDB_INGEST_PREFETCH_DEPTH blocks ahead of the transformer
        # on its own thread, sequential (untagged) — VEP updates are
        # order-bearing end to end
        from annotatedvdb_tpu.io.prefetch import ChunkPrefetcher

        with self.timer.wall(), _open_bytes(path) as fh:

            def blocks():
                tail = b""
                while True:
                    block = fh.read(4 << 20)
                    if not block:
                        break
                    block = tail + block
                    cut = block.rfind(b"\n")
                    if cut < 0:
                        tail = block
                        continue
                    yield block[:cut + 1]
                    tail = block[cut + 1:]
                    if test:
                        # one-batch smoke runs must still cover a SMALL
                        # file completely: if nothing follows, the
                        # unterminated final line belongs to this (only)
                        # batch
                        if not fh.read(1) and tail.strip():
                            yield tail + b"\n"
                        return
                if tail.strip():
                    yield tail + b"\n"

            pre = ChunkPrefetcher(
                blocks(), timer=self.timer, name="vep-ingest"
            )
            try:
                for text in pre:
                    timed_flush(text)
            finally:
                # settle the prefetch thread before fh leaves scope (an
                # aborted update must not leave it mid-read)
                pre.close()
                from annotatedvdb_tpu.utils.pipeline import merge_stage_stats

                merge_stage_stats(self.queue_stalls, "ingest", pre.stats)
        added = self.parser.ranker.added[n_added_before:]
        if added:
            self.log(f"added {len(added)} new consequence combos: {added}")
        self.ledger.finish(alg_id, dict(self.counters))
        self._cadence.finish(
            self.counters["line"], self.counters, self.timer.summary()
        )
        self.counters["alg_id"] = alg_id
        return dict(self.counters)

    # ------------------------------------------------------------------

    def _batch_identity(self, batch: VariantBatch):
        """(hash, prefix_len, host_fallback) for one per-alt batch — the
        three identity outputs the update path consumes.  Device kernels
        where the measured upload rate clears ``DEVICE_MIN_BANDWIDTH``
        (packed single-fetch transport), bit-exact numpy twins below it
        (see ops/hashing.allele_hash_np, ops/annotate.vep_identity_np)."""
        from annotatedvdb_tpu.loaders.vcf_loader import _pad_batch
        from annotatedvdb_tpu.store.variant_store import _transfer_fast
        from annotatedvdb_tpu.utils.arrays import next_pow2

        n = batch.n
        if not _transfer_fast():
            from annotatedvdb_tpu.ops.annotate import vep_identity_np
            from annotatedvdb_tpu.ops.hashing import allele_hash_np

            prefix, host = vep_identity_np(
                batch.ref, batch.alt, batch.ref_len, batch.alt_len
            )
            h = allele_hash_np(
                batch.ref, batch.alt, batch.ref_len, batch.alt_len
            )
            return h, prefix, host
        # tail flushes pad UP to the steady-state shape so a whole load
        # compiles at most two kernel shapes (both covered by ``warmup``)
        padded = _pad_batch(
            batch, max(next_pow2(n), next_pow2(self.batch_size))
        )
        ann_p = annotate_fn()(
            padded.chrom, padded.pos, padded.ref, padded.alt,
            padded.ref_len, padded.alt_len,
        )
        h_dev = allele_hash_jit(
            padded.ref, padded.alt, padded.ref_len, padded.alt_len
        )
        # only hash + prefix + fallback-flag feed the update path; pack
        # them into ONE fetched buffer — each materialization pays a
        # fixed round trip (see ops/pack.py)
        from annotatedvdb_tpu.ops.pack import (
            pack_vep_outputs_jit,
            transport_verified,
            unpack_vep_outputs,
        )

        # width bound: prefix_len rides a uint8 lane (>255 truncates)
        if transport_verified() and self.store.width <= 255:
            cols = unpack_vep_outputs(
                np.asarray(
                    pack_vep_outputs_jit(
                        h_dev, ann_p.prefix_len, ann_p.host_fallback
                    )
                )
            )
            return cols["h"][:n].copy(), cols["prefix_len"][:n], cols["host_fallback"][:n]
        return (
            np.array(h_dev)[:n],
            np.asarray(ann_p.prefix_len)[:n],
            np.asarray(ann_p.host_fallback)[:n],
        )

    def _mesh_lookup(self, batch: VariantBatch, h: np.ndarray,
                     host_fb: np.ndarray):
        """Resolve one slice's identities through the sharded update step.

        Returns ``(found [N] bool, global id [N] int64)`` in input row
        order.  Over-width rows (``host_fb``) are excluded on device (their
        tokenizer hash is full-string, the device snapshot's is width-
        bounded) and re-resolved with the host shard lookup — the same
        split the single-device path applies."""
        from annotatedvdb_tpu.loaders.vcf_loader import _pad_batch
        from annotatedvdb_tpu.parallel.distributed import (
            distributed_update_step,
        )
        from annotatedvdb_tpu.utils.arrays import mesh_capacity

        n = batch.n
        # pow2 shape bound (one traced mesh program per load) rounded to a
        # shard-count multiple (non-pow2 meshes) — see mesh_capacity
        q = _pad_batch(batch, mesh_capacity(n, self.mesh.devices.size))
        rid_out, found_s, store_row, _counters = distributed_update_step(
            self.mesh, q, self._dev_snapshot, routing="position"
        )
        rid_out = np.asarray(rid_out)
        take = rid_out >= 0
        src = rid_out[take]
        found = np.zeros(n, np.bool_)
        ids = np.full(n, -1, np.int64)
        keep = src < n  # pad rows carry chrom 0 and never come back real
        found[src[keep]] = np.asarray(found_s)[take][keep]
        ids[src[keep]] = np.asarray(store_row)[take][keep]
        # over-width tail: host re-resolve with the full-string hashes the
        # transformer already produced
        for i in np.where(host_fb)[0]:
            code = int(batch.chrom[i])
            shard = self.store.shards.get(code)
            if shard is None:
                continue
            f, idx = shard.lookup(
                batch.pos[i:i + 1], h[i:i + 1],
                batch.ref[i:i + 1], batch.alt[i:i + 1],
                batch.ref_len[i:i + 1], batch.alt_len[i:i + 1],
            )
            found[i] = bool(f[0])
            ids[i] = int(idx[0])
        return found, ids

    def _apply_native(self, res, alg_id: int, commit: bool,
                      lo: int = 0, hi: int | None = None) -> None:
        """Apply rows [lo, hi) of a native-transformed flush: identity
        lookup + RawJson store writes.  No per-row Python dicts are built —
        the four JSONB values ride as raw text
        (``store.variant_store.RawJson``), and sharing one RawJson across a
        doc's alts is safe because raw values are immutable (the store
        materializes fresh objects per row on any merge/read)."""
        from annotatedvdb_tpu.utils.arrays import next_pow2

        if hi is None:
            hi = res.n_rows
        # same shape discipline as _apply_batch: per-alt expansion can
        # exceed the two warmed kernel shapes (p, 2p); split rather than
        # compile a one-off bigger shape (~35s on TPU)
        cap = 2 * next_pow2(self.batch_size)
        if hi - lo > cap:
            for s0 in range(lo, hi, cap):
                self._apply_native(res, alg_id, commit, s0, min(s0 + cap, hi))
            return
        sl = slice(lo, hi)
        batch = VariantBatch(
            res.chrom[sl], res.pos[sl], res.ref[sl], res.alt[sl],
            res.ref_len[sl], res.alt_len[sl],
        )
        # local views: all row indexing below is relative to the slice
        ref_off, ref_slen = res.ref_off[sl], res.ref_slen[sl]
        alt_off, alt_slen = res.alt_off[sl], res.alt_slen[sl]
        ms_off, ms_len = res.ms_off[sl], res.ms_len[sl]
        rk_off, rk_len = res.rk_off[sl], res.rk_len[sl]
        fq_off, fq_len = res.fq_off[sl], res.fq_len[sl]
        vo_off, vo_len = res.vo_off[sl], res.vo_len[sl]
        # identity straight from the transformer: the C++ hash is the
        # device kernel's bit-exact twin, with over-width rows already
        # full-string re-hashed (parity pinned by tests/test_vep_native) —
        # the apply side makes no device round trip at all
        h = res.hash[sl]
        arena = res.arena
        # ASCII arenas (the normal case) decode once; byte offsets then
        # equal str offsets so per-value slicing stays on the str
        arena_s = arena.decode("ascii") if arena.isascii() else None
        check_existing = self.skip_existing
        counters = self.counters
        raw_cache: dict[tuple, RawJson] = {}  # (off, len) -> shared instance
        cache_get = raw_cache.get

        def raw(off: int, length: int):
            if length == 0:
                return {}
            key = (off, length)
            v = cache_get(key)
            if v is None:
                v = raw_cache[key] = RawJson(
                    arena_s[off:off + length] if arena_s is not None
                    else arena[off:off + length].decode()
                )
            return v

        mesh_found = mesh_ids = None
        if self.mesh is not None and self._dev_snapshot is not None:
            mesh_found, mesh_ids = self._mesh_lookup(
                batch, h, res.host_fb[sl].astype(bool)
            )
        for code in np.unique(batch.chrom):
            sel = np.where(batch.chrom == code)[0]
            shard = self.store.shard(int(code))
            if mesh_found is not None:
                found, idx = mesh_found[sel], mesh_ids[sel]
            else:
                found, idx = shard.lookup(
                    batch.pos[sel], h[sel], batch.ref[sel], batch.alt[sel],
                    batch.ref_len[sel], batch.alt_len[sel],
                )
            counters["not_found"] += int((~found).sum())
            rows_i = sel[found]
            ids = idx[found]
            if check_existing and rows_i.size:
                # policy path (rare): first occurrence per store row wins,
                # stored vep_output marks a duplicate
                keep = np.ones(rows_i.size, np.bool_)
                seen_in_batch: set[int] = set()
                for j, row_idx in enumerate(ids.tolist()):
                    if (row_idx in seen_in_batch
                            or shard.get_ann("vep_output", row_idx)
                            is not None):
                        keep[j] = False
                    elif commit:
                        # dry runs buffer nothing: only the stored-value
                        # check applies, matching _apply_batch's gating
                        seen_in_batch.add(row_idx)
                counters["duplicates"] += int((~keep).sum())
                rows_i, ids = rows_i[keep], ids[keep]
            counters["update"] += int(rows_i.size)
            if not commit or rows_i.size == 0:
                continue
            # bulk assembly: the C extension builds each column's wrapper
            # list in one call (consecutive shared spans — a doc's
            # vep_output across its alts — collapse to one instance);
            # fallback is the same assembly as a Python comprehension
            fmask = fq_len[rows_i] > 0
            fq_rows = rows_i[fmask]
            pf = _pyfast() if arena_s is not None else None
            if pf is not None:
                upd_freq = pf.raw_rows(
                    arena_s, fq_off[fq_rows], fq_len[fq_rows], RawJson
                )
                upd_ms = pf.raw_rows(
                    arena_s, ms_off[rows_i], ms_len[rows_i], RawJson
                )
                upd_ranked = pf.raw_rows(
                    arena_s, rk_off[rows_i], rk_len[rows_i], RawJson
                )
                upd_vep = pf.raw_rows(
                    arena_s, vo_off[rows_i], vo_len[rows_i], RawJson
                )
            else:
                upd_freq = [
                    raw(o, l)
                    for o, l in zip(fq_off[fq_rows].tolist(),
                                    fq_len[fq_rows].tolist())
                ]
                upd_ms = [
                    raw(o, l)
                    for o, l in zip(ms_off[rows_i].tolist(),
                                    ms_len[rows_i].tolist())
                ]
                upd_ranked = [
                    raw(o, l)
                    for o, l in zip(rk_off[rows_i].tolist(),
                                    rk_len[rows_i].tolist())
                ]
                upd_vep = [
                    raw(o, l)
                    for o, l in zip(vo_off[rows_i].tolist(),
                                    vo_len[rows_i].tolist())
                ]
            ids = np.asarray(ids, np.int64)
            if fq_rows.size:
                shard.update_annotation(
                    ids[fmask], "allele_frequencies", upd_freq,
                )
            shard.update_annotation(ids, "adsp_most_severe_consequence", upd_ms)
            shard.update_annotation(ids, "adsp_ranked_consequences", upd_ranked)
            shard.update_annotation(ids, "vep_output", upd_vep)
            shard.set_col("row_algorithm_id", ids, alg_id)
            if self.is_adsp:
                shard.set_col("is_adsp_variant", ids, 1)

    def _parse_result(self, annotation: dict) -> list[tuple]:
        """One VEP result -> per-alt pending update rows, as tuples
        ``(code, pos, ref, alt, annotation, freq_values, cleaned, shared)``
        (a dict per row measurably drags the 100k-results/sec path)."""
        self.parser.rank_and_sort(annotation)
        entry = annotation["input"]
        if isinstance(entry, str):
            fields = entry.rstrip("\n").split("\t")
        else:  # pre-parsed dict (ADSP identity-only runs)
            fields = [entry.get(k, ".") for k in ("chrom", "pos", "id", "ref", "alt")]
        chrom_str, pos_str, vid, ref, alt_str = [str(f) for f in fields[:5]]
        # structured replacement for the raw input string
        # (vep_variant_loader.py:279-281)
        pos = int(pos_str)
        annotation["input"] = {
            "chrom": chrom_str, "pos": pos, "id": vid,
            "ref": ref, "alt": alt_str,
        }
        code = chromosome_code(chrom_str)
        if code == 0:
            self.counters["skipped"] += 1
            return []
        ref_snp = vid if vid.startswith("rs") else None
        matching_id = ref_snp if self.is_dbsnp else None
        freqs = VepResultParser.frequencies(annotation, matching_id)
        freq_values = freqs["values"] if freqs else None
        cleaned = VepResultParser.cleaned_result(annotation)

        rows = []
        alts = alt_str.split(",")
        multi = len(alts) - alts.count(".") > 1
        for alt in alts:
            if alt == ".":
                self.counters["skipped"] += 1
                continue
            self.counters["variant"] += 1
            # multi-alt rows share one cleaned dict and must not alias
            # inside the store (deep-merge mutates in place) — flagged here,
            # un-aliased at apply time
            rows.append(
                (code, pos, ref, alt, annotation, freq_values, cleaned, multi)
            )
        return rows

    def _apply_batch(self, rows: list[tuple], alg_id: int, commit: bool,
                     seen_freq: set | None = None) -> None:
        # flushes trigger on raw RESULT count but rows are per-alt expanded:
        # multi-allelic-heavy input can exceed the two warmed kernel shapes
        # (p, 2p).  Split rather than compile a one-off bigger shape (~35s
        # on TPU); sub-batches are independent (earlier writes land before
        # later ones run, so the stored-value duplicate check still holds).
        from annotatedvdb_tpu.utils.arrays import next_pow2
        from annotatedvdb_tpu.types import encode_allele_array

        if seen_freq is None:
            # aliased-frequency tracking must span sub-batch splits AND
            # chromosome groups: two alts of one site sharing a frequency
            # bucket can land in different sub-batches (see the copy logic
            # at the buffer stage below)
            seen_freq = set()
        cap = 2 * next_pow2(self.batch_size)
        if len(rows) > cap:
            for lo in range(0, len(rows), cap):
                self._apply_batch(rows[lo:lo + cap], alg_id, commit,
                                  seen_freq=seen_freq)
            return
        n_rows = len(rows)
        ref_arr, ref_len = encode_allele_array(
            [r[R_REF] for r in rows], self.store.width
        )
        alt_arr, alt_len = encode_allele_array(
            [r[R_ALT] for r in rows], self.store.width
        )
        batch = VariantBatch(
            chrom=np.fromiter(
                (r[R_CODE] for r in rows), np.int8, count=n_rows
            ),
            pos=np.fromiter((r[R_POS] for r in rows), np.int32, count=n_rows),
            ref=ref_arr, alt=alt_arr, ref_len=ref_len, alt_len=alt_len,
        )
        h, prefix, host = self._batch_identity(batch)
        from annotatedvdb_tpu.loaders.vcf_loader import _fnv32_str
        from annotatedvdb_tpu.oracle import normalize_alleles

        check_existing = self.skip_existing  # stored-value probe is ONLY a
        # policy input; without the flag it would be a pure waste of a
        # per-row segment locate (measurable at ~7% of the whole load)
        msc = VepResultParser.most_severe_consequence
        conseqs_of = VepResultParser.allele_consequences
        counters = self.counters
        for code in np.unique(batch.chrom):
            sel = np.where(batch.chrom == code)[0]
            for i in sel[host[sel]]:
                h[i] = _fnv32_str(rows[i][R_REF], rows[i][R_ALT])
            shard = self.store.shard(code)
            found, idx = shard.lookup(
                batch.pos[sel], h[sel], batch.ref[sel], batch.alt[sel],
                batch.ref_len[sel], batch.alt_len[sel],
            )
            # per-row policy first; store writes buffer and apply in ONE
            # vectorized pass per column (the reference likewise buffers
            # jsonb_merge UPDATEs and flushes with execute_values,
            # variant_loader.py:457-476)
            upd_ids: list[int] = []
            upd_freq_ids: list[int] = []
            upd_freq: list = []
            upd_ms: list = []
            upd_ranked: list = []
            upd_vep: list = []
            seen_in_batch: set[int] = set()  # writes are buffered: the
            # stored-value check alone can't see earlier rows of this batch
            for j, i in enumerate(sel):
                if not found[j]:
                    counters["not_found"] += 1
                    continue
                row_idx = int(idx[j])
                r = rows[i]
                if check_existing and (
                        row_idx in seen_in_batch
                        or shard.get_ann("vep_output", row_idx) is not None):
                    counters["duplicates"] += 1
                    continue
                # normalized alleles key the VEP frequency/consequence maps
                if host[i]:
                    _norm_ref, norm_alt = normalize_alleles(
                        r[R_REF], r[R_ALT], snv_div_minus=True
                    )
                else:
                    p = int(prefix[i])
                    norm_alt = r[R_ALT][p:] or "-"
                freq_values = r[R_FREQ]
                allele_freq = None
                if freq_values and norm_alt in freq_values:
                    allele_freq = freq_values[norm_alt]
                ann = r[R_ANN]
                ms = msc(ann, norm_alt)
                ranked = conseqs_of(ann, norm_alt)
                if commit:
                    seen_in_batch.add(row_idx)
                    upd_ids.append(row_idx)
                    if allele_freq is not None:
                        # two alts of one site can normalize to the SAME
                        # allele (CAA->C and CAA->CA both key '-'), handing
                        # two store rows one frequency bucket — deep-merge
                        # mutates in place, so copy exactly the aliased ones
                        fkey = (id(freq_values), norm_alt)
                        if fkey in seen_freq:
                            allele_freq = _fresh(allele_freq)
                        seen_freq.add(fkey)
                        upd_freq_ids.append(row_idx)
                        upd_freq.append(allele_freq)
                    # {} merges as a no-op, so an empty new value never
                    # wipes stored data (the columns are JSONB_UPDATE_FIELDS
                    # in the reference, variant_loader.py:75-76)
                    upd_ms.append(ms if ms else {})
                    upd_ranked.append(ranked if ranked else {})
                    upd_vep.append(
                        _fresh(r[R_CLEANED]) if r[R_SHARED] else r[R_CLEANED]
                    )
                counters["update"] += 1
            if upd_ids:
                ids = np.array(upd_ids, np.int64)
                # un-alias the most-severe column: ms IS ranked's first
                # element (two columns of one row) and deep-merge mutates in
                # place.  One C-level JSON round trip over the whole column
                # replaces ~25 deepcopy frames per dict (values are
                # JSON-pure: json.loads output plus int/bool rank fields).
                upd_ms = _fresh(upd_ms)
                if upd_freq_ids:
                    shard.update_annotation(
                        np.array(upd_freq_ids, np.int64),
                        "allele_frequencies", upd_freq,
                    )
                shard.update_annotation(ids, "adsp_most_severe_consequence", upd_ms)
                shard.update_annotation(ids, "adsp_ranked_consequences", upd_ranked)
                shard.update_annotation(ids, "vep_output", upd_vep)
                shard.set_col("row_algorithm_id", ids, alg_id)
                if self.is_adsp:
                    shard.set_col("is_adsp_variant", ids, 1)
