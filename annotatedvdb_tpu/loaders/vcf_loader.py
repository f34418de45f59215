"""End-to-end VCF load: the TPU-native ``load_vcf_file`` equivalent.

Reference flow (``Load/bin/load_vcf_file.py:80-221`` +
``vcf_variant_loader.py:259-391``): per line, per alt — parse, PK, duplicate
check (one SQL round-trip), normalize, bin lookup (SQL on cache miss), build
COPY string, flush every 500 rows.

Here the batch is the unit: one jitted device program annotates the whole
chunk (normalize + end location + class + bin), one hash + sort kernel
dedups within the batch, one searchsorted join per chromosome shard replaces
the per-variant exists checks, and egress strings are built only for rows
that insert.  "Commit" = appending to the store + a ledger checkpoint of the
input-line cursor; crash recovery replays from the last checkpoint
idempotently (vs the reference's ``--resumeAfter`` log scan,
``variant_loader.py:440-455``).

Execution is an overlapped streaming pipeline (``AVDB_PIPELINE``,
default ``overlapped``): tokenizer scan, dispatch prep, result
processing, and store persistence run as four bounded in-order stages on
their own threads (see ``load_file`` and ``_run_overlapped``), with
byte-identical output to the serial double-buffered loop
(``tests/test_pipeline_modes.py``).
"""

from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from annotatedvdb_tpu import oracle
from annotatedvdb_tpu.io import egress
from annotatedvdb_tpu.io.vcf import VcfBatchReader, VcfChunk
from annotatedvdb_tpu.io.vcf import rs_number as _io_rs_number
from annotatedvdb_tpu.oracle.binindex import closed_form_bin
from annotatedvdb_tpu.types import AnnotatedBatch, VariantBatch
from annotatedvdb_tpu.models.pipeline import annotate_fn
from annotatedvdb_tpu.native import mapping as native_mapping
from annotatedvdb_tpu.ops.hashing import allele_hash_jit
from annotatedvdb_tpu.ops.vrs import VrsDigestGenerator
from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore
from annotatedvdb_tpu.store.variant_store import Segment, SparseValues
from annotatedvdb_tpu.utils.profiling import bulk_load_gc

class _LoadCtx(NamedTuple):
    """Per-load consume context threaded through the pipeline runners —
    everything ``_consume_entry`` needs to commit one chunk."""

    alg_id: int
    commit: bool
    resume_line: int
    mapping_fh: object
    fail_at: str | None
    persist: object
    path: str
    async_store: bool
    test: bool


def _pad_identity_cols(chrom, pos, ref_len, alt_len, pad: int) -> tuple:
    """THE pad-row fill invariant for the thin identity/length columns:
    chrom 0 (never a real code), position sentinel (sorts last, can't
    collide in dedup), 1-base allele lengths.  Single definition shared by
    ``_pad_batch`` (full-batch padding — mesh and update-loader paths) and
    the dispatch stage's width-bucketed upload, so the two can never
    drift."""
    from annotatedvdb_tpu.utils.arrays import POS_SENTINEL

    return (
        np.concatenate([chrom, np.zeros(pad, chrom.dtype)]),
        np.concatenate([pos, np.full(pad, POS_SENTINEL, pos.dtype)]),
        np.concatenate([ref_len, np.ones(pad, ref_len.dtype)]),
        np.concatenate([alt_len, np.ones(pad, alt_len.dtype)]),
    )


def _pad_batch(batch: VariantBatch, n_target: int) -> VariantBatch:
    """Pad to a fixed row count so jitted kernels see a bounded set of
    shapes (variable chunk sizes would recompile the Pallas pipeline per
    batch — tens of seconds each on TPU).  Pad-row fill:
    ``_pad_identity_cols`` + zeroed allele bytes."""
    pad = n_target - batch.n
    if pad <= 0:
        return batch
    chrom, pos, ref_len, alt_len = _pad_identity_cols(
        batch.chrom, batch.pos, batch.ref_len, batch.alt_len, pad
    )
    return VariantBatch(
        chrom,
        pos,
        np.concatenate(
            [batch.ref, np.zeros((pad, batch.width), batch.ref.dtype)]
        ),
        np.concatenate(
            [batch.alt, np.zeros((pad, batch.width), batch.alt.dtype)]
        ),
        ref_len,
        alt_len,
    )


def _slim_annotated(n: int, bin_level, leaf_bin, needs_digest,
                    host_fallback) -> AnnotatedBatch:
    """AnnotatedBatch carrying only the store-path columns; the display
    fields (derivable on demand, see ``store_display_attributes``) are
    zero-filled.  Shared by the packed and per-field fetch paths so the two
    transports cannot drift."""
    zeros_i32 = np.zeros(n, np.int32)
    return AnnotatedBatch(
        prefix_len=zeros_i32, norm_ref_len=zeros_i32,
        norm_alt_len=zeros_i32, end_location=zeros_i32,
        location_start=zeros_i32, location_end=zeros_i32,
        variant_class=np.zeros(n, np.int8),
        is_dup_motif=np.zeros(n, np.bool_),
        bin_level=bin_level, leaf_bin=leaf_bin,
        needs_digest=needs_digest, host_fallback=host_fallback,
    )


class TpuVcfLoader:
    """Insert-or-skip VCF loads into a :class:`VariantStore`."""

    def __init__(
        self,
        store: VariantStore,
        ledger: AlgorithmLedger,
        datasource: str | None = None,
        genome_build: str = "GRCh38",
        batch_size: int = 1 << 16,
        skip_existing: bool = True,
        digester: VrsDigestGenerator | None = None,
        chromosome_map: dict | None = None,
        genome=None,
        mesh=None,
        store_display_attributes: bool = False,
        log=print,
        log_after: int | None = None,
        quarantine=None,
        max_errors: int = -1,
    ):
        """``genome``: optional
        :class:`~annotatedvdb_tpu.genome.ReferenceGenome`; enables batched
        device-side ref-allele validation (mismatches are counted and
        logged, mirroring the reference's validation-on-PK-generation,
        ``vcf_variant_loader.py:234-256``) and canonical GA4GH digests.

        ``mesh``: optional multi-device :class:`jax.sharding.Mesh`; batches
        then annotate through ``distributed_annotate_step`` (chromosome
        re-shard all_to_all + per-shard annotate + psum counters) with
        lossless capacity — the TPU replacement for the reference's
        per-chromosome process pool (``load_vcf_file.py:307-313``).

        ``store_display_attributes``: display attributes are derivable from
        the stored identity columns, so by default they are NOT materialized
        at load time (the egress paths recompute them on demand —
        ``io/pg_egress.py``); True restores the reference's store-everything
        behavior (``createVariant.sql`` display_attributes column)."""
        self.store = store
        self.ledger = ledger
        self.datasource = datasource.lower() if datasource else None
        self.batch_size = batch_size
        self.skip_existing = skip_existing
        if digester is None and genome is not None:
            digester = VrsDigestGenerator(
                genome_build,
                sequence_digests=genome.lazy_digests(),
                reference_bases=genome.reference_bases,
            )
        self.digester = digester or VrsDigestGenerator(genome_build)
        self.genome = genome
        self.chromosome_map = chromosome_map
        self.mesh = mesh if (mesh is not None and mesh.devices.size > 1) else None
        self.log = log
        from annotatedvdb_tpu.genome.assemblies import BUILD_FILES, length_table

        # genome bounds sanity from the shipped length tables; builds we
        # have no table for (custom assemblies) skip the check
        self._chrom_lengths = (
            length_table(genome_build)
            if genome_build.lower() in BUILD_FILES else None
        )
        self.store_display_attributes = store_display_attributes
        # counters + stage rates every N input lines (the reference's
        # --logAfter cadence, ``load_vcf_file.py:29-47``); None = quiet
        from annotatedvdb_tpu.utils.logging import ProgressCadence
        from annotatedvdb_tpu.utils.profiling import DeviceOccupancy, StageTimer

        self._cadence = ProgressCadence(self.log, log_after)
        #: union coverage of per-chunk device in-flight windows (reset per
        #: file by load_file); ``device_idle_fraction`` is the last file's
        #: 1 − busy/wall headline — the bench's proof the device stopped
        #: being idle-dominant
        self._occ = DeviceOccupancy()
        self.device_idle_fraction: float | None = None
        # async store pipeline: built segments queue to a single writer
        # thread (append -> persist -> checkpoint -> cascade merge) while
        # the main thread runs the next chunk's device work.  Entries are
        # (future, payload); payload segments double as the pending
        # membership set (see _membership_segments).  AVDB_ASYNC_STORE=0
        # forces the synchronous path.
        import collections

        self._inflight: "collections.deque" = collections.deque()
        self._writer_pool = None

        #: per-stage wall-clock attribution (ingest/annotate/lookup/egress/
        #: append/persist) — the observability the reference only has as
        #: ad-hoc datetime pairs (``load_vcf_file.py:108-111,136-140``)
        self.timer = StageTimer()
        self._prefetch_pool = None  # lazily spawned by the packed path
        self.counters = {
            "line": 0, "variant": 0, "skipped": 0, "duplicates": 0, "update": 0,
        }
        #: backpressure accounting per stage boundary (ingest / dispatch /
        #: store-writer), accumulated across files like the timer:
        #: ``producer_block_s`` = that boundary's consumer was the
        #: bottleneck, ``consumer_wait_s`` = its producer starved it.
        #: Surfaced as the bench JSON ``queue_stalls`` block and the
        #: run-ledger record
        self.queue_stalls: dict[str, dict] = {}
        #: optional :class:`annotatedvdb_tpu.obs.metrics.LoadObserver`
        #: (chunk-granularity metrics; set by ``ObsSession.attach``)
        self.obs = None
        # quarantine sink + error budget (utils.quarantine): malformed
        # input lines are preserved replayably and counted against
        # --maxErrors; the sink's budget is authoritative when present
        from annotatedvdb_tpu.utils.quarantine import ErrorBudget

        self.quarantine = quarantine
        self._budget = (
            quarantine.budget if quarantine is not None
            else ErrorBudget(max_errors)
        )
        self._rejects_captured = False

    #: metric/run-ledger label for this loader family
    obs_name = "load-vcf"

    def _reject(self, line_no, raw, reason) -> None:
        """Quarantine one rejected input line (may run on the ingest
        thread; the sink and budget are thread-safe).  Raises
        ErrorBudgetExceeded past --maxErrors."""
        if self.quarantine is not None:
            self.quarantine.reject(line_no, raw, reason)
        else:
            self._budget.add(1, context=f"line {line_no}: {reason}")

    def _reject_uncaptured(self, n: int, reason: str) -> None:
        if n <= 0:
            return
        if self.quarantine is not None:
            self.quarantine.reject_uncaptured(n, reason)
        else:
            self._budget.add(n, context=reason)

    def _stall_rec(self, name: str) -> dict:
        return self.queue_stalls.setdefault(name, {
            "items": 0, "producer_block_s": 0.0, "consumer_wait_s": 0.0,
            "max_depth": 0,
        })

    def _merge_stage_stats(self, name: str, stats) -> None:
        """Fold one BoundedStage's StageStats into the cumulative table."""
        from annotatedvdb_tpu.utils.pipeline import merge_stage_stats

        merge_stage_stats(self.queue_stalls, name, stats)

    @property
    def is_adsp(self) -> bool:
        return self.datasource == "adsp"

    @bulk_load_gc()
    def load_file(
        self,
        path: str,
        commit: bool = False,
        test: bool = False,
        fail_at: str | None = None,
        mapping_path: str | None = None,
        resume: bool = True,
        persist=None,
    ) -> dict:
        """Load one VCF; returns counters.

        commit=False runs the full pipeline but discards mutations (the
        reference's default-rollback dry-run integration test, SURVEY.md §4.2);
        ``test`` stops after one batch; ``fail_at`` raises at a given variant
        id (fault injection, ``load_vcf_file.py:224-228``).

        ``persist`` (callable) is invoked before each ledger checkpoint so the
        store's durable state never lags the resume cursor; without it,
        checkpoints only guarantee in-process consistency (the CLI passes
        ``store.save``).

        Execution mode (``AVDB_PIPELINE``): ``overlapped`` (default) runs
        the load as a bounded streaming pipeline — the tokenizer ingests
        chunk *N+1* on a background thread while chunk *N*'s dispatch prep
        (padding, array assembly, device enqueue) runs on a second stage
        thread and chunk *N−1*'s results are forced/deduped/committed on
        this thread, with the store writer a fourth stage behind it.
        ``serial`` keeps the single-thread double-buffered loop — the
        debugging escape hatch.  Both orders are byte-identical by
        construction (in-order bounded queues; counter deltas travel with
        their chunk and apply only at process time), pinned by
        ``tests/test_pipeline_modes.py``."""
        alg_id = self.ledger.begin(
            "TpuVcfLoader.load_file",
            {"file": path, "datasource": self.datasource, "test": test},
            commit,
        )
        resume_line = self.ledger.last_checkpoint(path) if resume else 0
        if resume_line:
            self.log(f"resuming {path} after committed line {resume_line}")
        mapping_fh = open(mapping_path, "wb") if mapping_path else None
        import os as _os

        # async store pipeline (append/persist/checkpoint on the writer
        # thread) — the store side of the r3 bench was 61% of e2e
        # wall-clock, all of it overlappable with the next chunk's device
        # work.  Opt-out for debugging via AVDB_ASYNC_STORE=0.
        async_store = commit and _os.environ.get(
            "AVDB_ASYNC_STORE", "1"
        ) != "0"
        overlapped = _os.environ.get(
            "AVDB_PIPELINE", "overlapped"
        ).lower() != "serial"
        # the per-chunk consume context, threaded through both runners
        ctx = _LoadCtx(alg_id, commit, resume_line, mapping_fh, fail_at,
                       persist, path, async_store, test)
        try:
            from annotatedvdb_tpu.io.prefetch import ingest_chunk_rows
            from annotatedvdb_tpu.ops.pack import transport_wanted
            from annotatedvdb_tpu.utils.profiling import DeviceOccupancy

            # fresh occupancy + stage baselines: this file's device-idle
            # headline and per-stage obs export must not absorb earlier
            # files loaded through the same loader instance
            self._occ = DeviceOccupancy()
            wall0 = self.timer.wall_seconds
            stage0 = self.timer.as_dict()
            reader = VcfBatchReader(
                path,
                batch_size=ingest_chunk_rows(self.batch_size),
                width=self.store.width,
                chromosome_map=self.chromosome_map,
                # the mesh path never uploads packed alleles, and on CPU
                # backends packing saves no transfer; skip the tokenizer's
                # pack work in both cases
                pack_alleles=self.mesh is None and transport_wanted(),
                on_reject=self._reject,
            )
            # content-capturing rejects reach _reject directly (python
            # scanner); native-engine loads budget-count from the chunk
            # malformed counters instead (_entry_from_chunk)
            self._rejects_captured = reader.rejects_captured
            with self.timer.wall():
                if overlapped:
                    self._run_overlapped(reader, ctx)
                else:
                    self._run_serial(reader, ctx)
                self._drain_inflight()
            self.device_idle_fraction = self._occ.idle_fraction(
                self.timer.wall_seconds - wall0
            )
            if self.obs is not None:
                # per-stage busy-seconds deltas for THIS file, plus the
                # device-idle gauge, onto the obs plane
                after = self.timer.as_dict()
                for name, rec in after.items():
                    prev = stage0.get(name, {}).get("seconds", 0.0)
                    self.obs.stage_seconds(name, rec["seconds"] - prev)
                self.obs.device_idle(self.device_idle_fraction)
            self.ledger.finish(alg_id, dict(self.counters))
            # terminal counter line: short files (ending between cadences)
            # must still log their totals
            self._cadence.finish(
                self.counters["line"], self.counters, self.timer.summary()
            )
        finally:
            if self._budget.count:
                # rejected-row total (captured + uncaptured) — recorded on
                # success AND abort so the run ledger always witnesses it
                self.counters["rejected"] = self._budget.count
            try:
                # earlier chunks' queued commits land even when a later
                # chunk raised (failAt semantics: everything before the
                # fault commits, the fault's own chunk does not)
                self._drain_inflight()
            finally:
                if mapping_fh:
                    mapping_fh.close()
        self.counters["alg_id"] = alg_id
        return dict(self.counters)

    # -- pipeline runners ---------------------------------------------------

    def _run_serial(self, reader: VcfBatchReader, ctx: "_LoadCtx") -> None:
        """Single-thread double-buffered loop: chunk k+1's device work
        (annotate + hash, async under jax) is dispatched before chunk k's
        host-side processing forces its results, so device compute and
        transfers still overlap host work — but ingest, dispatch prep, and
        process all share this thread's clock."""
        resume_line = ctx.resume_line
        chunks = iter(reader)
        pending: tuple | None = None
        stop = False
        while not stop:
            with self.timer.stage("ingest"):
                chunk = next(chunks, None)
            entry = None
            if chunk is not None:
                entry = self._dispatch_entry(
                    self._entry_from_chunk(chunk, resume_line)
                )
            if pending is not None:
                stop = self._consume_entry(pending, ctx)
            pending = entry
            if chunk is None:
                break

    PIPELINE_DEPTH = 2  # unconsumed chunks per stage boundary (backpressure)

    def _run_overlapped(self, reader: VcfBatchReader, ctx) -> None:
        """Overlapped streaming executor: ingest thread -> dispatch thread
        -> this (process) thread -> store-writer thread, each boundary a
        bounded queue.

        Stage roles: the INGEST thread runs the tokenizer scan (the C call
        releases the GIL, so it genuinely overlaps host numpy work);
        DISPATCH pads/assembles host arrays and enqueues the annotate+hash
        programs (async dispatch returns before execution); PROCESS forces
        chunk results one step behind dispatch, runs dedup/membership, and
        builds segments; the writer thread appends + persists.

        Chunks travel seq-tagged: the prefetcher may emit them SHUFFLED
        (``AVDB_INGEST_SHUFFLE_SEED``, ``io/prefetch.py``) and dispatch is
        order-independent, but a :class:`Resequencer` restores source
        order before this consumer — so counters, identity first-wins,
        checkpoint cursors, and ``--maxErrors`` accounting all apply in
        chunk order regardless of schedule.  Serial/overlapped (and
        shuffled/in-order) parity is structural, not incidental."""
        resume_line = ctx.resume_line
        from annotatedvdb_tpu.io.prefetch import (
            ingest_prefetch_depth,
            ingest_shuffle_seed,
        )
        from annotatedvdb_tpu.utils.pipeline import BoundedStage, Resequencer

        depth = ingest_prefetch_depth(self.PIPELINE_DEPTH)
        ingest = reader.iter_prefetched(
            depth=depth, timer=self.timer,
            shuffle_seed=ingest_shuffle_seed(), tagged=True,
        )
        dispatch = BoundedStage(
            ingest,
            fn=lambda tagged: (
                tagged[0],
                self._dispatch_entry(
                    self._entry_from_chunk(tagged[1], resume_line)
                ),
            ),
            depth=depth,
            name="vcf-dispatch",
            boundary="dispatch",
        )
        tracer = self.timer.tracer
        entries = Resequencer(dispatch)
        try:
            for entry in entries:
                if tracer is not None:
                    # queue-depth gauge samples, one counter track per
                    # boundary (per CHUNK, so ~zero cost)
                    tracer.counter(
                        "queue_depth", ingest=ingest.depth(),
                        dispatch=dispatch.depth(),
                        resequencer=entries.held(),
                        store_writer=len(self._inflight),
                    )
                if self._consume_entry(entry, ctx):
                    break
        finally:
            # stop both producers promptly (a failed/aborted load must not
            # leave a tokenizer thread scanning a multi-GB file); pending
            # dispatched device work is abandoned — jax arrays are just
            # dropped, and un-applied chunks never touched the counters.
            # UPSTREAM first: the dispatch thread may be blocked pulling
            # from ingest, and ingest.close() unblocks it immediately
            ingest.close()
            dispatch.close()
            # fold this run's backpressure numbers into the cumulative
            # stall table (the close()s above settled both stage threads)
            self._merge_stage_stats("ingest", ingest.stats)
            self._merge_stage_stats("dispatch", dispatch.stats)
            # a stage error whose envelope never reached this consumer
            # (dropped by the close) is the abort's ROOT CAUSE — log it
            # unless it is the very exception already propagating
            import sys as _sys

            propagating = _sys.exc_info()[1]
            for _name, _st in (("ingest", ingest), ("dispatch", dispatch)):
                if _st.error is not None and _st.error is not propagating:
                    self.log(
                        f"pipeline {_name} stage failed during teardown: "
                        f"{_st.error!r}"
                    )

    def _entry_from_chunk(self, chunk: VcfChunk, resume_line: int) -> tuple:
        """Ingest-side accounting for one chunk: the counter delta that
        travels with it (applied only when the chunk is consumed, so
        checkpoints never count an uncommitted chunk) and whether it needs
        device dispatch at all."""
        delta = {
            "line": chunk.counters.get("line", 0),
            "skipped": (
                chunk.counters.get("skipped_alt", 0)
                + chunk.counters.get("skipped_contig", 0)
            ),
            "malformed": chunk.counters.get("malformed", 0),
        }
        needs_dispatch = True
        if chunk.batch.n == 0:
            needs_dispatch = False  # trailing counters-only chunk
        elif resume_line and chunk.line_number[-1] <= resume_line:
            # fully-replayed chunk: count it skipped, never dispatch
            delta["skipped"] += chunk.batch.n
            needs_dispatch = False
        return chunk, delta, needs_dispatch

    def _dispatch_entry(self, entry: tuple) -> tuple:
        """Dispatch stage: enqueue the chunk's device work (no result is
        forced here — see ``_dispatch_chunk``)."""
        chunk, delta, needs_dispatch = entry
        handles = None
        if needs_dispatch:
            with self.timer.stage("dispatch"):
                handles = self._dispatch_chunk(chunk)
            # device in-flight window opens at enqueue; _process_chunk
            # closes it when the results are forced (DeviceOccupancy)
            handles["t0"] = time.perf_counter()
        return chunk, handles, delta

    def _consume_entry(self, entry: tuple, ctx: "_LoadCtx") -> bool:
        """Process one dispatched chunk on the consumer thread: apply its
        counter delta, force + commit it, checkpoint.  Returns True when
        the load should stop (test mode)."""
        (alg_id, commit, resume_line, mapping_fh, fail_at, persist, path,
         async_store, test) = ctx
        chunk, handles, delta = entry
        t_chunk = time.perf_counter() if self.obs is not None else 0.0
        for key, v in delta.items():
            self.counters[key] = self.counters.get(key, 0) + v
        if delta["malformed"] and not self._rejects_captured:
            # native tokenizer: malformed lines were counted without
            # content — budget-check them HERE, on the process thread in
            # chunk order, so --maxErrors trips at the same input line no
            # matter how the prefetcher scheduled the chunks
            self._reject_uncaptured(
                delta["malformed"],
                "malformed VCF line(s); native engine captured no content "
                "— re-run with AVDB_INGEST_ENGINE=python to quarantine them",
            )
        if handles is None:
            # resume-replayed / counters-only chunks are NOT observed:
            # avdb_rows_total means rows actually processed (the update
            # loader's resume path skips them the same way), so a resumed
            # load's metrics never inflate past the work it really did
            return False
        # fault injection fires when the chunk holding the variant is
        # PROCESSED — earlier chunks commit first, exactly like the
        # reference's per-line failAt
        if fail_at is not None and fail_at in chunk.variant_id:
            raise RuntimeError(f"failAt variant reached: {fail_at}")
        self._prune_inflight()
        payload = self._process_chunk(
            chunk, handles, alg_id, commit, resume_line, mapping_fh,
            defer_commit=async_store,
        )
        self._log_progress()
        if commit and async_store:
            # checkpoint even for insert-less chunks (an all-duplicate
            # chunk must still advance the resume cursor)
            self._enqueue_commit(
                payload, persist, alg_id, path,
                int(chunk.line_number[-1]),
            )
        elif commit:
            with self.timer.stage("persist"):
                if persist is not None:
                    persist()
                self.ledger.checkpoint(
                    alg_id, path, int(chunk.line_number[-1]),
                    dict(self.counters),
                )
        if self.obs is not None:
            self.obs.chunk(
                chunk.batch.n, seconds=time.perf_counter() - t_chunk
            )
        if test:
            self.log("test mode: stopping after first batch")
            return True
        return False

    def _log_progress(self) -> None:
        self._cadence.maybe_log(
            self.counters["line"], self.counters, self.timer.summary()
        )

    def warmup(self) -> None:
        """Pre-compile the device kernels for this loader's padded batch
        shape (first XLA/Pallas compile costs tens of seconds on TPU; a
        steady-state load should not pay it mid-stream).  Optional — loads
        work without it, the first chunk just compiles lazily."""
        from annotatedvdb_tpu.io.synth import synthetic_batch
        from annotatedvdb_tpu.utils.arrays import next_pow2

        # chunks are line-aligned at <= batch_size and ``_dispatch_chunk``
        # min-pads to next_pow2(batch_size): ONE compiled shape per load
        # (the only exception — a single source line wider than the whole
        # batch — compiles lazily)
        batch = synthetic_batch(
            next_pow2(self.batch_size), width=self.store.width
        )
        if self.mesh is None:
            # probe the nibble transport (verdict consulted per-chunk by
            # _dispatch_chunk) and compile the full-shape inflate preamble
            # outside the measured stream
            from annotatedvdb_tpu.ops.pack import (
                encode_alleles_nibble,
                inflate_alleles_jit,
                nibble_verified,
                transport_wanted,
            )

            if transport_wanted() and nibble_verified():
                enc = encode_alleles_nibble(batch.ref, batch.alt)
                if enc is not None:
                    r, a = inflate_alleles_jit(
                        enc[0], enc[1], batch.ref.shape[1]
                    )
                    np.asarray(r), np.asarray(a)
        ann = self._annotate(batch)
        # mirror _dispatch_chunk's exact op chain (annotate + hash; in-batch
        # dedup is host-side) so no kernel is left to compile mid-load
        h = allele_hash_jit(
            batch.ref, batch.alt, batch.ref_len, batch.alt_len
        )
        np.asarray(ann.variant_class), np.asarray(h)
        if self.mesh is None and not self._will_pack():
            # width-bucketed dispatch (see _dispatch_chunk): pre-compile
            # EVERY pow2 bucket the runtime gate can produce so a
            # native-engine load never compiles mid-stream — the gate
            # condition here must mirror _dispatch_chunk's exactly
            w = 8
            while w < batch.ref.shape[1]:
                a = annotate_fn()(
                    batch.chrom, batch.pos,
                    np.ascontiguousarray(batch.ref[:, :w]),
                    np.ascontiguousarray(batch.alt[:, :w]),
                    np.minimum(batch.ref_len, w),
                    np.minimum(batch.alt_len, w),
                )
                np.asarray(a.variant_class)
                w *= 2
        if self.mesh is None and not self.store_display_attributes:
            # compile the output packer AND verify the packed transport
            # bit-exactly reproduces the individual fields on this backend
            # (bitcast byte order is hardware-defined; probe it here, not
            # mid-load)
            from annotatedvdb_tpu.ops.pack import (
                pack_outputs_jit,
                transport_verified,
                transport_wanted,
                unpack_outputs,
            )

            # run the transport probe here so its 4-row pack compile and
            # verdict never land inside the first measured chunk; when it
            # fails, _dispatch_chunk falls back to per-field fetches — no
            # packing to warm
            if transport_wanted() and transport_verified():
                import jax.numpy as jnp

                dup = jnp.zeros(h.shape, jnp.bool_)  # unused lane (host dedup)
                packed = pack_outputs_jit(
                    h, dup, ann.bin_level, ann.leaf_bin,
                    ann.needs_digest, ann.host_fallback,
                )
                cols = unpack_outputs(np.asarray(packed))
                for name, ref_val in (
                    ("h", h), ("bin_level", ann.bin_level),
                    ("leaf_bin", ann.leaf_bin),
                    ("needs_digest", ann.needs_digest),
                    ("host_fallback", ann.host_fallback),
                ):
                    if not (cols[name] == np.asarray(ref_val)).all():
                        raise RuntimeError(
                            f"packed transport probe passed but full-shape "
                            f"pack mismatched in {name!r}"
                        )

    def _will_pack(self) -> bool:
        """Single definition of the packed-transport predicate: dispatch
        (skip hash kernel / width-bucket) and warmup (which bucket shapes
        to pre-compile) must agree or a load compiles mid-stream."""
        from annotatedvdb_tpu.ops.pack import (
            transport_verified,
            transport_wanted,
        )

        return (
            not self.store_display_attributes
            and transport_wanted() and transport_verified()
        )

    def _annotate(self, batch: VariantBatch) -> AnnotatedBatch:
        """One annotate step: distributed over the mesh when present, else
        the fastest verified single-device kernel (Pallas on TPU)."""
        if self.mesh is None:
            return annotate_fn()(
                batch.chrom, batch.pos, batch.ref, batch.alt,
                batch.ref_len, batch.alt_len,
            )
        return self._annotate_distributed(batch)

    def _fetch_annotations(self, ann_p, n: int, host_rows) -> AnnotatedBatch:
        """Materialize annotate outputs on host, fetching only what the
        store path consumes (bin columns + identity flags, ~7B/row) unless
        display attributes are being stored (then everything, ~33B/row)."""
        if self.store_display_attributes:
            out = AnnotatedBatch(*(np.asarray(x)[:n] for x in ann_p))
            return out._replace(host_fallback=host_rows)
        return _slim_annotated(
            n, np.asarray(ann_p.bin_level)[:n],
            np.asarray(ann_p.leaf_bin)[:n],
            np.asarray(ann_p.needs_digest)[:n], host_rows,
        )

    def _annotate_distributed(self, batch: VariantBatch) -> AnnotatedBatch:
        """Mesh path: pad to a device multiple, run the sharded step with
        position-block routing (spreads chromosome-sorted input across all
        shards; chromosome locality is irrelevant while dedup/store are
        host-side), and scatter results back to input row order via the
        returned row ids.  Capacity is the exact lossless minimum for the
        batch: a drop is a bug, not an accounting line."""
        from annotatedvdb_tpu.parallel.distributed import (
            distributed_annotate_step,
            position_block_owner,
        )

        n_dev = self.mesh.devices.size
        padded = _pad_batch(batch, batch.n + (-batch.n) % n_dev)
        owner = position_block_owner(padded.chrom, padded.pos, n_dev)
        ann, rid, _counts, dropped, _n_fb = distributed_annotate_step(
            self.mesh, padded, owner=owner
        )
        if int(np.asarray(dropped)):
            raise RuntimeError(
                f"distributed annotate dropped {int(np.asarray(dropped))} rows "
                "despite lossless capacity"
            )
        rid = np.asarray(rid)
        take = rid >= 0
        src = rid[take]
        # only chrom>0 rows come back (the input may itself carry pad rows
        # from the pow2 shape bound; their outputs are sliced away upstream)
        n_real = int((batch.chrom > 0).sum())
        if src.size != n_real:
            raise RuntimeError(
                f"row-id coverage {src.size} != real row count {n_real}"
            )
        out = {}
        for field in AnnotatedBatch._fields:
            vals = np.asarray(getattr(ann, field))
            arr = np.empty((batch.n,) + vals.shape[1:], vals.dtype)
            arr[src] = vals[take]
            out[field] = arr
        return AnnotatedBatch(**out)

    def _load_chunk(self, chunk: VcfChunk, alg_id, commit, resume_line, mapping_fh):
        """Synchronous dispatch+process of one chunk (the path callers that
        re-chunk through the insert loader use; ``load_file`` itself
        pipelines the two halves across chunks)."""
        self._process_chunk(
            chunk, self._dispatch_chunk(chunk), alg_id, commit,
            resume_line, mapping_fh,
        )

    def _dispatch_chunk(self, chunk: VcfChunk) -> dict:
        """Enqueue the chunk's device work without forcing any result.

        Under jax's async dispatch the annotate/hash/dedup programs (and the
        input transfer) run while the host processes the previous chunk.
        The dedup here uses the device hash; rows flagged host_fallback are
        re-deduped at process time with their full-string host hashes (see
        ``_process_chunk``)."""
        from annotatedvdb_tpu.utils.arrays import next_pow2

        batch = chunk.batch
        # tail chunks pad UP to the steady-state shape: recompiling the
        # annotate/hash/dedup kernels for a one-off tail shape costs ~35s
        # on TPU — far more than annotating the pad rows
        n_target = max(next_pow2(batch.n), next_pow2(self.batch_size))
        if self.mesh is not None:
            # the sharded step scatters through numpy already (synchronous);
            # pipelining matters for the single-device transfer-bound path
            padded = _pad_batch(batch, n_target)
            ann_p = self._annotate_distributed(padded)
            if chunk.h_native is not None:
                return {"ann_p": ann_p, "h_dev": None,
                        "h_host": chunk.h_native}
            h_dev = allele_hash_jit(
                padded.ref, padded.alt, padded.ref_len, padded.alt_len
            )
            return {"ann_p": ann_p, "h_dev": h_dev}
        from annotatedvdb_tpu.ops.pack import (
            encode_alleles_nibble,
            inflate_alleles_jit,
            nibble_verified,
            transport_verified,
            transport_wanted,
        )

        # decided up front: the packed transport folds the DEVICE hash into
        # its 10-byte row, so configurations that will pack must upload
        # full-width arrays and run the hash kernel; everything else rides
        # the tokenizer hash when present
        will_pack = self._will_pack()

        # thin columns pad once here; the wide allele matrices pad at their
        # UPLOAD width below (padding full-width and then re-slicing to the
        # bucket copied ~13MB/chunk for nothing on bucketed loads)
        pad = n_target - batch.n
        if pad > 0:
            chrom_p, pos_p, rl_p, al_p = _pad_identity_cols(
                batch.chrom, batch.pos, batch.ref_len, batch.alt_len, pad
            )
        else:
            chrom_p, pos_p = batch.chrom, batch.pos
            rl_p, al_p = batch.ref_len, batch.alt_len
        width = batch.ref.shape[1]

        def pad_alleles(w: int):
            """[n_target, w] ref/alt: slice to the upload bucket FIRST so
            the pad copy moves only the bytes being uploaded."""
            ref, alt = batch.ref[:, :w], batch.alt[:, :w]
            if pad <= 0:
                return np.ascontiguousarray(ref), np.ascontiguousarray(alt)
            z = np.zeros((pad, w), batch.ref.dtype)
            return np.concatenate([ref, z]), np.concatenate([alt, z])

        # the allele matrices are ~90% of the upload bytes; send them
        # nibble-packed when the chunk's alphabet allows and inflate on
        # device (out-of-alphabet chunks upload raw — rare symbolic alleles).
        # The native tokenizer pre-packs during its scan; chunks without
        # pre-packed arrays encode here UNLESS the reader already tried and
        # failed (alleles_packable False) or the backend probe failed.
        # CPU backends skip packing entirely (no transfer to save).
        if not (transport_wanted() and nibble_verified()):
            enc = None
        elif chunk.ref_packed is not None:
            pk = n_target - chunk.ref_packed.shape[0]
            if pk:
                z = np.zeros((pk, chunk.ref_packed.shape[1]), np.uint8)
                enc = (
                    np.concatenate([chunk.ref_packed, z]),
                    np.concatenate([chunk.alt_packed, z]),
                )
            else:
                enc = (chunk.ref_packed, chunk.alt_packed)
        elif chunk.alleles_packable is False:
            enc = None  # reader's scan already found exotic bytes
        else:
            enc = encode_alleles_nibble(*pad_alleles(width))
        # uploads ride the bounded-retry wrapper: a transient runtime
        # error re-sends the buffer instead of killing a multi-hour load
        # (utils.retry)
        from annotatedvdb_tpu.utils.retry import device_put as _dput

        if enc is not None:
            ref_dev, alt_dev = inflate_alleles_jit(
                _dput(enc[0]), _dput(enc[1]), width,
            )
            dev = (
                _dput(chrom_p), _dput(pos_p),
                ref_dev, alt_dev,
                _dput(rl_p), _dput(al_p),
            )
        else:
            # width bucketing: annotate compute (and upload bytes) scale
            # with the allele-matrix width, but dbSNP/gnomAD chunks top out
            # at ~8 bytes inside width-49 arrays.  Slice to the pow2 bucket
            # covering this chunk's longest allele — annotate outputs are
            # width-independent (they depend on bytes+lengths only), and
            # the identity hash is NOT affected because this path is taken
            # only with a tokenizer-computed hash (h_native), which is
            # always store-width.  Bucketing keeps the compile count
            # O(log width).
            w = width
            if (chunk.h_native is not None and not will_pack and width > 8):
                w_act = int(max(int(rl_p.max()), int(al_p.max()), 1))
                wb = next_pow2(max(w_act, 8))
                if wb < width:
                    w = wb
            ref_p, alt_p = pad_alleles(w)
            dev = (
                _dput(chrom_p), _dput(pos_p),
                _dput(ref_p), _dput(alt_p),
                _dput(rl_p), _dput(al_p),
            )
        ann_p = annotate_fn()(*dev)
        # the packed transport needs the device hash (folded into its
        # 10-byte row); every other configuration uses the tokenizer's
        # host hash when present (skipping the hash kernel AND its result
        # fetch — on a 1-core CPU host that is ~15% of e2e)
        if chunk.h_native is not None and not will_pack:
            return {"ann_p": ann_p, "h_dev": None, "h_host": chunk.h_native}
        h_dev = allele_hash_jit(dev[2], dev[3], dev[4], dev[5])
        handles = {"ann_p": ann_p, "h_dev": h_dev}
        if will_pack:
            # every materialized array is its own transfer; pack the six
            # per-row outputs on device so process time fetches once
            # (_will_pack already probed the transport's bit-exactness on
            # this backend).
            import jax.numpy as jnp

            from annotatedvdb_tpu.ops.pack import pack_outputs_jit

            # the dup lane of the packed layout is unused since in-batch
            # dedup moved into the host identity sort; zeros keep the
            # 10-byte row format (and its bit-exactness probe) stable
            packed = pack_outputs_jit(
                h_dev, jnp.zeros(h_dev.shape, jnp.bool_),
                ann_p.bin_level, ann_p.leaf_bin,
                ann_p.needs_digest, ann_p.host_fallback,
            )
            # the device->host copy releases the GIL: prefetch it on a
            # worker thread so the transfer overlaps the next chunk's
            # ingest/dispatch instead of blocking process time
            handles["packed"] = self._prefetch().submit(
                np.asarray, packed
            )
        return handles

    # -- async store writer --------------------------------------------------

    MAX_INFLIGHT_COMMITS = 2  # bounds pending-segment memory + probe work

    def _writer(self):
        if self._writer_pool is None:
            import concurrent.futures

            self._writer_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="avdb-store"
            )
        return self._writer_pool

    def _membership_segments(self, code: int) -> list:
        """Segments to probe for membership of chromosome ``code``: pending
        (enqueued, possibly not yet appended) first, then a snapshot of the
        shard's list.  Only the writer thread mutates the shard's list, so
        the snapshot is consistent; pending-then-snapshot ordering plus the
        writer's append-before-completion means no segment can be missed."""
        segs = [
            seg
            for _fut, payload in self._inflight
            for c, seg in payload
            if c == code
        ]
        shard = self.store.shards.get(int(code))
        if shard is not None:
            segs.extend(list(shard.segments))
        return segs

    def _commit_job(self, payload, persist, alg_id, path, line, counters):
        """Writer-thread store commit for one chunk: append its segments,
        persist + checkpoint, THEN cascade-merge — merging after the persist
        keeps disk writes append-only (clean+clean merges reference their
        constituents' files instead of rewriting, Segment.merge)."""
        n_rows = sum(seg.n for _c, seg in payload)
        with self.timer.stage("append", items=n_rows):
            for code, seg in payload:
                self.store.shard(code).append_segment(seg)
        with self.timer.stage("persist"):
            if persist is not None:
                persist()
            self.ledger.checkpoint(alg_id, path, line, counters)
        with self.timer.stage("maintain"):
            for code in {c for c, _seg in payload}:
                self.store.shard(code).maintain()

    def _enqueue_commit(self, payload, persist, alg_id, path, line) -> None:
        """Queue one chunk's store commit; bounded in-flight depth applies
        backpressure by blocking on the oldest job (blocked seconds land in
        the ``store-writer`` stall record: the writer is the bottleneck)."""
        fut = self._writer().submit(
            self._commit_job, payload or [], persist, alg_id, path, line,
            dict(self.counters),
        )
        self._inflight.append((fut, payload or []))
        rec = self._stall_rec("store-writer")
        rec["items"] += 1
        rec["max_depth"] = max(rec["max_depth"], len(self._inflight))
        if len(self._inflight) > self.MAX_INFLIGHT_COMMITS:
            self._wait_writer(self.MAX_INFLIGHT_COMMITS)

    def _prune_inflight(self) -> None:
        """Drop completed commits (surfacing writer exceptions promptly)."""
        while self._inflight and self._inflight[0][0].done():
            fut, _ = self._inflight.popleft()
            fut.result()

    def _wait_writer(self, keep: int) -> None:
        """Block on the oldest queued commits until at most ``keep`` are
        in flight: the caller's thread waiting on the store writer.  The
        seconds go to the ``store-writer`` stall record and the episode is
        an ``avdb.wait.store-writer`` annotation on this thread, so a
        capture reads the gap as this wait, not as the writer's persist."""
        from annotatedvdb_tpu.utils.profiling import annotation

        rec = self._stall_rec("store-writer")
        with annotation("avdb.wait.store-writer", side="producer"):
            t0 = time.perf_counter()
            try:
                while len(self._inflight) > keep:
                    fut, _ = self._inflight.popleft()
                    fut.result()
            finally:
                rec["producer_block_s"] = round(
                    rec["producer_block_s"] + (time.perf_counter() - t0), 4
                )

    def _drain_inflight(self) -> None:
        if self._inflight:
            self._wait_writer(0)

    def _prefetch(self):
        """Single-worker transfer thread (lazy: configurations that never
        take the packed path spawn no thread).  Ordering is preserved —
        one outstanding prefetch per pipelined chunk."""
        if self._prefetch_pool is None:
            import concurrent.futures

            self._prefetch_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="avdb-fetch"
            )
        return self._prefetch_pool

    def close(self) -> None:
        """Release the prefetch + store-writer workers (idempotent; loaders
        are reusable until closed)."""
        if self._prefetch_pool is not None:
            self._prefetch_pool.shutdown(wait=False)
            self._prefetch_pool = None
        if self._writer_pool is not None:
            self._writer_pool.shutdown(wait=True)
            self._writer_pool = None

    def _process_chunk(self, chunk: VcfChunk, handles: dict, alg_id, commit,
                       resume_line, mapping_fh, defer_commit: bool = False):
        """Force the chunk's device results, filter to inserts, build the
        sorted segments.  With ``defer_commit`` the built segments are
        RETURNED (for the async store writer) instead of appended inline;
        the caller owns appending + persisting them in order."""
        batch = chunk.batch
        if self._chrom_lengths is not None:
            oob = batch.pos.astype(np.int64) > self._chrom_lengths[
                np.clip(batch.chrom.astype(np.int64), 0, 25)
            ]
            n_oob = int(oob.sum())
            if n_oob:  # counted + logged, not dropped (the reference's
                # SeqRepo validation would likewise only flag these)
                self.counters["out_of_bounds"] = (
                    self.counters.get("out_of_bounds", 0) + n_oob
                )
                i = int(np.argmax(oob))
                self.log(
                    f"{n_oob} positions beyond chromosome bounds, e.g. "
                    f"{chunk.variant_id[i]}"
                )
        # ---- force the dispatched device results (annotate + bin + hash +
        # in-batch dedup).  Only the fields the host path consumes are
        # fetched back.
        with self.timer.stage("annotate", items=batch.n):
            n = batch.n
            ann_p = handles["ann_p"]
            if handles.get("packed") is not None:
                # single-fetch path: one [n_padded, 10] uint8 transfer
                # carries hash + bin + flags (ops/pack.py), prefetched on
                # the worker thread at dispatch time
                from annotatedvdb_tpu.ops.pack import unpack_outputs

                cols = unpack_outputs(handles["packed"].result())
                h_p = cols["h"]
                host_rows = cols["host_fallback"][:n]
            elif handles.get("h_host") is not None:
                # tokenizer-computed hash: no device fetch to force
                h_p = handles["h_host"]
                host_rows = np.asarray(ann_p.host_fallback)[:n]
                cols = None
            else:
                h_p = np.array(handles["h_dev"])
                host_rows = np.asarray(ann_p.host_fallback)[:n]
                cols = None
            # long alleles are truncated in the device arrays: re-hash them
            # from the original strings so identity never collides on a
            # shared prefix.  (In-batch dedup happens on host, inside the
            # per-chromosome identity sort below, so the corrected hashes
            # are always the ones deduped on.)  Copy-on-write: the common
            # all-short chunk reads the tokenizer/unpack buffer directly,
            # only a chunk that actually re-hashes pays for a private copy
            fb = np.where(host_rows)[0]
            if fb.size:
                h_p = h_p.copy()
                for i in fb:
                    h_p[i] = _fnv32_str(chunk.refs[i], chunk.alts[i])
            h = h_p[:n]
            if cols is not None:
                ann = _slim_annotated(
                    n, cols["bin_level"][:n], cols["leaf_bin"][:n],
                    cols["needs_digest"][:n], host_rows,
                )
            else:
                ann = self._fetch_annotations(ann_p, n, host_rows)
        t0 = handles.get("t0")
        if t0 is not None:
            # close this chunk's device in-flight window (opened at
            # dispatch enqueue); the synchronous _load_chunk path carries
            # no t0 and records nothing
            self._occ.record(t0, time.perf_counter())
        # replayed rows within a partially-committed chunk
        replay = chunk.line_number <= resume_line

        # ---- in-batch dedup + membership filtering; egress strings only
        # for inserts.  Both ride ONE stable host sort per chromosome by
        # identity key: in-batch duplicates are adjacent-equal rows after
        # the sort (byte-confirmed; same first-wins semantics as the
        # ops.dedup device kernel, which the single-device path no longer
        # needs), and the surviving rows are already in sorted-merge append
        # order.  Membership is probed against in-flight (built but not yet
        # appended) segments FIRST, then a snapshot of the shard's segment
        # list — in that order, so a segment the async writer moves from
        # pending into the store mid-probe is seen at least once
        # (double-probing is idempotent; a gap would drop the
        # read-your-writes guarantee the reference gets from DB
        # transactions, database/variant.py:287-309).
        insert_rows: list[np.ndarray] = []
        with self.timer.stage("lookup", items=batch.n):
            from annotatedvdb_tpu.store.variant_store import combined_key

            # chromosome codes are a tiny bounded alphabet: bincount beats
            # np.unique's O(n log n) sort (same sorted output)
            codes = np.flatnonzero(
                np.bincount(batch.chrom, minlength=26)
            ) if batch.n else ()
            for code in codes:
                rows = np.where((batch.chrom == code) & ~replay)[0]
                if rows.size == 0:
                    continue
                key = combined_key(batch.pos[rows], h[rows])
                # position-sorted sources arrive key-sorted already: detect
                # violations in O(n).  Any position inversion IS a key
                # inversion (key = pos<<32 | h and h < 2^32), so when every
                # violation sits between EQUAL positions the disorder is
                # purely hash ties at multi-allelic sites — repair just
                # those runs instead of re-sorting the whole chunk (the
                # steady state of a sorted source drops from O(n log n)
                # back to O(n))
                if rows.size > 1:
                    viol = np.flatnonzero(key[1:] < key[:-1])
                    if viol.size:
                        pos_r = batch.pos[rows]
                        if bool((pos_r[viol] == pos_r[viol + 1]).all()):
                            # position is then globally non-decreasing, so
                            # only the equal-pos runs holding a violation
                            # need repair.  One stable argsort over ALL
                            # their rows at once is exact: runs are
                            # maximal, pos forms the key's high bits, so
                            # keys from distinct runs never interleave and
                            # the sort decomposes per-run.  Everything here
                            # is a vector pass — no per-site Python loop.
                            run_id = np.empty(pos_r.size, np.int64)
                            run_id[0] = 0
                            np.cumsum(pos_r[1:] != pos_r[:-1],
                                      out=run_id[1:])
                            dirty = np.zeros(int(run_id[-1]) + 1, np.bool_)
                            dirty[run_id[viol]] = True
                            idx = np.flatnonzero(dirty[run_id])
                            order = np.argsort(key[idx], kind="stable")
                            rows[idx] = rows[idx][order]
                            key[idx] = key[idx][order]
                        else:
                            order = np.argsort(key, kind="stable")
                            rows, key = rows[order], key[order]
                if rows.size > 1:
                    cand = np.where(key[1:] == key[:-1])[0]
                    if cand.size:
                        a, b = rows[cand], rows[cand + 1]
                        same = (
                            (batch.ref_len[b] == batch.ref_len[a])
                            & (batch.alt_len[b] == batch.alt_len[a])
                            & (batch.ref[b] == batch.ref[a]).all(axis=1)
                            & (batch.alt[b] == batch.alt[a]).all(axis=1)
                        )
                        if same.any():
                            keep = np.ones(rows.size, np.bool_)
                            keep[cand[same] + 1] = False
                            self.counters["duplicates"] += int((~keep).sum())
                            rows, key = rows[keep], key[keep]
                segs = self._membership_segments(int(code))
                if self.skip_existing and segs:
                    # probe columns materialize only if a probe actually
                    # fires: monotonic loads prune every segment on key
                    # range alone, and gathering the two [N, W] allele
                    # matrices up front would copy ~25MB per chunk just to
                    # throw it away
                    qref = found = None
                    for seg in segs:
                        # range pruning: monotonic loads probe only the
                        # (usually zero) segments overlapping this chunk's
                        # key range — key is sorted here
                        if (seg.n == 0 or seg.key_max < key[0]
                                or seg.key_min > key[-1]):
                            continue
                        if qref is None:
                            qpos, qh = batch.pos[rows], h[rows]
                            qref, qalt = batch.ref[rows], batch.alt[rows]
                            qrl = batch.ref_len[rows]
                            qal = batch.alt_len[rows]
                            found = np.zeros(rows.size, np.bool_)
                        elif found.all():
                            break
                        f, _ = seg.probe(key, qpos, qh, qref, qalt, qrl, qal)
                        found |= f
                    if found is not None:
                        self.counters["duplicates"] += int(found.sum())
                        rows = rows[~found]
                if rows.size:
                    insert_rows.append(rows)

        if not insert_rows:
            return None
        with self.timer.stage("gather", items=int(sum(r.size for r in insert_rows))):
            sel = np.concatenate(insert_rows)
            # all-insert sorted chunks (the steady state of a bulk load from
            # a position-sorted source) select every row in input order:
            # skip the per-column fancy-index copies entirely
            ident = sel.size == batch.n and bool(
                (sel == np.arange(batch.n)).all()
            )
            # np.take(..., axis=0) is the same gather as x[sel] but ~2.5x
            # faster on the 2D allele matrices (contiguous row memcpys)
            take = lambda x: np.take(np.asarray(x), sel, axis=0)
            sub = batch if ident else VariantBatch(*(take(x) for x in batch))
            if not self.store_display_attributes:
                # slim annotations: only 4 of the 12 fields carry data
                # (_slim_annotated zero-fills the display fields) — gather
                # those, rebuild the zeros at the new size
                sub_ann = ann if ident else _slim_annotated(
                    sel.size,
                    take(ann.bin_level),
                    take(ann.leaf_bin),
                    take(ann.needs_digest),
                    take(ann.host_fallback),
                )
            else:
                sub_ann = ann if ident else AnnotatedBatch(
                    *(take(x) for x in ann)
                )
            over = (
                (sub.ref_len > self.store.width)
                | (sub.alt_len > self.store.width)
            )
            needs_digest = np.asarray(sub_ann.needs_digest)
            host_fallback = np.asarray(sub_ann.host_fallback)
            # rows that read their allele strings whatever else happens:
            # retained long alleles, digest PKs, host-side bin recompute
            strings_anyway = (
                over | needs_digest.astype(bool) | host_fallback.astype(bool)
            )
            # rs numbers come pre-parsed from the reader (one int64 column);
            # the string forms are only materialized on the PK path below
            if chunk.rs_number is not None:
                rs_sel = chunk.rs_number[sel]
                rs_weird_sel = (
                    chunk.rs_weird[sel] if chunk.rs_weird is not None
                    else None
                )
            else:  # chunks from non-reader builders: derive both per row
                from annotatedvdb_tpu.io.vcf import rs_is_weird

                rs_strs = [chunk.ref_snp[i] for i in sel]
                rs_sel = np.array([_rs_number(r) for r in rs_strs], np.int64)
                rs_weird_sel = np.array(
                    [rs_is_weird(r, n) for r, n in zip(rs_strs, rs_sel)],
                    dtype=bool,
                )
            # a mapping line is, for most rows, a function of the columns:
            # those rows (``fast``) are written as bytes by the native pass
            # and never become Python strings.  Every other row — and every
            # row of a chunk without the reader's flag columns, or of a
            # process without the native library (``fast`` None) — takes
            # the scalar route: the definition, and the oracle
            fast = None
            if mapping_fh is not None and chunk.id_verbatim is not None:
                scalar_only = (
                    strings_anyway
                    | chunk.id_verbatim[sel] | chunk.is_multi_allelic[sel]
                )
                if rs_weird_sel is not None:
                    scalar_only |= rs_weird_sel
                fast = native_mapping.fast_rows(sub, ~scalar_only)
            # allele-string object arrays cost a PyObject per row: build
            # them only for the rows that read them — ``part`` (None =
            # every row): the scalar route's mapping rows and
            # ``strings_anyway``; genome validation and display attributes
            # read every row's.  The common insert path stores the
            # fixed-width byte matrices directly and never needs strings.
            if (self.genome is not None or self.store_display_attributes
                    or (mapping_fh is not None and fast is None)):
                part = None
            elif fast is not None:
                part = np.flatnonzero(~fast)
            else:
                part = np.flatnonzero(strings_anyway)
            if part is None:
                strs, strs_ann = sub, sub_ann
            else:
                pick = lambda x: np.take(np.asarray(x), part, axis=0)
                strs = VariantBatch(*(pick(x) for x in sub))
                strs_ann = _slim_annotated(
                    part.size, pick(sub_ann.bin_level),
                    pick(sub_ann.leaf_bin), pick(needs_digest),
                    pick(host_fallback),
                )

            def spread(values):
                """``part``'s values at their rows of ``sub`` (object)."""
                if part is None:
                    return values.astype(object)
                out = np.empty(sel.size, object)
                out[part] = values
                return out

            if strs.n:
                # vectorized view-decode; only the over-width tail needs
                # the parser sidecar's original strings (a lazy per-row
                # span decode, ~µs each)
                refs, alts = map(spread, egress.decode_alleles(strs))
                for j in np.where(over)[0]:
                    refs[j] = chunk.refs[int(sel[j])]
                    alts[j] = chunk.alts[int(sel[j])]
            else:
                refs = alts = None

        if self.genome is not None:
            # validate only the rows actually being inserted (post dedup /
            # replay / existing filters) so counts match 'variant' semantics
            from annotatedvdb_tpu.genome.refgenome import validate_ref_batch

            ok = validate_ref_batch(self.genome, sub, refs)
            n_bad = int((~ok).sum())
            if n_bad:
                self.counters["ref_mismatch"] = (
                    self.counters.get("ref_mismatch", 0) + n_bad
                )
                bad = np.where(~ok)[0][:5]
                self.log(
                    f"{n_bad} ref-allele mismatches vs genome, e.g. "
                    + ", ".join(chunk.variant_id[int(sel[j])] for j in bad)
                )
        with self.timer.stage("egress", items=int(sel.size)):
            # literal ids and PKs are built for the string rows only: the
            # scalar route's mapping lines read them, and digest PKs (rare
            # tail) are always needed — the store retains them as the
            # row's record PK
            if strs.n and (mapping_fh is not None or needs_digest.any()):
                # assembled from the reader's pre-parsed rs column; only
                # 'weird' refsnp rows materialize their sidecar string.
                # The literal id strings are shared with the mapping
                # stage's vectorized vid assembly below.
                at = slice(None) if part is None else part
                src = sel[at]
                literal = egress.metaseq_ids(strs, refs[at], alts[at])
                pks = spread(egress.primary_keys_from_ints(
                    strs, strs_ann, rs_sel[at], self.digester,
                    refs[at], alts[at],
                    rs_weird=(
                        None if rs_weird_sel is None else rs_weird_sel[at]
                    ),
                    ref_snp_at=lambda j: chunk.ref_snp[int(src[j])],
                    literal=literal,
                ))
                literal = spread(literal)
            else:
                pks = literal = None
            # display attributes are derivable: built here only when the
            # store-everything flag asks for them (see __init__)
            display = (
                egress.display_attributes(sub, sub_ann, refs, alts)
                if self.store_display_attributes else None
            )
            # device bin outputs are undefined for host-fallback rows:
            # recompute
            bin_level = np.asarray(sub_ann.bin_level).copy()
            leaf_bin = np.asarray(sub_ann.leaf_bin).copy()
            for j in np.where(host_fallback)[0]:
                end = oracle.infer_end_location(refs[j], alts[j], int(sub.pos[j]))
                bin_level[j], leaf_bin[j] = closed_form_bin(int(sub.pos[j]), end)
            sub_ann = sub_ann._replace(bin_level=bin_level, leaf_bin=leaf_bin)
            # the chunk's distinct ltree paths and each row's index into
            # them: the native pass copies a path's bytes, the scalar
            # route reads its rows' strings
            paths, path_idx = (
                egress.bin_path_table(sub, sub_ann)
                if mapping_fh is not None else (None, None)
            )

        payload: list[tuple[int, Segment]] | None = None
        if commit:
            # build the sorted segments HERE (cheap: insert rows are already
            # key-sorted per chromosome, so Segment.build skips its argsort
            # and gathers) — appending/merging/persisting them is the store
            # side of the pipeline, which runs on the async writer thread
            # when defer_commit is set (overlapping the next chunk's device
            # work) or inline otherwise.
            with self.timer.stage("build", items=int(sel.size)):
                payload = []
                offset = 0
                freqs = _freq_slices(chunk, insert_rows)
                for at, rows in enumerate(insert_rows):
                    k = rows.size
                    j = slice(offset, offset + k)
                    jj = np.arange(offset, offset + k)
                    code = int(batch.chrom[rows[0]])
                    if freqs is None:
                        annotations = {
                            "allele_frequencies": [
                                chunk.frequencies[i] for i in rows
                            ],
                        }
                    else:
                        annotations = (
                            {} if freqs[at] is None
                            else {"allele_frequencies": freqs[at]}
                        )
                    if display is not None:
                        annotations["display_attributes"] = (
                            display[offset:offset + k]
                        )
                    seg = Segment.build(
                        {
                            "pos": sub.pos[j],
                            "h": h[rows],
                            "ref_len": sub.ref_len[j],
                            "alt_len": sub.alt_len[j],
                            "ref_snp": rs_sel[jj],
                            "is_multi_allelic": chunk.is_multi_allelic[rows],
                            "is_adsp_variant": np.full(
                                k, 1 if self.is_adsp else -1, np.int8
                            ),
                            "bin_level": bin_level[jj],
                            "leaf_bin": leaf_bin[jj],
                            "needs_digest": needs_digest[jj],
                            "row_algorithm_id": np.full(k, alg_id, np.int32),
                        },
                        sub.ref[j],
                        sub.alt[j],
                        annotations=annotations,
                        # the rare tails (digest PKs / width-truncated
                        # alleles) know their rows
                        digest_pk=_at_rows(
                            needs_digest[j], lambda jx: pks[offset + jx]
                        ),
                        # retain original strings for width-truncated rows:
                        # the device arrays can't reconstruct them and later
                        # joins (CADD) and VCF export need the exact alleles
                        long_alleles=_at_rows(
                            over[j],
                            lambda jx: (refs[offset + jx], alts[offset + jx]),
                        ),
                    )
                    payload.append((code, seg))
                    offset += k
            if not defer_commit:
                with self.timer.stage("append", items=int(sel.size)):
                    for code, seg in payload:
                        sh = self.store.shard(code)
                        sh.append_segment(seg)
                        sh.maintain()
                payload = None
        self.counters["variant"] += int(sel.size)

        if mapping_fh is not None:
            with self.timer.stage("mapping", items=int(sel.size)):
                # the scalar route's rows, as rows of ``sub``.
                # Mapping ids: rows whose ID is '.' or an rs accession use
                # the assembled chr:pos:ref:altcol form — for single-alt
                # rows that IS the metaseq id already built vectorized;
                # only verbatim-ID and multi-allelic rows (rare in dbSNP
                # loads) materialize their sidecar string
                scalar = (
                    np.arange(sel.size) if fast is None
                    else np.flatnonzero(~fast)
                )
                lines = []
                if scalar.size:
                    at_chunk = sel[scalar]
                    if chunk.id_verbatim is not None:
                        slow = (
                            chunk.id_verbatim[at_chunk]
                            | chunk.is_multi_allelic[at_chunk]
                        )
                        vids = literal[scalar]
                        for j in np.where(slow)[0]:
                            vids[j] = chunk.variant_id[int(at_chunk[j])]
                        vids = vids.tolist()
                    else:
                        vids = [chunk.variant_id[i] for i in at_chunk]
                    lines = egress.mapping_lines(
                        vids, pks[scalar], paths[path_idx[scalar]].tolist()
                    )
                # one write per chunk
                if fast is None:
                    mapping_fh.write(
                        ("\n".join(lines) + "\n").encode("ascii")
                    )
                else:
                    mapping_fh.write(native_mapping.mapping_lines(
                        sub, rs_sel, path_idx, paths.tolist(), fast, lines
                    ))
                egress.mapping_stats["rows"] += int(sel.size)
                egress.mapping_stats["scalar_rows"] += len(lines)
                egress.mapping_stats["native_rows"] += (
                    int(sel.size) - len(lines)
                )
        return payload


def _freq_slices(chunk: VcfChunk, slices: list) -> list | None:
    """One sparse FREQ column (or None) a slice of chunk rows: a reader
    that flags its FREQ rows is asked for those rows' values only, every
    slice's in one call (a FREQ-less chunk, the common case, for none).
    None for a chunk without the flags: the caller takes a per-row list."""
    if chunk.has_freq is None:
        return None
    flagged = [np.flatnonzero(chunk.has_freq[rows]) for rows in slices]
    at = np.concatenate([rows[f] for rows, f in zip(slices, flagged)])
    if not at.size:
        return [None] * len(slices)
    values = np.split(chunk.freq_values(at),
                      np.cumsum([f.size for f in flagged])[:-1])
    return [SparseValues(f, v) if f.size else None
            for f, v in zip(flagged, values)]


def _at_rows(flags: np.ndarray, value_at) -> SparseValues | None:
    """``value_at(row)`` for the flagged rows of a slice, as the sparse
    column :meth:`Segment.build` takes; None when no row is flagged."""
    at = np.flatnonzero(flags)
    if not at.size:
        return None
    return SparseValues(at, [value_at(jx) for jx in at.tolist()])


def _fnv32_str(ref: str, alt: str) -> np.uint32:
    """Host FNV-1a over full allele strings (identity hash for rows wider
    than the device arrays) — domain-separated from the device hash by
    hashing lengths first, like ``ops/hashing.py``."""
    h = np.uint32(2166136261)
    prime = np.uint32(16777619)
    data = bytes([len(ref) & 0xFF, len(alt) & 0xFF]) + ref.encode() + alt.encode()
    for b in data:
        h = np.uint32((int(h) ^ b) * int(prime) & 0xFFFFFFFF)
    return h


# single source of truth for the rs-parse rule (mirrored byte-for-byte by
# the native tokenizer's rs_number_of); re-exported here for the loaders
_rs_number = _io_rs_number
