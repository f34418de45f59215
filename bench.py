"""Benchmark: device kernel throughput AND end-to-end VCF -> committed store.

Two numbers, one JSON line:

- ``value`` (the headline metric): END-TO-END variants/sec — VCF bytes on
  disk through parse -> annotate -> PK/bin -> dedupe -> store commit with
  per-batch durable checkpoints, the whole pipeline the reference's
  ``load_vcf_file.py`` runs against Postgres.  ``vs_baseline`` is the ratio
  against the BASELINE.md gnomAD-chr1 gate (~90M variants in <10 min =
  150k variants/sec);
- ``kernel_variants_per_sec``: steady-state throughput of the jitted
  annotate+bin device pipeline alone (the >=1M/s/chip north star, reported
  as ``kernel_vs_target``).

``stages`` breaks the end-to-end load down by pipeline stage
(ingest / annotate / lookup / egress / append / persist) via the loader's
built-in StageTimer.  Under the overlapped executor these are per-stage
BUSY seconds on their pipeline threads; ``stage_wall`` reports the load's
wall-clock against the busy sum (overlap > 1 = stages genuinely ran
concurrently).  Legs with multiple measured runs report the MEDIAN as
their headline (``median_headline``), with every run recorded.

Row count via AVDB_BENCH_ROWS (default 2M — enough to amortize store
behavior into the steady-state regime).  At ~10M rows on the shared
1-core host the measured rate drops to ~40% of the 2M figure: the
resident store (~1GB) plus the writer thread's persist traffic saturate
DRAM, slowing every stage uniformly — per-stage profiles show no
algorithmic growth (maintain stays zero, probes stay range-pruned).
"""

import gc
import json
import os
import random
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from annotatedvdb_tpu.io.synth import write_synth_vcf


def settle():
    """Measurement hygiene between legs on the shared 1-core host: drain
    dirty page-cache writeback (a prior leg's store/VCF writes otherwise
    steal CPU from the measured window), take the GC hit outside the
    clock, and freeze surviving objects out of the collector — a mid-leg
    gen2 collection over a prior leg's millions of live objects (store
    rows, RawJson values) otherwise lands inside whichever leg runs next.
    None of that belongs to any leg's own throughput."""
    try:
        os.sync()
    except (AttributeError, OSError):
        pass
    gc.collect()
    gc.freeze()

BATCH = 1 << 20          # kernel bench: 1M variants per step
WIDTH = 16               # covers the dbSNP/gnomAD allele-length distribution
WARMUP_STEPS = 3
MEASURE_STEPS = 10
KERNEL_TARGET = 1_000_000.0          # variants/sec/chip north star
END_TO_END_TARGET = 90_000_000 / 600.0  # gnomAD chr1 in <10 min
# Open-loop target: the r06 headline metric (max sustainable offered QPS
# at the p99 SLO) is a different methodology from the r05 records'
# closed-loop ``serve_point_qps`` — never compare the two across records.
SERVE_OPEN_LOOP_QPS_TARGET = 10_000.0  # SLO-gated offered queries/sec
EXPORT_TOKENS_TARGET = 1_000_000.0   # corpus-export tokens/sec north star

E2E_ROWS = int(os.environ.get("AVDB_BENCH_ROWS", 1 << 21))


def median_headline(runs: list) -> float:
    """The reporting policy for EVERY leg: the median of its measured runs
    (single-run legs trivially report that run).  Replaces the VEP leg's
    best-of-2, which read optimistically against the other legs'
    single-run numbers (ADVICE r5 #3 / VERDICT r5 weak #4).  Best and
    worst stay visible in each leg's ``runs`` list."""
    import statistics

    return round(statistics.median(runs), 1)


def bench_kernel():
    import jax

    from annotatedvdb_tpu.io.synth import synthetic_batch
    from annotatedvdb_tpu.models.pipeline import best_annotate_pipeline

    # on TPU this selects the fused Pallas kernel (verified for compile +
    # parity on a probe batch first); elsewhere the portable jnp pipeline
    pipeline_fn, kernel_kind = best_annotate_pipeline()

    batch = synthetic_batch(BATCH, width=WIDTH)
    args = [jax.device_put(x) for x in batch]

    def step():
        return pipeline_fn(*args)

    for _ in range(WARMUP_STEPS):
        jax.block_until_ready(step())
    # steady-state throughput: enqueue all steps, block once — per-step
    # blocking measures the host<->device round-trip, not the pipeline
    t0 = time.perf_counter()
    out = None
    for _ in range(MEASURE_STEPS):
        out = step()
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    # release this leg's device buffers + compiled programs: their
    # allocator footprint measurably degrades the LATER legs' numbers on
    # the shared 1-core host (the e2e leg re-warms its own kernels outside
    # its clock)
    del args, out
    jax.clear_caches()
    gc.collect()
    return BATCH * MEASURE_STEPS / dt, kernel_kind


def write_synth_vep(vcf_path: str, out_path: str, n_results: int) -> int:
    """VEP JSON results for the first ``n_results`` variants of the VCF
    (transcript consequences + colocated frequencies, the update-path
    shape the chr22 BASELINE config measures)."""
    import json as _json

    written = 0
    with open(vcf_path) as src, open(out_path, "w", buffering=1 << 20) as out:
        for line in src:
            if line.startswith("#"):
                continue
            chrom, pos, vid, ref, alt = line.split("\t")[:5]
            alt0 = alt.split(",")[0]
            # VEP keys consequences/frequencies by the left-normalized
            # allele ('-' when normalization empties it, e.g. deletions)
            p = 0
            while p < min(len(ref), len(alt0)) and ref[p] == alt0[p]:
                p += 1
            norm = alt0[p:] or "-"
            out.write(_json.dumps({
                "input": f"{chrom}\t{pos}\t{vid}\t{ref}\t{alt0}",
                "most_severe_consequence": "missense_variant",
                "transcript_consequences": [
                    {"consequence_terms": ["missense_variant"],
                     "variant_allele": norm, "gene_id": "ENSG0001",
                     "impact": "MODERATE"},
                    {"consequence_terms": ["intron_variant"],
                     "variant_allele": norm, "gene_id": "ENSG0001"},
                ],
                "colocated_variants": [
                    {"id": vid, "allele_string": f"{ref}/{alt0}",
                     "frequencies": {norm: {"gnomad": 0.01, "af": 0.02}}}
                ],
            }) + "\n")
            written += 1
            if written >= n_results:
                break
    return written


def bench_end_to_end(metrics_out: str | None = None,
                     trace_out: str | None = None):
    from annotatedvdb_tpu.conseq import ConsequenceRanker
    from annotatedvdb_tpu.loaders import TpuVcfLoader
    from annotatedvdb_tpu.loaders.vep_loader import TpuVepLoader
    from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore
    from annotatedvdb_tpu.types import DEFAULT_ALLELE_WIDTH

    work = tempfile.mkdtemp(prefix="avdb_bench_")
    try:
        vcf = os.path.join(work, "bench.vcf")
        write_synth_vcf(vcf, E2E_ROWS)
        vcf_bytes = os.path.getsize(vcf)
        store_dir = os.path.join(work, "vdb")
        store = VariantStore(width=DEFAULT_ALLELE_WIDTH)
        ledger = AlgorithmLedger(os.path.join(work, "ledger.jsonl"))
        loader = TpuVcfLoader(
            store, ledger, datasource="dbSNP", batch_size=1 << 18,
            log=lambda *a: None,
        )
        # --metrics-out / --trace-out: full telemetry capture of the
        # measured load (host span tracer on every pipeline thread +
        # Prometheus textfile on exit).  Span emission is per STAGE per
        # chunk (~10 events x ~16 chunks), so the measured rate moves by
        # well under the acceptance budget (<=2%).
        obs_session = None
        if metrics_out or trace_out:
            from annotatedvdb_tpu.obs import ObsSession

            obs_session = ObsSession(
                "bench-e2e", vcf,
                {"rows": E2E_ROWS, "batch_size": 1 << 18,
                 "pipeline": os.environ.get("AVDB_PIPELINE", "overlapped")},
                metrics_out=metrics_out, trace_out=trace_out,
            )
            obs_session.attach(loader)
        loader.warmup()  # steady-state measurement: compile outside the clock
        from annotatedvdb_tpu.utils.profiling import device_trace

        # median_headline policy, same as the VEP sub-leg: the measured
        # load runs AVDB_BENCH_E2E_RUNS times (run 0 is canonical — its
        # store feeds the VEP leg and wears the obs capture; later runs
        # are fresh throwaway stores) and the headline is the median run.
        # A single sample on the shared host read ±25% run to run.
        n_e2e = max(1, int(os.environ.get("AVDB_BENCH_E2E_RUNS", "5")))
        e2e_rates: list = []
        e2e_samples: list = []
        for run in range(n_e2e):
            if run:
                r_store = VariantStore(width=DEFAULT_ALLELE_WIDTH)
                r_loader = TpuVcfLoader(
                    r_store, ledger, datasource="dbSNP",
                    batch_size=1 << 18, log=lambda *a: None,
                )
                r_loader.warmup()
                r_dir = os.path.join(work, f"vdb.s{run}")
            else:
                r_store, r_loader, r_dir = store, loader, store_dir
            settle()  # drain writeback (synth VCF / prior run's store)
            # AVDB_PROFILE=<dir> captures an XLA trace of the canonical
            # load; the clock sits INSIDE the trace context so profiler
            # start/flush never skews the reported rate
            with device_trace(
                os.environ.get("AVDB_PROFILE") if run == 0 else None
            ):
                t0 = time.perf_counter()
                counters_r = r_loader.load_file(
                    vcf, commit=True,
                    # durable per-checkpoint persistence (incremental)
                    persist=lambda: r_store.save(r_dir),
                )
                r_store.save(r_dir)
                dt_r = time.perf_counter() - t0
            e2e_rates.append(round(counters_r["variant"] / dt_r, 1))
            e2e_samples.append((dt_r, r_loader.device_idle_fraction))
            if run == 0:
                counters = counters_r
        vps = median_headline(e2e_rates)
        # the median run's own wall/idle back the headline (best and
        # worst stay visible in the ``runs`` list)
        mid = min(range(n_e2e), key=lambda i: abs(e2e_rates[i] - vps))
        dt, idle_fraction = e2e_samples[mid]
        if obs_session is not None:
            # exports happen OUTSIDE the measured window
            obs_session.finish(ledger, counters, store=store)

        # update path: VEP results over a slice of the loaded store.
        # Measured N times (run 0 against the live store, later runs
        # against the pristine pre-VEP store reloaded from disk) with the
        # MEDIAN as the headline — this sub-leg runs last so it wears the
        # most host drift, and best-of-N was flagged as optimistic
        # (ADVICE r5 #3).  Every run is recorded.
        vep_json = os.path.join(work, "bench.vep.json")
        n_vep = write_synth_vep(vcf, vep_json, min(E2E_ROWS // 5, 200_000))
        vep_runs = []
        n_runs = max(1, int(os.environ.get("AVDB_BENCH_VEP_RUNS", "3")))
        for run in range(n_runs):
            if run == 0:
                vep_store = store
            else:
                from annotatedvdb_tpu.store import VariantStore as _VS

                vep_store = _VS.load(store_dir)  # pre-VEP state (never saved after)
            vep_loader = TpuVepLoader(
                vep_store, ledger, ConsequenceRanker(), datasource="dbSNP",
                log=lambda *a: None,
            )
            vep_loader.warmup()  # compile outside the clock, like the VCF leg
            settle()  # prior store writes are still landing on disk
            t1 = time.perf_counter()
            vep_counters = vep_loader.load_file(vep_json, commit=True)
            vep_runs.append(round(n_vep / (time.perf_counter() - t1), 1))
        vep_rps = median_headline(vep_runs)
        vep_dt = n_vep / vep_rps

        return {
            "variants_per_sec": vps,
            "runs": e2e_rates,
            "variants": counters["variant"],
            "duplicates": counters["duplicates"],
            "seconds": round(dt, 2),
            "vcf_mb": round(vcf_bytes / 1e6, 1),
            "mb_per_sec": round(vcf_bytes / 1e6 / dt, 1),
            # spine-v2 marker: records produced by the chunked-prefetch
            # ingest spine (io/prefetch.py).  The schema checker requires
            # device_idle_fraction + stage detail when this key is present
            # (pre-spine BENCH history keeps validating without them)
            "ingest_spine": 2,
            # 1 − (union of device in-flight windows / wall): the proof
            # the measured rate is not an idle-device artifact
            # (utils.profiling.DeviceOccupancy; lower bound on true idle)
            "device_idle_fraction": round(
                idle_fraction if idle_fraction is not None else 0.0, 4
            ),
            "shuffle_seed": os.environ.get("AVDB_INGEST_SHUFFLE_SEED"),
            "stages": loader.timer.as_dict(),
            # wall vs per-stage busy time: the overlapped executor runs
            # ingest/dispatch/process/store-writer concurrently, so busy
            # seconds legitimately sum past wall (overlap > 1 proves the
            # pipeline overlapped instead of hiding stages in each other)
            "stage_wall": loader.timer.wall_dict(),
            # backpressure accounting per stage boundary: producer_block_s
            # (that boundary's consumer was the bottleneck) and
            # consumer_wait_s (its producer starved it) make "overlap 3.1x
            # but dispatch starved 40% of wall" a recorded fact
            "queue_stalls": loader.queue_stalls,
            "pipeline": os.environ.get("AVDB_PIPELINE", "overlapped"),
            "vep_update": {
                "results_per_sec": vep_rps,
                "runs": vep_runs,
                "updated": vep_counters["update"],
                "seconds": round(vep_dt, 2),
            },
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


def bench_cadd_join(n_variants: int = 100_000, table_positions: int = 300_000):
    """BASELINE measurement config #3 (CADD whole-genome SNV join): stream
    a scored-SNV table once and join against the store's device-shaped
    columns — the reference equivalent is a server-side cursor with one
    tabix fetch per variant (``load_cadd_scores.py:98-141``)."""
    from annotatedvdb_tpu.io.synth import synthetic_cadd_setup
    from annotatedvdb_tpu.loaders.cadd_loader import TpuCaddUpdater
    from annotatedvdb_tpu.store import AlgorithmLedger

    work = tempfile.mkdtemp(prefix="avdb_cadd_")
    try:
        cadd_dir = os.path.join(work, "cadd")
        store, _expected = synthetic_cadd_setup(
            cadd_dir, n_variants, table_positions
        )
        up = TpuCaddUpdater(
            store, AlgorithmLedger(os.path.join(work, "l.jsonl")), cadd_dir,
            log=lambda *a: None,
        )
        # dry run first (throwaway updater: counters must not leak into
        # the measured run): compiles the join kernel's shapes outside the
        # clock, same discipline as every other leg's warmup — a real
        # whole-genome pass amortizes those compiles over hours
        TpuCaddUpdater(
            store, AlgorithmLedger(os.path.join(work, "lw.jsonl")),
            cadd_dir, log=lambda *a: None,
        ).update_all(commit=False)
        settle()
        t0 = time.perf_counter()
        counters = up.update_all(commit=True)
        dt = time.perf_counter() - t0
        n_rows = 3 * table_positions
        return {
            "table_rows_per_sec": round(n_rows / dt, 1),
            "matched": counters["snv"],
            "variants": n_variants,
            "seconds": round(dt, 2),
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


def bench_qc_update(n_rows: int = 100_000):
    """BASELINE measurement config #4 shape (ADSP QC pVCF batch
    annotation): stream a QC pVCF against a loaded store, writing
    ``adsp_qc`` JSONB + the ``is_adsp_variant`` flag
    (``update_from_qc_pvcf_file.py`` semantics)."""
    from annotatedvdb_tpu.loaders import TpuVcfLoader
    from annotatedvdb_tpu.loaders.qc_loader import TpuQcPvcfLoader
    from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore
    from annotatedvdb_tpu.types import DEFAULT_ALLELE_WIDTH

    work = tempfile.mkdtemp(prefix="avdb_qc_")
    try:
        vcf = os.path.join(work, "base.vcf")
        write_synth_vcf(vcf, n_rows)
        store = VariantStore(width=DEFAULT_ALLELE_WIDTH)
        ledger = AlgorithmLedger(os.path.join(work, "l.jsonl"))
        TpuVcfLoader(store, ledger, batch_size=1 << 16,
                     log=lambda *a: None).load_file(vcf, commit=True)
        qc = os.path.join(work, "qc.vcf")
        with open(vcf) as src, open(qc, "w", buffering=1 << 20) as out:
            out.write("##fileformat=VCFv4.2\n"
                      "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\n")
            k = 0
            for line in src:
                if line.startswith("#"):
                    continue
                chrom, pos, vid, ref, alt = line.split("\t")[:5]
                flt = "PASS" if k % 3 else "LowQual"
                out.write(f"{chrom}\t{pos}\t{vid}\t{ref}\t{alt}\t50\t{flt}"
                          f"\tABHet=0.5;AC={k % 7}\tGT:DP\n")
                k += 1
        loader = TpuQcPvcfLoader(store, ledger, "r4", log=lambda *a: None)
        settle()
        t0 = time.perf_counter()
        counters = loader.load_file(qc, commit=True)
        dt = time.perf_counter() - t0
        return {
            "rows_per_sec": round(k / dt, 1),
            "updated": counters["update"],
            "seconds": round(dt, 2),
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _build_serve_store(work: str, n_rows: int):
    """(store_dir, point ids) — one committed synth store for the serving
    legs (closed-loop in-process AND the open-loop fleet sweep)."""
    from annotatedvdb_tpu.loaders import TpuVcfLoader
    from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore
    from annotatedvdb_tpu.types import DEFAULT_ALLELE_WIDTH

    vcf = os.path.join(work, "base.vcf")
    write_synth_vcf(vcf, n_rows)
    store_dir = os.path.join(work, "store")
    store = VariantStore(width=DEFAULT_ALLELE_WIDTH)
    ledger = AlgorithmLedger(os.path.join(work, "l.jsonl"))
    TpuVcfLoader(store, ledger, batch_size=1 << 16,
                 log=lambda *a: None).load_file(vcf, commit=True)
    store.save(store_dir)
    ids = []
    with open(vcf) as fh:
        for line in fh:
            if line.startswith("#"):
                continue
            chrom, pos, _vid, ref, alt = line.split("\t")[:5]
            ids.append(f"{chrom}:{pos}:{ref}:{alt.split(',')[0]}")
    return store_dir, ids


def _retire_conn(sel, c) -> None:
    """Unregister + close a dead bench connection: a closed-by-peer fd is
    permanently readable, and one left in the selector turns the client
    into a busy-poll loop that corrupts the rest of the step."""
    try:
        sel.unregister(c.sock)
    except (KeyError, ValueError, OSError):
        pass
    try:
        c.sock.close()
    except OSError:
        pass


class _OpenLoopConn:
    """One connection's open-loop state (selector-driven client)."""

    __slots__ = ("sock", "fd", "outbox", "scheds", "rel", "buf", "sent",
                 "recvd", "offset", "writable")

    def __init__(self, sock, offset: float, rel):
        self.sock = sock
        self.fd = sock.fileno()
        self.outbox = bytearray()
        self.scheds: list = []
        self.rel = rel  # precomputed arrival offsets (burst-grouped)
        self.buf = b""
        self.sent = 0
        self.recvd = 0
        self.offset = offset  # start stagger so conns never beat together
        self.writable = False


def _open_loop_step(host: str, port: int, blobs: list, offered_qps: float,
                    duration_s: float, conns: int, timeout_s: float = 30.0):
    """One offered-load step against a live serve fleet.

    OPEN loop: every request has a deterministic scheduled arrival and is
    sent at (or as soon after as possible) that time regardless of any
    response — a slow server eats queueing delay (measured: completion
    minus SCHEDULED arrival, the honest open-loop latency), it does not
    slow the offered rate.  The whole client is ONE selector thread:
    a thread-per-connection client on this 2-core container adds tens of
    milliseconds of GIL/scheduler jitter to every percentile, drowning
    the quantity under measurement.  Arrivals come in 10ms BURSTS (every
    request in a burst shares its burst's arrival time): syscalls cost
    hundreds of microseconds in this sandboxed kernel, so per-request
    packets would make both client and server syscall-bound — a bursty
    arrival process is also the harsher, more production-shaped load.

    Error classification: ``errors`` counts HTTP-level non-200 responses
    (bucketed per status in ``status_counts``); ``transport_errors``
    counts connect failures, resets, and requests a dead connection never
    delivered.  Latency samples come ONLY from 200 responses — a refused
    connection or a fast 429 during a worker restart used to land in the
    latency array and skew p99 downward exactly when the server was at
    its worst (chaos runs made the skew systematic)."""
    import selectors
    import socket

    burst_s = 0.01
    per_conn = offered_qps / conns
    n_per_conn = max(int(per_conn * duration_s), 1)
    per_burst = per_conn * burst_s
    rel = [int(j / per_burst) * burst_s for j in range(n_per_conn)]
    rng = random.Random(7300)
    sel = selectors.DefaultSelector()
    cs: list[_OpenLoopConn] = []
    try:
        for ci in range(conns):
            sock = socket.create_connection((host, port), timeout=timeout_s)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.setblocking(False)
            conn = _OpenLoopConn(sock, offset=ci * burst_s / conns, rel=rel)
            sel.register(sock, selectors.EVENT_READ, conn)
            cs.append(conn)
    except OSError:
        for c in cs:
            c.sock.close()
        return {
            "offered_qps": float(offered_qps), "achieved_qps": 0.0,
            "p50_ms": 0.0, "p99_ms": 0.0,
            "errors": 0, "transport_errors": conns * n_per_conn,
            "status_counts": {}, "requests": 0, "seconds": 0.0,
        }
    lat: list = []
    errors = 0            # HTTP-level non-200 responses
    transport_errors = 0  # connect/reset/undelivered (no response at all)
    status_counts: dict = {}
    total = conns * n_per_conn
    t0 = time.perf_counter()
    deadline = t0 + duration_s + timeout_s
    done = 0
    while done < total:
        now = time.perf_counter()
        if now > deadline:
            break
        next_due = deadline
        for c in cs:
            if c.recvd >= n_per_conn:
                continue  # finished or retired: nothing left to schedule
            # queue every request whose scheduled (burst) arrival has
            # passed — one sendall per burst — then one non-blocking
            # send attempt
            base = t0 + c.offset
            rel_now = now - base
            while c.sent < n_per_conn and c.rel[c.sent] <= rel_now:
                c.scheds.append(base + c.rel[c.sent])
                c.outbox += blobs[rng.randrange(len(blobs))]
                c.sent += 1
            if c.sent < n_per_conn:
                next_due = min(next_due, base + c.rel[c.sent])
            if c.outbox:
                try:
                    n = c.sock.send(c.outbox)
                    del c.outbox[:n]
                except BlockingIOError:
                    pass
                except OSError:
                    transport_errors += n_per_conn - c.recvd
                    done += n_per_conn - c.recvd
                    c.recvd = n_per_conn
                    _retire_conn(sel, c)  # a dead readable fd busy-spins
                    continue
                if c.outbox and not c.writable:
                    sel.modify(c.sock,
                               selectors.EVENT_READ | selectors.EVENT_WRITE,
                               c)
                    c.writable = True
                elif not c.outbox and c.writable:
                    sel.modify(c.sock, selectors.EVENT_READ, c)
                    c.writable = False
        wait = max(min(next_due - time.perf_counter(), 0.05), 0.0)
        for key, _mask in sel.select(wait):
            c = key.data
            if c.recvd >= n_per_conn:
                continue
            try:
                chunk = c.sock.recv(1 << 18)
            except BlockingIOError:
                continue
            except OSError:
                chunk = b""
            if not chunk:
                transport_errors += n_per_conn - c.recvd
                done += n_per_conn - c.recvd
                c.recvd = n_per_conn
                _retire_conn(sel, c)
                continue
            buf = c.buf + chunk
            start = 0
            tr = time.perf_counter()
            while True:
                he = buf.find(b"\r\n\r\n", start)
                if he < 0:
                    break
                # Content-Length is terminated by its own CRLF — it is
                # NOT always the last header (429s carry Retry-After)
                cl = buf.find(b"Content-Length: ", start, he)
                if cl < 0:
                    transport_errors += n_per_conn - c.recvd
                    done += n_per_conn - c.recvd
                    c.recvd = n_per_conn
                    _retire_conn(sel, c)
                    break
                blen = int(buf[cl + 16:buf.find(b"\r\n", cl, he + 2)])
                if len(buf) < he + 4 + blen:
                    break
                status = buf[start + 9:start + 12].decode("latin-1")
                status_counts[status] = status_counts.get(status, 0) + 1
                if status == "200":
                    # ONLY delivered successes are latency samples: a fast
                    # reject (429 during a restart) or refused connection
                    # must not improve p99
                    lat.append(tr - c.scheds[c.recvd])
                else:
                    errors += 1
                start = he + 4 + blen
                c.recvd += 1
                done += 1
            c.buf = buf[start:]
    dt = max(time.perf_counter() - t0, 1e-9)
    undelivered = total - sum(min(c.recvd, n_per_conn) for c in cs)
    transport_errors += max(undelivered, 0)
    for c in cs:
        try:
            c.sock.close()
        except OSError:
            pass
    sel.close()
    lat_ms = np.asarray(lat or [0.0]) * 1000.0
    return {
        "offered_qps": float(offered_qps),
        "achieved_qps": round(len(lat) / dt, 1),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "errors": int(errors),
        "transport_errors": int(transport_errors),
        "status_counts": status_counts,
        "requests": int(len(lat)),
        "seconds": round(dt, 2),
    }


def _step_sustains(step: dict, slo_p99_ms: float) -> bool:
    """A step counts as sustained when the fleet kept up with the offered
    rate (>=92% delivered), met the latency SLO, and dropped nothing —
    neither HTTP errors nor transport-level failures."""
    return (step["errors"] == 0
            and step.get("transport_errors", 0) == 0
            and step["achieved_qps"] >= 0.92 * step["offered_qps"]
            and step["p99_ms"] <= slo_p99_ms)


def bench_serve_open_loop(store_dir: str, ids: list,
                          fleets: tuple = (1, 2),
                          steps: tuple = (2_000, 4_000, 6_000, 8_000,
                                          10_000, 12_000, 14_000, 16_000,
                                          18_000),
                          duration_s: float = 2.5, conns: int = 8,
                          slo_p99_ms: float = 25.0):
    """Open-loop QPS sweep against a real serve fleet (subprocess CLI,
    SO_REUSEPORT port sharing where the kernel has it): stepped offered
    load per fleet size, reporting the max sustainable QPS at the p99 SLO.
    Steps that miss the bar re-measure up to twice — this container is a
    noisy neighbor, and a sweep exists to find capacity, not to
    immortalize one bad scheduling quantum."""
    import re as re_mod
    import signal
    import subprocess
    import urllib.request

    blobs = [
        (f"GET /variant/{i} HTTP/1.1\r\nHost: b\r\n\r\n").encode()
        for i in ids[:20_000]
    ]
    out = {
        "slo_p99_ms": slo_p99_ms,
        "conns": conns,
        "duration_s": duration_s,
        "fleets": [],
    }
    for workers in fleets:
        proc = subprocess.Popen(
            [sys.executable, "-m", "annotatedvdb_tpu", "serve",
             "--storeDir", store_dir, "--port", "0",
             "--workers", str(workers), "--maxQueue", "65536"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        fleet_rec = {"workers": int(workers), "steps": [],
                     "max_sustainable_qps": 0.0}
        try:
            line = proc.stdout.readline()
            m = re_mod.search(r"http://([\d.]+):(\d+)", line)
            if m is None:
                fleet_rec["error"] = f"no address line: {line[:120]!r}"
                out["fleets"].append(fleet_rec)
                continue
            host, port = m.group(1), int(m.group(2))
            for _ in range(300):  # workers import jax; give them time
                try:
                    urllib.request.urlopen(
                        f"http://{host}:{port}/healthz", timeout=2)
                    break
                except OSError:
                    time.sleep(0.2)
            settle()
            # warmup (discarded): first connections, code paths, and the
            # store's first probe batches all pay one-time costs that
            # belong to no step
            _open_loop_step(host, port, blobs, 1_000, 1.0, conns)
            for offered in steps:
                step = _open_loop_step(
                    host, port, blobs, offered, duration_s, conns)
                for _attempt in range(2):  # noisy-neighbor re-measures
                    if _step_sustains(step, slo_p99_ms):
                        break
                    retry = _open_loop_step(
                        host, port, blobs, offered, duration_s, conns)
                    if _step_sustains(retry, slo_p99_ms) \
                            or retry["p99_ms"] < step["p99_ms"]:
                        step = retry
                fleet_rec["steps"].append(step)
                if _step_sustains(step, slo_p99_ms):
                    fleet_rec["max_sustainable_qps"] = max(
                        fleet_rec["max_sustainable_qps"],
                        step["achieved_qps"],
                    )
        finally:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
        out["fleets"].append(fleet_rec)
    out["max_sustainable_qps"] = max(
        (f["max_sustainable_qps"] for f in out["fleets"]), default=0.0
    )
    # throughput independent of the latency SLO: the highest delivered
    # rate with zero errors — on this noisy shared container the p99 gate
    # can blow a step whose delivery was fine, and capacity planning
    # wants both numbers
    out["max_achieved_qps"] = max(
        (s["achieved_qps"]
         for f in out["fleets"] for s in f["steps"]
         if s["errors"] == 0 and s.get("transport_errors", 0) == 0
         and s["achieved_qps"] >= 0.92 * s["offered_qps"]),
        default=0.0,
    )
    return out


#: absolute p99-overhead noise floor (ms): below this, a relative bound
#: on a 10-40ms baseline measures the container, not the code
P99_ABS_FLOOR_MS = 2.0


def _overhead_gate(store_dir: str, ids: list, armed_env: dict,
                   unarmed_env: dict, offered_qps: float | None = None,
                   duration_s: float = 2.5, conns: int = 8,
                   rounds: int = 5, max_overhead: float = 0.03,
                   sample_route: str | None = None):
    """The paired armed/unarmed overhead methodology shared by the
    tracing gate (:func:`bench_observability`) and the health-plane gate
    (:func:`bench_slo_overhead`): two live servers differing ONLY by
    ``armed_env``/``unarmed_env``, alternating adjacent-in-time rounds,
    median-of-paired-ratios verdict with re-measures and the absolute
    p99 noise floor.

    Both servers stay alive for the whole leg and rounds alternate
    armed/unarmed (the idle one costs only its 4 Hz maintenance tick):
    interleaving is the only defensible methodology on this
    noisy-neighbor container, and medians-of-rounds judge the ratio.
    Rounds whose ratio lands over the bound re-measure (two extra pairs)
    before the verdict — a bad scheduling quantum is not an overhead.

    The offered rate ADAPTS to the box: a probe step on the unarmed
    server measures today's capacity and the gate runs at ~45% of it
    (clamped to [1500, 6000]).  At the capacity knee a few µs of extra
    per-request work explodes queueing delay — the ratio there measures
    the knee's cliff, not the code's cost — and this container's
    capacity swings 2-3x between windows, so no fixed rate stays in the
    stable region.  The verdict uses the MEDIAN OF PAIRED per-round
    ratios (armed_i / unarmed_i, adjacent in time): the box's p99 swings
    5-10x on minute timescales, and pairing cancels what a
    ratio-of-medians would eat whole.  The p99 criterion additionally
    carries an ABSOLUTE noise floor (:data:`P99_ABS_FLOOR_MS`): at
    10-40ms baselines a 3% relative bound is 0.3-1.2ms — below this
    container's own round-to-round spread — so the gate passes when the
    ratio holds OR the median paired delta sits under the floor, and
    records both numbers so the judgment is auditable.

    ``sample_route`` (when given) is fetched once from the ARMED server
    after the last round and recorded verbatim — the gate's record then
    carries proof the armed surface actually answered."""
    import re as re_mod
    import signal
    import statistics
    import subprocess
    import urllib.request

    blobs = [
        (f"GET /variant/{i} HTTP/1.1\r\nHost: o\r\n\r\n").encode()
        for i in ids[:20_000]
    ]

    def spawn(env_extra):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   AVDB_JAX_PLATFORM="cpu", **env_extra)
        proc = subprocess.Popen(
            [sys.executable, "-m", "annotatedvdb_tpu", "serve",
             "--storeDir", store_dir, "--port", "0",
             "--workers", "1", "--maxQueue", "65536"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True,
        )
        line = proc.stdout.readline()
        m = re_mod.search(r"http://([\d.]+):(\d+)", line)
        if m is None:
            proc.kill()
            raise RuntimeError(f"no address line: {line[:120]!r}")
        host, port = m.group(1), int(m.group(2))
        for _ in range(300):
            try:
                urllib.request.urlopen(
                    f"http://{host}:{port}/healthz", timeout=2)
                break
            except OSError:
                time.sleep(0.2)
        return proc, host, port

    samples = {"armed": [], "unarmed": []}
    procs = []
    try:
        servers = {}
        for name, env_extra in (("armed", armed_env),
                                ("unarmed", unarmed_env)):
            proc, host, port = spawn(env_extra)
            procs.append(proc)
            servers[name] = (host, port)
            # warmup (discarded): first connections + first probe batches
            _open_loop_step(host, port, blobs, 1_000, 1.0, conns)
        if offered_qps is None:
            host, port = servers["unarmed"]
            probe = _open_loop_step(host, port, blobs, 8_000, 2.0, conns)
            offered_qps = float(min(
                max(round(probe["achieved_qps"] * 0.45, -2), 1_500.0),
                6_000.0,
            ))
            probe_qps = probe["achieved_qps"]
        else:
            probe_qps = None

        def medians():
            out = {}
            for name, steps in samples.items():
                out[name] = {
                    "achieved_qps": round(statistics.median(
                        s["achieved_qps"] for s in steps), 1),
                    "p99_ms": round(statistics.median(
                        s["p99_ms"] for s in steps), 3),
                }
            return out

        def overheads(_med):
            # paired per-round ratios: round i's armed and unarmed steps
            # ran back-to-back, so a noise window hits both sides of the
            # SAME ratio instead of one side of a cross-window median
            qps_ratios = [
                a["achieved_qps"] / max(u["achieved_qps"], 1e-9)
                for a, u in zip(samples["armed"], samples["unarmed"])
            ]
            p99_ratios = [
                a["p99_ms"] / max(u["p99_ms"], 1e-9)
                for a, u in zip(samples["armed"], samples["unarmed"])
            ]
            p99_deltas = [
                a["p99_ms"] - u["p99_ms"]
                for a, u in zip(samples["armed"], samples["unarmed"])
            ]
            return (
                max(0.0, 1.0 - statistics.median(qps_ratios)),
                max(0.0, statistics.median(p99_ratios) - 1.0),
                max(0.0, statistics.median(p99_deltas)),
            )

        round_no = [0]

        def run_round():
            # adjacent in time so a noise swing hits both sides of the
            # ratio — and the order ALTERNATES per round: the first step
            # of a pair inherits the previous pair's socket/cleanup
            # churn, and pinning one side to that phase would bill the
            # churn as tracing overhead
            order = ("armed", "unarmed") if round_no[0] % 2 == 0 \
                else ("unarmed", "armed")
            round_no[0] += 1
            for name in order:
                host, port = servers[name]
                samples[name].append(_open_loop_step(
                    host, port, blobs, offered_qps, duration_s, conns))

        def verdict(over_qps, over_p99, p99_delta_ms):
            p99_ok = (over_p99 <= max_overhead
                      or p99_delta_ms <= P99_ABS_FLOOR_MS)
            return over_qps <= max_overhead and p99_ok

        for _ in range(rounds):
            run_round()
        med = medians()
        over_qps, over_p99, p99_delta_ms = overheads(med)
        remeasures = 0
        while not verdict(over_qps, over_p99, p99_delta_ms) \
                and remeasures < 3:
            remeasures += 1
            run_round()
            med = medians()
            over_qps, over_p99, p99_delta_ms = overheads(med)
        sample_body = None
        if sample_route is not None:
            host, port = servers["armed"]
            with urllib.request.urlopen(
                f"http://{host}:{port}{sample_route}", timeout=5
            ) as r:
                sample_body = json.loads(r.read().decode())
    finally:
        for proc in procs:
            proc.send_signal(signal.SIGTERM)
        for proc in procs:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
    out = {
        "offered_qps": offered_qps,
        "probe_achieved_qps": probe_qps,
        "duration_s": duration_s,
        "conns": conns,
        "rounds": len(samples["armed"]),
        "armed": {**med["armed"],
                  "samples": [
                      {"achieved_qps": s["achieved_qps"],
                       "p99_ms": s["p99_ms"]}
                      for s in samples["armed"]]},
        "unarmed": {**med["unarmed"],
                    "samples": [
                        {"achieved_qps": s["achieved_qps"],
                         "p99_ms": s["p99_ms"]}
                        for s in samples["unarmed"]]},
        "overhead_qps": round(over_qps, 4),
        "overhead_p99": round(over_p99, 4),
        "overhead_p99_ms": round(p99_delta_ms, 3),
        "p99_abs_floor_ms": P99_ABS_FLOOR_MS,
        "max_overhead": max_overhead,
        "within_bound": bool(verdict(over_qps, over_p99, p99_delta_ms)),
    }
    if sample_body is not None:
        out["alerts_sample"] = sample_body
    return out


def bench_observability(store_dir: str, ids: list,
                        offered_qps: float | None = None,
                        duration_s: float = 2.5, conns: int = 8,
                        rounds: int = 5, max_overhead: float = 0.03):
    """Tracing-overhead gate: the open-loop headline re-run with the
    request-observability plane fully ARMED (span recording on every
    request, slow-log threshold set, flight recorder on) vs fully
    UNARMED (``AVDB_TRACE_SAMPLE=0``, ``AVDB_FLIGHT_EVENTS=0``) —
    REQUIRED by the schema to cost <= ``max_overhead`` on sustained QPS
    and p99, so the layer's price is pinned forever.  Methodology in
    :func:`_overhead_gate`."""
    return _overhead_gate(
        store_dir, ids,
        armed_env={"AVDB_TRACE_SAMPLE": "1", "AVDB_TRACE_SLOW_MS": "250"},
        unarmed_env={"AVDB_TRACE_SAMPLE": "0", "AVDB_FLIGHT_EVENTS": "0"},
        offered_qps=offered_qps, duration_s=duration_s, conns=conns,
        rounds=rounds, max_overhead=max_overhead,
    )


def bench_slo_overhead(store_dir: str, ids: list,
                       offered_qps: float | None = None,
                       duration_s: float = 2.5, conns: int = 8,
                       rounds: int = 5, max_overhead: float = 0.03):
    """Health-plane overhead gate: the same paired methodology as
    :func:`bench_observability`, armed = the metrics history ring + SLO
    burn-rate evaluation at their DEFAULT cadence (1 s tick, 300 s
    retention) vs unarmed = the plane disabled (``AVDB_OBS_TICK_S=0``).
    REQUIRED by the schema to cost <= ``max_overhead`` on sustained QPS
    and p99 — the alert plane must be cheap enough to never turn off.
    The armed server's ``/alerts`` body is sampled after the last round
    (``alerts_sample``) so the record proves the plane was live, not
    just enabled."""
    return _overhead_gate(
        store_dir, ids,
        armed_env={"AVDB_OBS_TICK_S": "1.0", "AVDB_OBS_HISTORY_S": "300"},
        unarmed_env={"AVDB_OBS_TICK_S": "0"},
        offered_qps=offered_qps, duration_s=duration_s, conns=conns,
        rounds=rounds, max_overhead=max_overhead, sample_route="/alerts",
    )


def bench_serve_mixed_workload(store_dir: str, ids: list,
                               read_qps: float = 2_000.0,
                               upserts_per_sec: float = 150.0,
                               duration_s: float = 6.0, conns: int = 8,
                               slo_p99_ms: float = 25.0) -> dict:
    """Mixed read/write leg: sustained point-read QPS measured open-loop
    WHILE a writer drives durable upserts through the same worker.

    A real 1-worker ``serve --upserts`` subprocess runs over a COPY of
    the synth store (the write path mutates it; other legs must not
    see that).  The reader is the open-loop step machinery; the writer
    is closed-loop at a fixed target rate on one keep-alive connection,
    each POST a WAL-fsync'd ack whose latency is sampled.  After the
    step, every acknowledged upsert id is read back through bulk
    ``POST /variants`` — ``acked_missing`` MUST be 0 (zero
    acknowledged-write loss, the ack contract under load)."""
    import http.client
    import re as re_mod
    import signal
    import subprocess
    import threading
    import urllib.request

    work = tempfile.mkdtemp(prefix="avdb_mixed_")
    mixed_dir = os.path.join(work, "store")
    shutil.copytree(store_dir, mixed_dir)
    blobs = [
        (f"GET /variant/{i} HTTP/1.1\r\nHost: b\r\n\r\n").encode()
        for i in ids[:20_000]
    ]
    out: dict = {
        "read_qps_target": float(read_qps),
        "upserts_per_sec_target": float(upserts_per_sec),
        "duration_s": duration_s,
        "slo_p99_ms": slo_p99_ms,
        "conns": conns,
    }
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               # triggers stay quiet during the measured window: the
               # flush leg of the story is certified by the smoke/matrix,
               # this leg measures steady-state write+read throughput
               AVDB_MEMTABLE_BYTES="0", AVDB_MEMTABLE_FLUSH_S="0")
    proc = subprocess.Popen(
        [sys.executable, "-m", "annotatedvdb_tpu", "serve",
         "--storeDir", mixed_dir, "--port", "0", "--upserts",
         "--maxQueue", "65536"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True,
    )
    try:
        line = proc.stdout.readline()
        m = re_mod.search(r"http://([\d.]+):(\d+)", line)
        if m is None:
            out["error"] = f"no address line: {line[:120]!r}"
            return out
        host, port = m.group(1), int(m.group(2))
        for _ in range(300):
            try:
                urllib.request.urlopen(
                    f"http://{host}:{port}/healthz", timeout=2)
                break
            except OSError:
                time.sleep(0.2)
        settle()
        _open_loop_step(host, port, blobs, 500, 0.5, conns)  # warmup

        acks: list = []
        acked_ids: list = []
        wstats = {"errors": 0}
        stop = threading.Event()

        def writer():
            conn = http.client.HTTPConnection(host, port, timeout=10)
            interval = 1.0 / upserts_per_sec
            k = 0
            t0 = time.perf_counter()
            while not stop.is_set():
                target = t0 + k * interval
                now = time.perf_counter()
                if target > now:
                    time.sleep(min(target - now, 0.05))
                    continue
                vid = f"9:{50_000_000 + k}:A:G"
                body = json.dumps({"variants": [
                    {"id": vid,
                     "annotations": {"other_annotation": {"k": k}}},
                ]}).encode()
                ts = time.perf_counter()
                try:
                    conn.request("POST", "/variants/upsert", body,
                                 {"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    ok = resp.status == 200
                    resp.read()
                except OSError:
                    ok = False
                    conn.close()
                    conn = http.client.HTTPConnection(
                        host, port, timeout=10)
                if ok:
                    acks.append(time.perf_counter() - ts)
                    acked_ids.append(vid)
                else:
                    wstats["errors"] += 1
                k += 1
            conn.close()

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        t0 = time.perf_counter()
        read_step = _open_loop_step(
            host, port, blobs, read_qps, duration_s, conns)
        stop.set()
        wt.join(timeout=30)
        dt = max(time.perf_counter() - t0, 1e-9)

        # zero acknowledged-write loss: every acked id answers
        missing = 0
        for lo in range(0, len(acked_ids), 500):
            chunk = acked_ids[lo:lo + 500]
            req = urllib.request.Request(
                f"http://{host}:{port}/variants", method="POST",
                data=json.dumps({"ids": chunk}).encode(),
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                found = json.loads(r.read())["found"]
            missing += len(chunk) - found
        ack_ms = np.asarray(acks or [0.0]) * 1000.0
        out.update({
            "read": read_step,
            "read_slo_met": bool(
                read_step["errors"] == 0
                and read_step.get("transport_errors", 0) == 0
                and read_step["p99_ms"] <= slo_p99_ms
            ),
            "upserts": {
                "acked": len(acked_ids),
                "errors": int(wstats["errors"]),
                "achieved_per_sec": round(len(acked_ids) / dt, 1),
                "ack_p50_ms": round(float(np.percentile(ack_ms, 50)), 3),
                "ack_p99_ms": round(float(np.percentile(ack_ms, 99)), 3),
            },
            "acked_verified": len(acked_ids),
            "acked_missing": int(missing),
        })
        return out
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
        shutil.rmtree(work, ignore_errors=True)


def bench_chaos() -> dict:
    """The chaos/soak certification leg (``tools/chaos_soak.py``, full
    schedule): a 2-worker fleet under open-loop load absorbs injected
    drain latency, a device-EIO breaker trip, a snapshot-swap failure
    against a real commit, a worker SIGKILL, and a wedged loop — the
    record lands as the ``serving.chaos`` block (schema-checked).  The
    harness runs as a subprocess (it builds its own fleet and store);
    a failed run records the violations instead of aborting the bench."""
    import subprocess

    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "chaos_soak.py")
    try:
        p = subprocess.run(
            [sys.executable, tool, "--json", "-"],
            capture_output=True, text=True, timeout=600,
        )
    except subprocess.TimeoutExpired:
        return {"error": "chaos soak timed out"}
    try:
        record = json.loads(p.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"chaos soak rc={p.returncode}, no JSON "
                         f"({p.stderr[-300:]!r})"}
    return record


def bench_replication() -> dict:
    """The replica-fleet leg (``tools/chaos_soak.py --repl``): a leader
    takes WAL-durable upserts while a follower tails its ship stream,
    then the leader is SIGKILLed mid-ship and the follower is promoted —
    the record lands as the ``serving.replication`` block (schema-checked
    with ``acked_missing`` REQUIRED 0, the mixed-workload precedent
    extended across a failover).  Runs as a subprocess (it builds its own
    fleets and stores); a failed run records the violations instead of
    aborting the bench."""
    import subprocess

    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "chaos_soak.py")
    try:
        p = subprocess.run(
            [sys.executable, tool, "--repl", "--json", "-"],
            capture_output=True, text=True, timeout=300,
        )
    except subprocess.TimeoutExpired:
        return {"error": "replication leg timed out"}
    try:
        record = json.loads(p.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"replication leg rc={p.returncode}, no JSON "
                         f"({p.stderr[-300:]!r})"}
    rp = dict(record.get("repl") or {})
    rp["acked"] = (record.get("upserts") or {}).get("acked", 0)
    rp["wrong_bytes"] = record.get("wrong_bytes", 0)
    rp["violations"] = record.get("violations", [])
    return rp


def _build_fragmented_store(work: str, n_rows: int, batch: int = 4096):
    """(store_dir, ids): a synth store committed checkpoint-by-checkpoint
    (persist per batch), so the directory holds one segment file pair per
    checkpoint — the fragmented shape ``doctor compact`` exists to fix."""
    from annotatedvdb_tpu.loaders import TpuVcfLoader
    from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore
    from annotatedvdb_tpu.types import DEFAULT_ALLELE_WIDTH

    vcf = os.path.join(work, "frag.vcf")
    write_synth_vcf(vcf, n_rows)
    store_dir = os.path.join(work, "fragstore")
    store = VariantStore(width=DEFAULT_ALLELE_WIDTH)
    ledger = AlgorithmLedger(os.path.join(work, "frag_ledger.jsonl"))
    TpuVcfLoader(
        store, ledger, batch_size=batch, log=lambda *a: None
    ).load_file(vcf, commit=True, persist=lambda: store.save(store_dir))
    store.save(store_dir)
    ids = []
    with open(vcf) as fh:
        for line in fh:
            if line.startswith("#"):
                continue
            chrom, pos, _vid, ref, alt = line.split("\t")[:5]
            ids.append(f"{chrom}:{pos}:{ref}:{alt.split(',')[0]}")
    return store_dir, ids


def bench_compaction(n_rows: int = 40_000) -> dict:
    """The store-maintenance leg: compact a fragmented synth store with a
    REAL ``doctor compact`` subprocess while ONE live serve worker answers
    open-loop point load against it.  Reports files/bytes before/after,
    the merge rate, read amplification (mean segment files per chromosome
    a scan must touch) before/after, the serve leg's latency DURING the
    pass, and a byte-identity verdict: post-compaction responses (after
    the snapshot TTL publishes the new generation) must equal the
    pre-compaction reference bytes."""
    import re
    import signal
    import subprocess
    import urllib.request

    from annotatedvdb_tpu.store.compact import segment_spans

    work = tempfile.mkdtemp(prefix="avdb_compact_bench_")
    proc = None
    try:
        store_dir, ids = _build_fragmented_store(work, n_rows)
        spans = segment_spans(store_dir)
        files_before = sum(spans.values())
        read_amp_before = files_before / max(len(spans), 1)
        bytes_before = sum(
            os.path.getsize(os.path.join(store_dir, f))
            for f in os.listdir(store_dir)
            if f.endswith(".npz") or f.endswith(".ann.jsonl")
        )

        env = dict(os.environ, JAX_PLATFORMS="cpu", AVDB_JAX_PLATFORM="cpu")
        env.pop("AVDB_FAULT", None)
        proc = subprocess.Popen(
            [sys.executable, "-m", "annotatedvdb_tpu", "serve",
             "--storeDir", store_dir, "--port", "0"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True,
        )
        m = re.search(r"http://([\d.]+):(\d+)", proc.stdout.readline())
        if not m:
            raise RuntimeError("serve worker printed no address line")
        host, port = m.group(1), int(m.group(2))

        def get(path):
            with urllib.request.urlopen(
                f"http://{host}:{port}{path}", timeout=10
            ) as r:
                return r.status, r.read().decode()

        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if get("/healthz")[0] == 200:
                    break
            except OSError:
                pass
            time.sleep(0.2)  # back off on transport errors AND non-200s

        sample = ids[:: max(len(ids) // 16, 1)][:16]
        reference = {}
        for vid in sample:
            status, body = get(f"/variant/{vid}")
            if status != 200:
                raise RuntimeError(f"reference GET {vid} -> {status}")
            reference[vid] = body

        blobs = [
            (f"GET /variant/{i} HTTP/1.1\r\nHost: b\r\n\r\n").encode()
            for i in ids
        ]
        live: dict = {}

        def drive():
            live["step"] = _open_loop_step(
                host, port, blobs, 400.0, 8.0, 4, timeout_s=10.0
            )

        driver = threading.Thread(target=drive, daemon=True)
        driver.start()
        time.sleep(0.5)  # the pass runs under established load
        t0 = time.perf_counter()
        p = subprocess.run(
            [sys.executable, "-m", "annotatedvdb_tpu", "doctor", "compact",
             "--storeDir", store_dir, "--json"],
            env=env, capture_output=True, text=True, timeout=300,
        )
        compact_s = max(time.perf_counter() - t0, 1e-9)
        driver.join(timeout=60)
        if p.returncode != 0:
            return {"error": f"doctor compact rc={p.returncode}: "
                             f"{p.stderr[-300:]}"}
        report = json.loads(p.stdout)
        if report["status"] != "compacted":
            return {"error": f"pass did not compact: {report}"}

        # the snapshot TTL (250ms) publishes the compacted generation;
        # verify the served bytes never changed
        time.sleep(0.6)
        mismatches = 0
        for vid, want in reference.items():
            status, body = get(f"/variant/{vid}")
            if status != 200 or body != want:
                mismatches += 1
        spans_after = segment_spans(store_dir)
        step = live.get("step") or {}
        return {
            "rows": int(report["rows"]),
            "files_before": int(files_before),
            "files_after": int(report["files_after"]),
            "bytes_before": int(bytes_before),
            "bytes_after": int(report["bytes_after"]),
            "bytes_reclaimed": int(report["bytes_reclaimed"]),
            "rows_dropped": int(report["rows_dropped"]),
            "seconds": round(compact_s, 3),
            "segments_per_sec": round(files_before / compact_s, 2),
            "read_amp_before": round(read_amp_before, 2),
            "read_amp_after": round(
                sum(spans_after.values()) / max(len(spans_after), 1), 2
            ),
            "byte_identical": mismatches == 0,
            "mismatches": int(mismatches),
            "serve": {
                "offered_qps": float(step.get("offered_qps", 0.0)),
                "achieved_qps": float(step.get("achieved_qps", 0.0)),
                "p50_ms": float(step.get("p50_ms", 0.0)),
                "p99_ms": float(step.get("p99_ms", 0.0)),
                "errors": int(step.get("errors", 0)),
                "transport_errors": int(step.get("transport_errors", 0)),
                "requests": int(step.get("requests", 0)),
            },
        }
    finally:
        if proc is not None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
        shutil.rmtree(work, ignore_errors=True)


def bench_autonomy(duration_s: float = 12.0) -> dict:
    """The autonomy leg (``storage.autonomy``): a maintenance daemon
    holds read amplification bounded while a checkpoint writer keeps
    fragmenting the store — the watermark trips, daemon passes run
    through the cooperative protocol (preemptions by the live writer are
    expected and retried/backed off), and once the writer stops the
    store converges to <= the LOW watermark with nobody invoking
    ``doctor compact``.  Reports the daemon's pass/preemption/pause
    counters (the ``avdb_maintain_*`` series) and the read-amp-over-time
    envelope."""
    import numpy as np

    from annotatedvdb_tpu.obs.metrics import MetricsRegistry
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.store.compact import segment_spans
    from annotatedvdb_tpu.store.maintenance import MaintenanceDaemon
    from annotatedvdb_tpu.store.variant_store import Segment

    # high = low + 1: every over-low state trips the daemon, so the end
    # state after the writer stops is ALWAYS <= low (a gap between the
    # watermarks would leave amp parked in it — correct hysteresis, but
    # not the convergence this leg certifies)
    high, low = 3, 2
    work = tempfile.mkdtemp(prefix="avdb_autonomy_")
    store_dir = os.path.join(work, "store")
    daemon = None
    try:
        def checkpoint(k: int, n: int = 1500) -> None:
            """One loader-shaped checkpoint: fresh load (the live
            manifest may have been compacted under us) -> append one
            disjoint segment -> save."""
            if os.path.exists(os.path.join(store_dir, "manifest.json")):
                store = VariantStore.load(store_dir)
            else:
                store = VariantStore(width=8)
            shard = store.shard(8)
            cols = {
                "pos": np.arange(1000 + 400_000 * k,
                                 1000 + 400_000 * k + n, dtype=np.int32),
                "h": np.arange(n, dtype=np.uint32) + 3,
                "ref_len": np.full(n, 1, np.int32),
                "alt_len": np.full(n, 1, np.int32),
            }
            shard.append_segment(Segment.build(
                cols, np.full((n, 8), 65, np.uint8),
                np.full((n, 8), 71, np.uint8),
            ))
            shard._starts_cache = None
            store.save(store_dir)

        checkpoint(0)
        registry = MetricsRegistry()
        daemon = MaintenanceDaemon(
            store_dir, high=high, low=low, tick_s=0.2, cooldown_s=0.3,
            registry=registry, log=lambda m: None,
        )
        daemon.start()
        t0 = time.monotonic()
        k = 1
        peak = 1
        amps = []
        while time.monotonic() - t0 < duration_s:
            checkpoint(k)
            k += 1
            amp = max(segment_spans(store_dir).values())
            peak = max(peak, amp)
            amps.append(int(amp))
            time.sleep(0.7)
        # the writer stops; the daemon must converge on its own
        amp = peak
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            amp = max(segment_spans(store_dir).values())
            if amp <= low:
                break
            time.sleep(0.2)
        stats = daemon.stats()
        bound = 2 * high  # transient ceiling: trip + in-flight writer +
        # one preemption backoff must never stack past this
        return {
            "high": high, "low": low,
            "segments_written": int(k),
            "passes": int(stats["passes"]),
            "preemptions": int(stats["preemptions"]),
            "paused": int(stats["paused"]),
            "read_amp_peak": int(peak),
            "read_amp_bound": int(bound),
            "read_amp_bounded": bool(peak <= bound),
            "read_amp_end": int(amp),
            "read_amp_samples": amps,
            "converged": bool(amp <= low),
            "seconds": round(time.monotonic() - t0, 2),
        }
    finally:
        if daemon is not None:
            daemon.stop()
        shutil.rmtree(work, ignore_errors=True)


def bench_serve_regions(store_dir: str, ids: list,
                        n_intervals: int = 2048, window_bp: int = 30,
                        limit: int = 10, batch_size: int = 256):
    """The batch-region-join leg: a gene-panel/BED-shaped workload of
    ``n_intervals`` distinct windows over the loaded span, answered two
    ways against ONE live server — sequentially (one keep-alive
    ``GET /region`` per interval, the pre-batch-API access pattern) and
    device-batched (``POST /regions`` in ``batch_size`` chunks, the BITS
    kernel path) — reporting intervals/sec and p99 for both, the speedup,
    and a byte-identity verdict (every sequential response body must
    appear verbatim as its batch envelope).  A count-only run of the same
    panel (``limit=0``, answered from kernel span widths alone) rides
    along."""
    import http.client

    from annotatedvdb_tpu.serve.aio import build_aio_server

    positions = sorted(int(i.split(":")[1]) for i in ids)
    lo_pos, hi_pos = positions[0], positions[-1]
    rng = random.Random(12083407)
    span = max(hi_pos - lo_pos - window_bp, 1)
    panel = []
    for _ in range(n_intervals):
        start = lo_pos + rng.randrange(span)
        panel.append((start, start + window_bp - 1))
    specs = [f"1:{s}-{e}" for s, e in panel]

    server = build_aio_server(store_dir=store_dir, port=0)
    server.start_background()
    try:
        host, port = server.server_address[:2]

        def request(conn, method, path, body=None):
            conn.request(method, path, body=body,
                         headers={"Content-Type": "application/json"}
                         if body else {})
            resp = conn.getresponse()
            return resp.status, resp.read()

        conn = http.client.HTTPConnection(host, port, timeout=60)
        # warmup OUTSIDE the clocks: first connection, route code paths,
        # the per-generation interval-index build, and the BITS kernel
        # trace all pay one-time costs that belong to no leg
        request(conn, "GET", f"/region/{specs[0]}?limit={limit}")
        request(conn, "POST", "/regions", json.dumps(
            {"regions": specs[:batch_size], "limit": limit}
        ))
        settle()

        # sequential baseline: one region per round-trip, keep-alive
        seq_bodies = []
        seq_lat = []
        t0 = time.perf_counter()
        for spec in specs:
            t1 = time.perf_counter()
            status, body = request(
                conn, "GET", f"/region/{spec}?limit={limit}"
            )
            seq_lat.append(time.perf_counter() - t1)
            if status != 200:
                raise RuntimeError(f"sequential region {spec}: {status}")
            seq_bodies.append(body.decode())
        seq_dt = max(time.perf_counter() - t0, 1e-9)

        settle()
        # batched: the same panel through the BITS kernel path
        batch_lat = []
        batch_text = []
        t0 = time.perf_counter()
        for off in range(0, n_intervals, batch_size):
            chunk = specs[off:off + batch_size]
            t1 = time.perf_counter()
            status, body = request(conn, "POST", "/regions", json.dumps(
                {"regions": chunk, "limit": limit}
            ))
            batch_lat.append(time.perf_counter() - t1)
            if status != 200:
                raise RuntimeError(f"regions batch at {off}: {status}")
            batch_text.append(body.decode())
        batch_dt = max(time.perf_counter() - t0, 1e-9)

        # byte identity: every sequential body must sit verbatim inside
        # its chunk's batch response (the per-interval envelope contract)
        mismatches = 0
        for i, body in enumerate(seq_bodies):
            if body not in batch_text[i // batch_size]:
                mismatches += 1

        settle()
        # count-only: the never-materialize mode (limit=0, no filters)
        t0 = time.perf_counter()
        for off in range(0, n_intervals, batch_size):
            status, _b = request(conn, "POST", "/regions", json.dumps(
                {"regions": specs[off:off + batch_size], "limit": 0}
            ))
            if status != 200:
                raise RuntimeError(f"count-only batch at {off}: {status}")
        count_dt = max(time.perf_counter() - t0, 1e-9)
        conn.close()

        seq_ms = np.asarray(seq_lat) * 1000.0
        bat_ms = np.asarray(batch_lat) * 1000.0
        seq_ips = n_intervals / seq_dt
        bat_ips = n_intervals / batch_dt
        return {
            "intervals": n_intervals,
            "window_bp": window_bp,
            "limit": limit,
            "batch_size": batch_size,
            "byte_identical": mismatches == 0,
            "mismatches": mismatches,
            "sequential": {
                "intervals_per_sec": round(seq_ips, 1),
                "p50_ms": round(float(np.percentile(seq_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(seq_ms, 99)), 3),
                "seconds": round(seq_dt, 3),
            },
            "batched": {
                "intervals_per_sec": round(bat_ips, 1),
                "calls": len(batch_lat),
                "p50_ms": round(float(np.percentile(bat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(bat_ms, 99)), 3),
                "seconds": round(batch_dt, 3),
            },
            "speedup": round(bat_ips / seq_ips, 2),
            "count_only": {
                "intervals_per_sec": round(n_intervals / count_dt, 1),
                "seconds": round(count_dt, 3),
                "speedup": round((n_intervals / count_dt) / seq_ips, 2),
            },
        }
    finally:
        server.shutdown()
        server.ctx.batcher.close()


def bench_serve_stats(n_rows: int = 60_000, n_intervals: int = 1024,
                      window_bp: int = 4_000, batch_size: int = 256,
                      point_probes: int = 400) -> dict:
    """The on-device analytics leg: an annotated synth store served live,
    a panel of ``n_intervals`` windows summarized two ways —

    - **sequential host scan** (the pre-analytics access pattern the
      reference's Postgres aggregates imply): one keep-alive
      ``GET /region`` per interval shipping every row to the client,
      which parses the sidecar JSON and aggregates in Python;
    - **batched device stats** (``POST /stats/region`` in ``batch_size``
      chunks): the fused kernel path over the pre-decoded feature
      columns.

    Byte-identity verdict: every batched per-interval summary must equal
    the summary REBUILT from the sequential leg's rows through the same
    shared helpers (``ops.stats.feature_values`` /
    ``summary_from_totals``) — same numbers from two independent data
    paths.  A point-read p99 probe brackets the stats legs
    (``point_read.parity_ok``): resident analytics state must not move
    the point path (a generous noise bound — this box swings 2-3x)."""
    import http.client

    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.ops import stats as stats_ops
    from annotatedvdb_tpu.serve.aio import build_aio_server
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.types import encode_allele_array

    work = tempfile.mkdtemp(prefix="avdb_stats_bench_")
    server = None
    try:
        store_dir = os.path.join(work, "store")
        width = 8
        store = VariantStore(width=width)
        bases = ("A", "C", "G", "T")
        refs = [bases[i % 4] for i in range(n_rows)]
        alts = [bases[(i + 1) % 4] for i in range(n_rows)]
        ref, ref_len = encode_allele_array(refs, width)
        alt, alt_len = encode_allele_array(alts, width)
        h = identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts)
        pos = np.arange(1_000, 1_000 + 61 * n_rows, 61, np.int32)[:n_rows]
        store.shard(8).append(
            {"pos": pos, "h": h, "ref_len": ref_len, "alt_len": alt_len},
            ref, alt,
            annotations={
                "cadd_scores": [
                    {"CADD_phred": float(i % 400) / 10.0}
                    if i % 3 else None for i in range(n_rows)
                ],
                "allele_frequencies": [
                    {"GnomAD": {"af": (i % 1000) / 1000.0}}
                    if i % 2 else None for i in range(n_rows)
                ],
                "adsp_most_severe_consequence": [
                    {"rank": i % 25} if i % 4 else None
                    for i in range(n_rows)
                ],
            },
        )
        store.save(store_dir)
        server = build_aio_server(store_dir=store_dir, port=0)
        server.start_background()
        host, port = server.server_address[:2]

        def request(conn, method, path, body=None):
            conn.request(method, path, body=body,
                         headers={"Content-Type": "application/json"}
                         if body else {})
            resp = conn.getresponse()
            return resp.status, resp.read()

        rng = random.Random(0x57A75)
        lo_pos, hi_pos = int(pos[0]), int(pos[-1])
        span = max(hi_pos - lo_pos - window_bp, 1)
        specs = []
        for _ in range(n_intervals):
            start = lo_pos + rng.randrange(span)
            specs.append(f"8:{start}-{start + window_bp - 1}")
        point_ids = [
            f"8:{int(pos[i])}:{refs[i]}:{alts[i]}"
            for i in rng.sample(range(n_rows), min(point_probes, n_rows))
        ]

        conn = http.client.HTTPConnection(host, port, timeout=60)

        def point_p99() -> float:
            lat = []
            for vid in point_ids:
                t1 = time.perf_counter()
                status, _b = request(conn, "GET", f"/variant/{vid}")
                lat.append(time.perf_counter() - t1)
                if status != 200:
                    raise RuntimeError(f"point probe {vid}: {status}")
            return float(np.percentile(np.asarray(lat) * 1000.0, 99))

        # warmup OUTSIDE the clocks: route code, the interval-index and
        # feature-column builds, and the kernel traces are one-time costs
        request(conn, "GET", f"/region/{specs[0]}?limit=100000")
        request(conn, "POST", "/stats/region", json.dumps(
            {"regions": specs[:batch_size]}
        ))
        settle()
        p99_before = point_p99()

        settle()
        # sequential host scan: rows to the client, JSON parse + Python
        # aggregation per interval
        ref_entries = []
        seq_lat = []
        t0 = time.perf_counter()
        for spec in specs:
            t1 = time.perf_counter()
            status, body = request(
                conn, "GET", f"/region/{spec}?limit=100000"
            )
            if status != 200:
                raise RuntimeError(f"sequential region {spec}: {status}")
            doc = json.loads(body)
            if doc["count"] != doc["returned"]:
                raise RuntimeError(f"{spec}: rows truncated")
            af_fp, cadd_fp, rank_i = [], [], []
            for rec in doc["variants"]:
                ann = rec["annotations"]
                _cf, _rf, a, c, r = stats_ops.feature_values(
                    ann.get("cadd_scores"),
                    ann.get("allele_frequencies"),
                    ann.get("adsp_most_severe_consequence"),
                )
                af_fp.append(a)
                cadd_fp.append(c)
                rank_i.append(r)
            _p, af_sum, af_hist = stats_ops.column_totals(
                np.asarray(af_fp or [-1], np.int64), stats_ops.AF_EDGES_FP
            )
            _p, cadd_sum, cadd_hist = stats_ops.column_totals(
                np.asarray(cadd_fp or [-1], np.int64),
                stats_ops.CADD_EDGES_FP,
            )
            ranks = stats_ops.rank_totals(
                np.asarray(rank_i or [-1], np.int64)
            )
            ref_entries.append({
                "region": spec,
                **stats_ops.summary_from_totals(
                    doc["count"], af_sum, af_hist, cadd_sum, cadd_hist,
                    ranks,
                ),
            })
            seq_lat.append(time.perf_counter() - t1)
        seq_dt = max(time.perf_counter() - t0, 1e-9)

        settle()
        # batched device stats: the fused kernel path
        got_entries = []
        batch_lat = []
        t0 = time.perf_counter()
        for off in range(0, n_intervals, batch_size):
            chunk = specs[off:off + batch_size]
            t1 = time.perf_counter()
            status, body = request(conn, "POST", "/stats/region",
                                   json.dumps({"regions": chunk}))
            batch_lat.append(time.perf_counter() - t1)
            if status != 200:
                raise RuntimeError(f"stats batch at {off}: {status}")
            got_entries.extend(json.loads(body)["results"])
        batch_dt = max(time.perf_counter() - t0, 1e-9)

        mismatches = sum(
            1 for got, want in zip(got_entries, ref_entries)
            if got != want
        )

        settle()
        p99_after = point_p99()
        conn.close()

        seq_ms = np.asarray(seq_lat) * 1000.0
        bat_ms = np.asarray(batch_lat) * 1000.0
        seq_ips = n_intervals / seq_dt
        bat_ips = n_intervals / batch_dt
        ratio = p99_after / max(p99_before, 1e-9)
        return {
            "intervals": n_intervals,
            "window_bp": window_bp,
            "batch_size": batch_size,
            "store_rows": n_rows,
            "byte_identical": mismatches == 0,
            "mismatches": mismatches,
            "sequential": {
                "intervals_per_sec": round(seq_ips, 1),
                "p50_ms": round(float(np.percentile(seq_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(seq_ms, 99)), 3),
                "seconds": round(seq_dt, 3),
            },
            "batched": {
                "intervals_per_sec": round(bat_ips, 1),
                "calls": len(batch_lat),
                "p50_ms": round(float(np.percentile(bat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(bat_ms, 99)), 3),
                "seconds": round(batch_dt, 3),
            },
            "speedup": round(bat_ips / seq_ips, 2),
            "point_read": {
                "p99_ms_before": round(p99_before, 3),
                "p99_ms_after": round(p99_after, 3),
                "ratio": round(ratio, 3),
                # generous noise bound: the box swings 2-3x on minute
                # timescales, and sub-ms baselines amplify ratios
                "parity_ok": bool(p99_after <= max(p99_before * 2.5,
                                                   p99_before + 5.0)),
            },
        }
    finally:
        if server is not None:
            server.shutdown()
            server.ctx.batcher.close()
        shutil.rmtree(work, ignore_errors=True)


def bench_multichip_virtual(n_devices: int = 8):
    """Mesh insert-step timing on a VIRTUAL n-device CPU mesh — a labeled
    scaling datapoint (reshard + annotate + dedup + membership as one mesh
    program), NOT a hardware throughput claim: all virtual devices share
    this host's cores, so the number is an upper bound on per-step cost and
    a lower bound on what real chips with ICI would do.  Requires
    ``--xla_force_host_platform_device_count`` set before backend init
    (main() does this)."""
    import jax

    try:
        cpu_devices = jax.devices("cpu")
    except RuntimeError:
        return {"skipped": "no CPU backend available"}
    if len(cpu_devices) < n_devices:
        return {
            "skipped": f"only {len(cpu_devices)} CPU devices (flag not set "
                       "before backend init)"
        }
    from jax.sharding import Mesh

    from annotatedvdb_tpu.io.synth import synthetic_batch
    from annotatedvdb_tpu.parallel.device_store import build_device_shard_store
    from annotatedvdb_tpu.parallel.distributed import distributed_insert_step
    from annotatedvdb_tpu.parallel.mesh import SHARD_AXIS
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.ops.hashing import allele_hash_jit

    mesh = Mesh(np.array(cpu_devices[:n_devices]), (SHARD_AXIS,))
    batch_rows = 1 << 19   # 512k rows/step: a realistic per-step load
    # >=10M resident rows: the snapshot scale a gnomAD-chr1-sized load
    # actually probes against (VERDICT r4 item 8 — the <10-min projection
    # should rest on a measured large-store step, not extrapolation)
    store_rows = 10 * (1 << 20)
    batch = synthetic_batch(batch_rows, width=16, seed=23)
    resident = synthetic_batch(store_rows, width=16, seed=29)
    store = VariantStore(width=16)
    h = np.asarray(allele_hash_jit(
        resident.ref, resident.alt, resident.ref_len, resident.alt_len
    ))
    for code in np.unique(resident.chrom):
        rows = np.where(resident.chrom == code)[0]
        store.shard(int(code)).append(
            {"pos": resident.pos[rows], "h": h[rows],
             "ref_len": resident.ref_len[rows],
             "alt_len": resident.alt_len[rows]},
            resident.ref[rows], resident.alt[rows],
        )
    dev_store = build_device_shard_store(store, n_devices)

    def step():
        return distributed_insert_step(mesh, batch, dev_store=dev_store)

    out = step()  # compile
    jax.block_until_ready(out[3]["class_counts"])
    t0 = time.perf_counter()
    out = step()
    jax.block_until_ready(out[3]["class_counts"])
    dt = time.perf_counter() - t0
    return {
        "label": "virtual-cpu-mesh (shared host cores; NOT chip throughput)",
        "devices": n_devices,
        "batch_rows": batch_rows,
        "resident_store_rows": store_rows,
        "step_seconds": round(dt, 3),
        "rows_per_sec_virtual": round(batch_rows / dt, 1),
        "counters": {
            k: np.asarray(v).tolist()
            for k, v in out[3].items()
        },
    }


def bench_multichip_curve(device_counts=(1, 2, 4, 8)):
    """The MULTICHIP scaling-curve block: the mesh-sharded annotate
    pipeline and the sharded serve bulk lookup measured at 1→2→4→8
    devices on a forced host mesh, byte-verified against the
    single-device answers AT EVERY COUNT.

    Honesty first: on a virtual-CPU mesh every "device" shares this
    host's physical cores, so the wall-clock speedup ceiling is the core
    count, not the device count — the block records ``cores`` and labels
    itself accordingly.  What the curve DOES prove: the sharded programs
    are correct at every width (byte_identical), the per-device work
    genuinely partitions (speedup tracks min(devices, cores)), and on
    real chips — where devices stop sharing silicon — the same programs
    scale with the mesh instead of the host."""
    import jax

    from annotatedvdb_tpu.io.synth import synthetic_batch
    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.models.pipeline import annotate_pipeline_jit
    from annotatedvdb_tpu.ops.dedup import CHROM_MIX
    from annotatedvdb_tpu.parallel.device_store import (
        build_device_shard_store,
    )
    from annotatedvdb_tpu.parallel.distributed import (
        distributed_serve_lookup_step,
    )
    from annotatedvdb_tpu.parallel.mesh import batch_sharding, make_mesh
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.ops.hashing import allele_hash_np

    cpu_devices = jax.devices("cpu")
    counts = [d for d in device_counts if d <= len(cpu_devices)]
    if not counts or counts[-1] < max(device_counts):
        return {
            "skipped": f"only {len(cpu_devices)} CPU devices (flag not "
                       "set before backend init)"
        }

    # ---- annotate pipeline leg (ingest -> normalize -> class -> bin) ----
    rows = 1 << 19
    width = 16
    batch = synthetic_batch(rows, width=width, seed=23)
    args_np = (batch.chrom, batch.pos, batch.ref, batch.alt,
               batch.ref_len, batch.alt_len)
    annotate_leg = {"rows": rows, "width": width, "per_device": []}
    reference = None
    iters, rounds = 5, 3
    ann_ctx = []
    for nd in counts:
        mesh = make_mesh(nd, devices=cpu_devices)
        sharding = batch_sharding(mesh)
        dargs = tuple(jax.device_put(np.asarray(a), sharding)
                      for a in args_np)
        out = annotate_pipeline_jit(*dargs)  # compile + verify pass
        jax.block_until_ready(out)
        got = {f: np.asarray(getattr(out, f))
               for f in out._fields}
        if reference is None:
            reference = got
        identical = all(
            np.array_equal(reference[f], got[f]) for f in reference
        )
        ann_ctx.append({"devices": nd, "args": dargs,
                        "byte_identical": bool(identical),
                        "dt": float("inf")})
    # interleaved best-of rounds: the box's background load swings 2-3x
    # on minute timescales, so each device count gets measured in every
    # time window and keeps its best — one noisy window can't tilt the
    # curve toward whichever count it happened to land on
    for _round in range(rounds):
        for ctx in ann_ctx:
            t0 = time.perf_counter()
            for _ in range(iters):
                out = annotate_pipeline_jit(*ctx["args"])
            jax.block_until_ready(out)
            ctx["dt"] = min(
                ctx["dt"],
                max((time.perf_counter() - t0) / iters, 1e-9),
            )
    for ctx in ann_ctx:
        annotate_leg["per_device"].append({
            "devices": ctx["devices"],
            "rows_per_sec": round(rows / ctx["dt"], 1),
            "seconds": round(ctx["dt"], 4),
            "byte_identical": ctx["byte_identical"],
        })

    # ---- serve bulk-lookup leg (one sharded call + cross-device gather) --
    store_rows = 1 << 21
    n_queries = 1 << 16
    resident = synthetic_batch(store_rows, width=width, seed=29)
    store = VariantStore(width=width)
    h_all = allele_hash_np(resident.ref, resident.alt,
                           resident.ref_len, resident.alt_len)
    for code in np.unique(resident.chrom):
        sel = np.where(resident.chrom == code)[0]
        order = np.argsort(
            (resident.pos[sel].astype(np.uint64) << np.uint64(32))
            | h_all[sel], kind="stable",
        )
        sel = sel[order]
        store.shard(int(code)).append(
            {"pos": resident.pos[sel], "h": h_all[sel],
             "ref_len": resident.ref_len[sel],
             "alt_len": resident.alt_len[sel]},
            resident.ref[sel], resident.alt[sel],
        )
    # queries: half present (sampled store rows), half absent
    rng = np.random.default_rng(31)
    take = rng.choice(store_rows, n_queries, replace=False)
    q_chrom = resident.chrom[take].copy()
    q_pos = resident.pos[take].copy()
    q_ref = resident.ref[take].copy()
    q_alt = resident.alt[take].copy()
    q_rl = resident.ref_len[take].copy()
    q_al = resident.alt_len[take].copy()
    q_pos[::2] = q_pos[::2] + 1  # misses (position off by one)
    q_h = identity_hashes(width, q_ref, q_alt, q_rl, q_al)
    q_hm = q_h ^ (q_chrom.astype(np.uint32) * np.uint32(CHROM_MIX))
    # the single-device production reference: the store's own host path
    ref_found = np.zeros(n_queries, bool)
    ref_gid = np.full(n_queries, -1, np.int64)
    for code in np.unique(q_chrom):
        sel = np.where(q_chrom == code)[0]
        shard = store.shards.get(int(code))
        if shard is None:
            continue
        f, g = shard.lookup(q_pos[sel], q_h[sel], q_ref[sel], q_alt[sel],
                            q_rl[sel], q_al[sel], host_only=True)
        ref_found[sel], ref_gid[sel] = f, g
    bulk_leg = {"store_rows": store_rows, "queries": n_queries,
                "per_device": []}
    bulk_ctx = []
    for nd in counts:
        mesh = make_mesh(nd, devices=cpu_devices)
        sharding = batch_sharding(mesh)
        host_store = build_device_shard_store(store, nd)
        dev_store = type(host_store)(*(
            jax.device_put(np.asarray(getattr(host_store, f)), sharding)
            if f != "n_rows" else host_store.n_rows
            for f in host_store._fields
        ))

        def step(mesh=mesh, dev_store=dev_store):
            return distributed_serve_lookup_step(
                mesh, q_chrom, q_pos, q_hm, q_ref, q_alt, q_rl, q_al,
                dev_store,
            )

        rid_out, found, store_row = step()  # compile + verify pass
        rid_out = np.asarray(rid_out)
        found = np.asarray(found)
        store_row = np.asarray(store_row)
        got_found = np.zeros(n_queries, bool)
        got_gid = np.full(n_queries, -1, np.int64)
        take_slots = rid_out >= 0
        got_found[rid_out[take_slots]] = found[take_slots]
        got_gid[rid_out[take_slots]] = store_row[take_slots]
        identical = bool(
            np.array_equal(got_found, ref_found)
            and np.array_equal(got_gid, ref_gid)
        )
        bulk_ctx.append({"devices": nd, "step": step,
                         "byte_identical": identical,
                         "dt": float("inf")})
    for _round in range(rounds):  # interleaved best-of (see annotate leg)
        for ctx in bulk_ctx:
            t0 = time.perf_counter()
            for _ in range(iters):
                out = ctx["step"]()
            jax.block_until_ready(out[0])
            ctx["dt"] = min(
                ctx["dt"],
                max((time.perf_counter() - t0) / iters, 1e-9),
            )
    for ctx in bulk_ctx:
        bulk_leg["per_device"].append({
            "devices": ctx["devices"],
            "lookups_per_sec": round(n_queries / ctx["dt"], 1),
            "seconds": round(ctx["dt"], 4),
            "byte_identical": ctx["byte_identical"],
        })

    def _finish(leg, key):
        base = leg["per_device"][0][key]
        for entry in leg["per_device"]:
            entry["speedup"] = round(entry[key] / base, 2)
            entry["efficiency"] = round(
                entry[key] / base / entry["devices"], 3
            )
        leg["speedup_at_max"] = leg["per_device"][-1]["speedup"]

    _finish(annotate_leg, "rows_per_sec")
    _finish(bulk_leg, "lookups_per_sec")
    cores = os.cpu_count() or 1
    return {
        "devices": counts,
        "cores": cores,
        "label": ("virtual-cpu host mesh: all devices share this host's "
                  f"{cores} core(s), so the wall-clock speedup ceiling "
                  "is min(devices, cores) — correctness and partitioning "
                  "are what the curve certifies here; chip-count scaling "
                  "needs real chips"),
        "annotate": annotate_leg,
        "bulk_lookup": bulk_leg,
    }


def multichip_only():
    """One-command mesh scaling capture (``python bench.py --multichip``):
    force the 8-virtual-device CPU host platform, run the MULTICHIP
    scaling curve (annotate pipeline + sharded bulk lookup at 1→2→4→8
    devices, byte-verified at every count), and print one schema-valid
    JSON line."""
    from annotatedvdb_tpu.utils import runtime

    runtime.force_cpu_mesh(8)
    import jax

    out = {
        "mode": "multichip",
        "metric": "multichip_annotate_speedup_8dev",
        "unit": "x_vs_1dev",
        "backend": jax.default_backend(),
        "platform_pin": "cpu",
    }
    curve = bench_multichip_curve()
    out["multichip"] = curve
    speedup = (
        curve.get("annotate", {}).get("speedup_at_max", 0.0)
        if "skipped" not in curve else 0.0
    )
    out["value"] = speedup
    # the honest baseline for a virtual mesh is the CORE-count
    # ceiling, not the device count (see the block's label)
    ceiling = min(8, os.cpu_count() or 1)
    out["vs_baseline"] = round(speedup / ceiling, 3) if ceiling else 0.0
    print(json.dumps(out))


def _argv_opt(name: str) -> str | None:
    """Minimal ``--flag VALUE`` / ``--flag=VALUE`` lookup (the bench keeps
    argv handling dependency-free, like --tpu-only)."""
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a == name and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith(name + "="):
            return a.split("=", 1)[1]
    return None


def tpu_only():
    """One-command TPU capture (``python bench.py --tpu-only``): the
    kernel + end-to-end legs on the accelerator, one JSON line.  Without
    an accelerator the run fails (non-zero exit, no record) — a CPU
    number is never written under this mode's name."""
    from annotatedvdb_tpu.utils import runtime

    platform = runtime.pin_platform("auto")
    import jax

    if jax.default_backend() == "cpu":
        raise SystemExit(
            "bench.py --tpu-only: JAX found no accelerator "
            f"(backend {jax.default_backend()!r}); nothing measured"
        )
    out = {
        "mode": "tpu-only",
        "platform_pin": platform,
        "backend": jax.default_backend(),
        "device": runtime.device_summary(),
    }
    kernel_vps, kernel_kind = bench_kernel()
    out.update(
        kernel_variants_per_sec=round(kernel_vps, 1),
        kernel_vs_target=round(kernel_vps / KERNEL_TARGET, 3),
        kernel=kernel_kind,
    )
    e2e = bench_end_to_end(
        metrics_out=_argv_opt("--metrics-out"),
        trace_out=_argv_opt("--trace-out"),
    )
    out.update(
        value=round(e2e["variants_per_sec"], 1),
        vs_baseline=round(e2e["variants_per_sec"] / END_TO_END_TARGET, 3),
        end_to_end=e2e,
    )
    print(json.dumps(out))


def _in_child(fn, *args):
    """``fn(*args)`` in a spawned child process; returns its result.

    A chip belongs to one process at a time: the legs that touch JAX
    in-process must not share a parent with the legs that spawn ``serve``
    children, so the former run in a child of their own and the parent
    never initializes a backend."""
    import concurrent.futures
    import multiprocessing

    with concurrent.futures.ProcessPoolExecutor(
        1, mp_context=multiprocessing.get_context("spawn")
    ) as pool:
        return pool.submit(fn, *args).result()


def _serve_inprocess_legs(work: str):
    """The serving legs that run JAX in-process (store build, regions,
    stats) — one child owns the device for all three.
    Returns ``(store_dir, ids, serving, device)``."""
    from annotatedvdb_tpu.utils import runtime

    runtime.pin_platform("auto")
    store_dir, ids = _build_serve_store(work, 50_000)
    serving = {"regions": bench_serve_regions(store_dir, ids)}
    settle()
    serving["stats"] = bench_serve_stats()
    return store_dir, ids, serving, runtime.device_summary()


def serve_only():
    """One-command serving bench (``python bench.py --serve``): the
    open-loop QPS sweep against a real 1- and 2-worker fleet (subprocess
    CLI, asyncio front end), printed as one JSON line with the
    ``serving`` block.  The headline ``value`` is the open-loop max
    sustainable QPS at the p99 SLO — the number a capacity plan would
    use (0 when nothing met the SLO).

    This process never initializes a JAX backend: the in-process legs run
    in a child of their own (:func:`_in_child`), then the fleet legs
    spawn their ``serve`` children.  A failing leg fails the run."""
    work = tempfile.mkdtemp(prefix="avdb_serve_ol_")
    try:
        store_dir, ids, serving, device = _in_child(
            _serve_inprocess_legs, work
        )
        settle()
        serving["open_loop"] = bench_serve_open_loop(store_dir, ids)
        settle()
        serving["observability"] = bench_observability(store_dir, ids)
        settle()
        serving["slo"] = bench_slo_overhead(store_dir, ids)
        settle()
        serving["mixed_workload"] = bench_serve_mixed_workload(
            store_dir, ids)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    settle()
    serving["chaos"] = bench_chaos()
    settle()
    serving["replication"] = bench_replication()
    settle()
    compaction = bench_compaction()
    settle()
    storage = {"autonomy": bench_autonomy()}
    headline = serving["open_loop"]["max_sustainable_qps"]
    print(json.dumps({
        "metric": "serve_open_loop_sustainable_qps",
        "value": headline,
        "unit": "queries/sec",
        "vs_baseline": round(headline / SERVE_OPEN_LOOP_QPS_TARGET, 3),
        "backend": device["platform"],
        "device": device,
        "platform_pin": "auto",
        "serving": serving,
        "compaction": compaction,
        "storage": storage,
    }))


def _corpus_files_equal(a_dir: str, b_dir: str) -> bool:
    """Byte-compare two corpus directories (manifest + every part)."""
    names = sorted(
        f for f in os.listdir(a_dir)
        if f.endswith(".npz") or f == "corpus.manifest.json"
    )
    if names != sorted(
        f for f in os.listdir(b_dir)
        if f.endswith(".npz") or f == "corpus.manifest.json"
    ):
        return False
    for name in names:
        with open(os.path.join(a_dir, name), "rb") as fa, \
                open(os.path.join(b_dir, name), "rb") as fb:
            if fa.read() != fb.read():
                return False
    return bool(names)


def export_only():
    """One-command corpus-export bench (``python bench.py --export``):
    the tokens/sec headline + device-idle occupancy of a one-shot
    chromosome export, then the determinism battery — same-seed re-run,
    ``--hostOnly`` twin, and a SIGKILL-mid-part + ``--resume`` run
    through the real CLI — each byte-compared against the reference
    corpus.  Printed as one schema-valid JSON line with
    ``mode: "export"``.  The kill/resume children are pinned to the CPU
    explicitly (this process holds the device; their output is compared
    byte for byte, and the host twin is byte-identical by contract).  A
    failing leg fails the run."""
    import subprocess

    from annotatedvdb_tpu.utils import runtime

    platform = runtime.pin_platform("auto")
    import jax

    from annotatedvdb_tpu.config import StoreConfig
    from annotatedvdb_tpu.export.core import run_export

    rows = int(os.environ.get("AVDB_BENCH_EXPORT_ROWS", 120_000))
    seed, batch_rows, part_bytes = 11, 4096, "2m"
    work = tempfile.mkdtemp(prefix="avdb_export_")
    export: dict = {"rows": rows, "seed": seed, "batch_rows": batch_rows}
    try:
        store_dir, _ids = _build_serve_store(work, rows)
        store, ledger = StoreConfig(store_dir).open(create=False,
                                                    readonly=True)
        ref = os.path.join(work, "ref")
        settle()
        summary = run_export(store, ledger, store_dir, ref,
                             chromosome="1", seed=seed,
                             batch_rows=batch_rows, part_bytes=part_bytes)
        export["one_shot"] = {
            "tokens_per_sec": summary["tokens_per_sec"],
            "device_idle_frac": summary["device_idle_frac"],
            "rows": summary["rows"], "tokens": summary["tokens"],
            "parts": summary["parts_written"],
            "seconds": summary["seconds"],
            "complete": summary["complete"],
        }
        settle()
        rerun = os.path.join(work, "rerun")
        run_export(store, ledger, store_dir, rerun, chromosome="1",
                   seed=seed, batch_rows=batch_rows,
                   part_bytes=part_bytes)
        export["replay_identical"] = _corpus_files_equal(ref, rerun)
        settle()
        host = os.path.join(work, "host")
        run_export(store, ledger, store_dir, host, chromosome="1",
                   seed=seed, batch_rows=batch_rows,
                   part_bytes=part_bytes, host_only=True)
        export["host_twin_identical"] = _corpus_files_equal(ref, host)
        settle()
        # the durability leg rides the REAL CLI: SIGKILL on the 2nd
        # part commit (env-armed fault), then --resume completes and
        # the corpus must equal the uninterrupted reference
        resumed = os.path.join(work, "resumed")
        argv = [
            sys.executable, "-m", "annotatedvdb_tpu", "export",
            "--storeDir", store_dir, "--out", resumed, "--commit",
            "--chromosome", "1", "--seed", str(seed),
            "--batchRows", str(batch_rows), "--partBytes", part_bytes,
        ]
        env = dict(os.environ, AVDB_FAULT="export.commit:2:kill",
                   AVDB_JAX_PLATFORM="cpu")
        kill = subprocess.run(
            argv, env=env, capture_output=True, timeout=600
        )
        env.pop("AVDB_FAULT")
        resume = subprocess.run(
            argv + ["--resume"], env=env, capture_output=True,
            timeout=600,
        )
        export["resume"] = {
            "killed_rc": kill.returncode,
            "resume_rc": resume.returncode,
            "identical": _corpus_files_equal(ref, resumed),
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)
    headline = export["one_shot"]["tokens_per_sec"]
    print(json.dumps({
        "metric": "export_tokens_per_sec",
        "value": headline,
        "unit": "tokens/sec",
        "vs_baseline": round(headline / EXPORT_TOKENS_TARGET, 3),
        "backend": jax.default_backend(),
        "platform_pin": platform,
        "mode": "export",
        "export": export,
    }))


def main():
    if "--tpu-only" in sys.argv[1:]:
        tpu_only()
        return
    if "--serve" in sys.argv[1:]:
        serve_only()
        return
    if "--export" in sys.argv[1:]:
        export_only()
        return
    if "--multichip" in sys.argv[1:]:
        multichip_only()
        return
    from annotatedvdb_tpu.utils import runtime

    # virtual CPU devices for the multi-chip projection leg (harmless when
    # the accelerator backend is selected: the CPU platform coexists);
    # must precede backend init, like the platform pin itself
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    platform = runtime.pin_platform("auto")

    import jax

    # every leg below runs in THIS process, which holds the device; the
    # one child a leg starts (bench_compaction's serve worker) is pinned
    # to the CPU explicitly.  A failing leg fails the run: no re-exec on
    # another backend, no error recorded under an exit code of 0.
    kernel_vps, kernel_kind = bench_kernel()
    e2e = bench_end_to_end(
        metrics_out=_argv_opt("--metrics-out"),
        trace_out=_argv_opt("--trace-out"),
    )
    cadd = bench_cadd_join()
    qc = bench_qc_update()
    multichip = bench_multichip_virtual()
    compaction = bench_compaction()
    storage = {"autonomy": bench_autonomy()}

    print(
        json.dumps(
            {
                "metric": "end_to_end_vcf_to_store_variants_per_sec",
                "value": round(e2e["variants_per_sec"], 1),
                "unit": "variants/sec",
                "vs_baseline": round(
                    e2e["variants_per_sec"] / END_TO_END_TARGET, 3
                ),
                "kernel_variants_per_sec": round(kernel_vps, 1),
                "kernel_vs_target": round(kernel_vps / KERNEL_TARGET, 3),
                "kernel": kernel_kind,
                "backend": jax.default_backend(),
                "device": runtime.device_summary(),
                "platform_pin": platform,
                "end_to_end": e2e,
                "cadd_join": cadd,
                "qc_update": qc,
                "multichip_virtual": multichip,
                "compaction": compaction,
                "storage": storage,
            }
        )
    )


if __name__ == "__main__":
    main()
