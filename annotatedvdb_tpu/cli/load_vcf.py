"""CLI: load a VCF into the TPU-native variant store.

The ``Load/bin/load_vcf_file.py`` equivalent (flags mirror
``load_vcf_file.py:247-286``): default is a dry run (full pipeline, no
mutation) unless ``--commit`` is passed; ``--test`` stops after one batch;
``--failAt`` is fault injection; the algorithm-invocation id is printed on
exit so a wrapper can undo the load (``load_vcf_file.py:220``).

Shared flags come from the typed config registry
(``annotatedvdb_tpu.config``); also reachable as
``python -m annotatedvdb_tpu load-vcf``.

Usage:  python -m annotatedvdb_tpu.cli.load_vcf --fileName x.vcf[.gz] \
            --storeDir ./vdb [--commit] [--datasource dbSNP] ...
"""

from __future__ import annotations

import argparse
import os
import sys

from annotatedvdb_tpu.config import (
    StoreConfig,
    add_load_args,
    add_runtime_args,
    load_from_args,
    runtime_from_args,
)
from annotatedvdb_tpu.io.vcf import read_chromosome_map
from annotatedvdb_tpu.loaders import TpuVcfLoader
from annotatedvdb_tpu.utils.profiling import device_trace, startup_phase


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="load a VCF into the TPU-native annotated variant store"
    )
    parser.add_argument("--fileName", required=True, help="VCF file (.gz ok)")
    parser.add_argument("--storeDir", required=True, help="variant store directory")
    add_load_args(parser)
    add_runtime_args(parser)
    parser.add_argument("--chromosomeMap", default=None,
                        help="TSV mapping seq accessions to chromosomes")
    parser.add_argument("--refGenome", default=None,
                        help="packed genome .npz (cli.index_genome); enables "
                             "ref-allele validation + canonical GA4GH digests "
                             "(the reference's --seqrepoProxyPath)")
    parser.add_argument("--skipExisting", action=argparse.BooleanOptionalAction,
                        default=True,
                        help="check the store for existing variants "
                             "(--no-skipExisting disables, the reference's "
                             "unchecked fast path)")
    parser.add_argument("--profile", default=None, metavar="DIR",
                        help="capture a jax.profiler trace of the load into "
                             "DIR: the load's stages and waits (avdb.*) on "
                             "host lines and the device's operations, one "
                             ".xplane.pb (view in TensorBoard/Perfetto)")
    from annotatedvdb_tpu.obs import add_obs_args

    add_obs_args(parser)
    args = parser.parse_args(argv)

    runtime = runtime_from_args(args)
    cfg = load_from_args(args)
    try:
        runtime.validate()  # flag VALUES only; env/runtime errors propagate
    except ValueError as err:
        parser.error(str(err))
    mesh = runtime.apply()  # platform pin + multihost + annotate mesh
    if mesh is not None:
        print(f"annotating across {mesh.devices.size} devices", file=sys.stderr)

    store, ledger = StoreConfig(args.storeDir).open()
    chrom_map = read_chromosome_map(args.chromosomeMap) if args.chromosomeMap else None
    genome = None
    if args.refGenome:
        from annotatedvdb_tpu.genome import ReferenceGenome

        genome = ReferenceGenome.load(args.refGenome)

    from annotatedvdb_tpu.utils.logging import load_logger

    log, _logger, log_path = load_logger(
        args.fileName, "load-vcf", args.logFilePath
    )
    log(f"load_vcf {args.fileName} -> {args.storeDir} "
        f"(commit={cfg.commit}, log={log_path})")

    from annotatedvdb_tpu.config import quarantine_from_args

    loader = TpuVcfLoader(
        store,
        ledger,
        datasource=cfg.datasource,
        genome_build=cfg.genome_build,
        genome=genome,
        batch_size=cfg.commit_after,
        skip_existing=args.skipExisting,
        chromosome_map=chrom_map,
        mesh=mesh,
        log=log,
        log_after=cfg.effective_log_after,
        quarantine=quarantine_from_args(args, args.storeDir, "load-vcf",
                                        log=log),
        max_errors=args.maxErrors,
    )
    # telemetry session: --metricsOut / --traceOut exports + the per-load
    # run-ledger record (appended on success AND abort)
    from annotatedvdb_tpu.obs import ObsSession
    from annotatedvdb_tpu.utils.profiling import stall_summary

    obs = ObsSession.from_args("load-vcf", args, {
        "file": args.fileName, "store": args.storeDir,
        "commit": cfg.commit, "test": cfg.test, "resume": cfg.resume,
        "datasource": cfg.datasource, "batch_size": cfg.commit_after,
        "skip_existing": args.skipExisting,
        "pipeline": os.environ.get("AVDB_PIPELINE", "overlapped"),
    })
    obs.attach(loader)
    # the whole load lifecycle sits in one try: warmup compiles, the load
    # itself, close() (which surfaces deferred store-writer exceptions),
    # and the final save can each die — the run ledger must witness every
    # abort, not just mid-stream ones
    try:
        # compile the device kernels (and probe the packed-output
        # transport) before streaming begins: a steady-state load should
        # not pay the first-compile cost mid-stream
        with startup_phase("programs"):
            loader.warmup()
        with device_trace(args.profile):
            counters = loader.load_file(
                args.fileName,
                commit=cfg.commit,
                test=cfg.test,
                fail_at=cfg.fail_at,
                mapping_path=args.fileName + ".mapping",
                resume=cfg.resume,
                # persist before every checkpoint so the durable store never
                # lags the resume cursor (crash between them would silently
                # skip rows)
                persist=lambda: store.save(args.storeDir),
            )
        loader.close()
        if cfg.commit:
            store.save(args.storeDir)
    except BaseException as exc:
        # witness the crash in the run ledger, then propagate unchanged
        obs.abort(ledger, exc, store=store)
        raise
    if cfg.commit:
        log(f"COMMITTED {counters}")
    else:
        log(f"ROLLING BACK (dry run) {counters}")
    log(f"stage breakdown: {loader.timer.summary()}")
    if loader.queue_stalls:
        log(f"queue stalls: "
            f"{stall_summary(loader.queue_stalls, loader.timer.wall_seconds)}")
    obs.finish(ledger, counters, store=store)
    print(counters["alg_id"])  # undo handle, like load_vcf_file.py:220
    return 0


if __name__ == "__main__":
    sys.exit(main())
