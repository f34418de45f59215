"""CLI: update/insert from an ADSP QC pVCF
(``Load/bin/update_from_qc_pvcf_file.py`` equivalent).

Usage:
    python -m annotatedvdb_tpu.cli.update_qc --fileName qc.vcf[.gz] \
        --storeDir ./vdb --version r4 [--updateExistingValues] \
        [--commit] [--test] [--chromosomeMap map.tsv]
"""

from __future__ import annotations

import argparse
import json
import os

from annotatedvdb_tpu.io.vcf import read_chromosome_map
from annotatedvdb_tpu.loaders.qc_loader import TpuQcPvcfLoader
from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore


def main(argv=None) -> int:
    from annotatedvdb_tpu.utils.runtime import pin_platform

    # honor an explicit cpu pin, place the compile cache
    pin_platform("auto")

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fileName", required=True)
    ap.add_argument("--storeDir", required=True)
    ap.add_argument("--version", required=True,
                    help="ADSP release tag keying the adsp_qc JSONB (e.g. r4)")
    ap.add_argument("--updateExistingValues", action="store_true")
    ap.add_argument("--chromosomeMap")
    from annotatedvdb_tpu.config import add_lifecycle_args, effective_log_after
    from annotatedvdb_tpu.obs import ObsSession, add_obs_args

    add_lifecycle_args(ap)
    add_obs_args(ap)
    args = ap.parse_args(argv)

    from annotatedvdb_tpu.utils.logging import load_logger

    log, _logger, _log_path = load_logger(args.fileName, "update-qc", args.logFilePath)

    store = VariantStore.load(args.storeDir)
    ledger = AlgorithmLedger(os.path.join(args.storeDir, "ledger.jsonl"))
    from annotatedvdb_tpu.config import quarantine_from_args

    loader = TpuQcPvcfLoader(
        store, ledger, args.version,
        update_existing=args.updateExistingValues,
        datasource="ADSP",
        chromosome_map=(
            read_chromosome_map(args.chromosomeMap) if args.chromosomeMap else None
        ),
        log=log,
        log_after=effective_log_after(args.logAfter, 1 << 15),
        quarantine=quarantine_from_args(args, args.storeDir, "update-qc",
                                        log=log),
        max_errors=args.maxErrors,
    )
    obs = ObsSession.from_args("update-qc", args, {
        "file": args.fileName, "store": args.storeDir,
        "version": args.version, "commit": args.commit, "test": args.test,
        "update_existing": args.updateExistingValues,
    })
    obs.attach(loader)
    try:
        counters = loader.load_file(
            args.fileName, commit=args.commit, test=args.test,
            persist=(lambda: store.save(args.storeDir)) if args.commit else None,
        )
    except BaseException as exc:
        obs.abort(ledger, exc, store=store)
        raise
    obs.finish(ledger, counters, store=store)
    print(json.dumps(counters))
    print(counters["alg_id"])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
