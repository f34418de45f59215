"""CLI: generic TSV-driven annotation updates
(``Load/bin/update_variant_annotation.py`` equivalent).

The input is tab-delimited with a ``variant`` column (metaseq id, refSNP id,
or record primary key per ``--variantIdType``) plus columns named after
Variant-table fields; update fields are inferred from the header.

Usage:
    python -m annotatedvdb_tpu.cli.update_variant_annotation \
        --fileName ann.tsv --storeDir ./vdb [--variantIdType METASEQ] \
        [--datasource NIAGADS] [--skipExisting] [--commit] [--test]
"""

from __future__ import annotations

import argparse
import json
import os

from annotatedvdb_tpu.loaders.txt_loader import TpuTextLoader, VARIANT_ID_TYPES
from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore


def main(argv=None) -> int:
    from annotatedvdb_tpu.utils.runtime import pin_platform

    # honor an explicit cpu pin, place the compile cache
    pin_platform("auto")

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fileName", required=True)
    ap.add_argument("--storeDir", required=True)
    ap.add_argument("--variantIdType", default="METASEQ",
                    choices=VARIANT_ID_TYPES)
    ap.add_argument("--datasource", default=None)
    ap.add_argument("--skipExisting", action="store_true",
                    help="skip known variants instead of updating them")
    from annotatedvdb_tpu.config import add_lifecycle_args, effective_log_after
    from annotatedvdb_tpu.obs import ObsSession, add_obs_args

    add_lifecycle_args(ap)
    add_obs_args(ap)
    args = ap.parse_args(argv)

    from annotatedvdb_tpu.utils.logging import load_logger

    log, _logger, _log_path = load_logger(args.fileName, "update-annotation", args.logFilePath)

    store = VariantStore.load(args.storeDir)
    ledger = AlgorithmLedger(os.path.join(args.storeDir, "ledger.jsonl"))
    from annotatedvdb_tpu.config import quarantine_from_args

    loader = TpuTextLoader(
        store, ledger,
        variant_id_type=args.variantIdType,
        datasource=args.datasource,
        update_existing=not args.skipExisting,
        skip_existing=args.skipExisting,
        log=log,
        log_after=effective_log_after(args.logAfter, 1 << 15),
        quarantine=quarantine_from_args(
            args, args.storeDir, "update-variant-annotation", log=log
        ),
        max_errors=args.maxErrors,
    )
    obs = ObsSession.from_args("update-variant-annotation", args, {
        "file": args.fileName, "store": args.storeDir,
        "id_type": args.variantIdType, "commit": args.commit,
        "test": args.test, "datasource": args.datasource,
        "skip_existing": args.skipExisting,
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
