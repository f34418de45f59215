"""CLI: annotate stored variants from Ensembl VEP JSON output
(``Load/bin/load_vep_result.py`` equivalent; update-only).

Usage: python -m annotatedvdb_tpu.cli.load_vep --fileName results.json[.gz] \
           --storeDir ./vdb [--rankingFile ranks.txt] [--commit] ...
"""

from __future__ import annotations

import argparse
import os
import sys

from annotatedvdb_tpu.conseq import ConsequenceRanker
from annotatedvdb_tpu.loaders import TpuVepLoader
from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore


def main(argv=None):
    # platform pinning happens in runtime.apply() AFTER argparse, so the
    # --platform flag is known
    parser = argparse.ArgumentParser(description="load VEP JSON results")
    parser.add_argument("--fileName", required=True)
    parser.add_argument("--storeDir", required=True)
    parser.add_argument("--rankingFile", default=None,
                        help="consequence ranking TSV; omitted -> the shipped "
                             "294-combo ADSP seed (the reference's "
                             "Load/data/custom_consequence_ranking.txt), "
                             "ranked on load")
    parser.add_argument("--rankOnLoad", action="store_true", default=None,
                        help="re-rank the ranking file on load (implied for "
                             "the shipped default seed)")
    parser.add_argument("--saveOnAddConsequence", action="store_true")
    parser.add_argument("--datasource", default=None)
    from annotatedvdb_tpu.config import (
        add_lifecycle_args,
        add_runtime_args,
        effective_log_after,
        runtime_from_args,
    )

    add_lifecycle_args(parser)
    add_runtime_args(parser)
    parser.add_argument("--skipExisting", action="store_true",
                        help="skip variants that already have vep_output")
    from annotatedvdb_tpu.obs import ObsSession, add_obs_args

    add_obs_args(parser)
    args = parser.parse_args(argv)

    runtime = runtime_from_args(args)
    try:
        runtime.validate()
    except ValueError as err:
        parser.error(str(err))
    mesh = runtime.apply()  # platform pin + multihost + update mesh

    from annotatedvdb_tpu.utils.logging import load_logger

    log, _logger, _log_path = load_logger(
        args.fileName, "load-vep", args.logFilePath
    )

    store = VariantStore.load(args.storeDir)
    ledger = AlgorithmLedger(os.path.join(args.storeDir, "ledger.jsonl"))
    ranker = ConsequenceRanker(
        args.rankingFile,
        save_on_add=args.saveOnAddConsequence,
        rank_on_load=args.rankOnLoad,
    )
    from annotatedvdb_tpu.config import quarantine_from_args

    loader = TpuVepLoader(
        store, ledger, ranker,
        datasource=args.datasource,
        skip_existing=args.skipExisting,
        log=log,
        log_after=effective_log_after(args.logAfter, 1 << 14),
        mesh=mesh,
        quarantine=quarantine_from_args(args, args.storeDir, "load-vep",
                                        log=log),
        max_errors=args.maxErrors,
    )
    obs = ObsSession.from_args("load-vep", args, {
        "file": args.fileName, "store": args.storeDir,
        "commit": args.commit, "test": args.test,
        "datasource": args.datasource, "skip_existing": args.skipExisting,
    })
    obs.attach(loader)
    try:
        counters = loader.load_file(
            args.fileName, commit=args.commit, test=args.test
        )
        # the commit save sits inside the try: a full-disk save is an
        # abort the run ledger must witness too
        if args.commit:
            store.save(args.storeDir)
    except BaseException as exc:
        obs.abort(ledger, exc, store=store)
        raise
    if args.commit:
        log(f"COMMITTED {counters}")
    else:
        log(f"ROLLING BACK (dry run) {counters}")
    obs.finish(ledger, counters, store=store)
    print(counters["alg_id"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
