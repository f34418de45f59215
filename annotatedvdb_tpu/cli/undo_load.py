"""CLI: undo a load by algorithm-invocation id
(``Load/bin/undo_variant_load.py`` equivalent — columnar mask delete instead
of chunked SQL DELETE with back-off).

Usage: python -m annotatedvdb_tpu.cli.undo_load --storeDir ./vdb --algId 3 --commit
"""

from __future__ import annotations

import argparse
import os
import sys

from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore


def main(argv=None):
    from annotatedvdb_tpu.utils.runtime import pin_platform

    # host-only CLI: pin CPU outright
    pin_platform("cpu")

    parser = argparse.ArgumentParser(description="undo a variant load")
    parser.add_argument("--storeDir", required=True)
    parser.add_argument("--algId", type=int, required=True)
    parser.add_argument("--commit", action="store_true")
    args = parser.parse_args(argv)

    store = VariantStore.load(args.storeDir)
    removed = store.delete_by_algorithm(args.algId)
    if args.commit:
        # intent BEFORE the save: a crash between the store mutation and
        # the completing `undo` record is then detectable (fsck reports the
        # dangling intent and prescribes re-running this idempotent undo)
        # instead of silently leaving store and ledger inconsistent
        ledger = AlgorithmLedger(os.path.join(args.storeDir, "ledger.jsonl"))
        ledger.undo_intent(args.algId)
        store.save(args.storeDir)
        ledger.undo(args.algId, removed)
        print(f"COMMITTED: removed {removed} rows for algorithm {args.algId}",
              file=sys.stderr)
    else:
        print(f"ROLLING BACK (dry run): would remove {removed} rows",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
