"""What a killed server left durable: the committed store on disk and its
write-ahead log, read offline by the program's own readers.

``python benchmarks/children/durable_child.py <store dir> <ids.json>
<out.json>``: for each ``chr:pos:ref:alt`` of ``ids.json``, the rs number
and the annotations (``[rs, {column: value}]``) of the row the committed
store holds (``VariantStore.load``; a host probe) and of the row a WAL
record carries (``WriteAheadLog("serve-w0").replay_records``, the worker's
own recovery scan) — ``None`` where there is none.  The driver compares
them with the reference after it has SIGKILLed the server: an
acknowledged insert must be in one of them, exactly.  Runs on the CPU; the
chip is not needed.
"""

from __future__ import annotations

import json
import sys

from _report import write_json


def main(argv: list) -> int:
    store_dir, ids_path, out_path = argv
    import numpy as np

    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.serve.engine import parse_variant_id
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.store.variant_store import JSONB_COLUMNS
    from annotatedvdb_tpu.store.wal import WriteAheadLog
    from annotatedvdb_tpu.types import chromosome_label, encode_allele_array

    with open(ids_path) as f:
        ids = json.load(f)
    found = {ident: {"store": None, "wal": None} for ident in ids}
    store = VariantStore.load(store_dir, readonly=True)
    by_code: dict = {}
    for ident in ids:
        by_code.setdefault(parse_variant_id(ident)[0], []).append(ident)
    for code, group in by_code.items():
        shard = store.shards.get(code)
        if shard is None:
            continue
        parsed = [parse_variant_id(ident) for ident in group]
        refs, alts = [p[2] for p in parsed], [p[3] for p in parsed]
        ref, ref_len = encode_allele_array(refs, store.width)
        alt, alt_len = encode_allele_array(alts, store.width)
        pos = np.array([p[1] for p in parsed], np.int32)
        h = identity_hashes(store.width, ref, alt, ref_len, alt_len,
                            refs, alts)
        hit, gid = shard.lookup(pos, h, ref, alt, ref_len, alt_len,
                                host_only=True)
        for ident, ok, g in zip(group, hit.tolist(), gid.tolist()):
            if not ok:
                continue
            ann = {}
            for column in JSONB_COLUMNS:
                value = shard.get_ann(column, g)
                if value is not None:
                    ann[column] = value
            found[ident]["store"] = [
                int(shard.get_col("ref_snp", [g])[0]), ann]
    wal = WriteAheadLog(store_dir, "serve-w0")
    for record in wal.replay_records():
        for row in record.get("rows") or ():
            ident = (f"{chromosome_label(row['code'])}:{row['pos']}:"
                     f"{row['ref']}:{row['alt']}")
            if ident in found and found[ident]["wal"] is None:
                found[ident]["wal"] = [row.get("ref_snp"),
                                       row.get("ann") or {}]
    write_json(out_path, {"found": found, "rows": int(store.n)})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
