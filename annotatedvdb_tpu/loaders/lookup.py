"""Shared chunk→store identity resolution.

One definition of the identity rule used everywhere a parsed chunk is joined
against the store: device FNV hash over the width-bounded alleles, host
re-hash from the original strings for over-width rows (their device arrays
are truncated, so the device hash would collide on shared prefixes), then a
per-chromosome sorted-merge lookup against the shard.

The serving read path (``serve/engine.py``) and the upsert path
(``store/memtable.py``) build a group's identity columns from
client-supplied alleles through :func:`identity_columns`: one native pass
for ASCII groups, else :func:`identity_hashes` — the numpy twin of the
same rule — so a query hashes byte-identically to the load that wrote the
row.
"""

from __future__ import annotations

import numpy as np

from annotatedvdb_tpu.io.vcf import VcfChunk
from annotatedvdb_tpu.native import identity as native_identity
from annotatedvdb_tpu.ops.hashing import allele_hash_jit, allele_hash_np
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.types import encode_allele_array

#: cumulative rows of this process's :func:`identity_columns` calls (the
#: ``store.variant_store.probe_stats`` pattern; ``/stats`` ``identity``),
#: by route: ``native_rows`` through the one native pass, ``scalar_rows``
#: through ``encode_allele_array`` + :func:`identity_hashes`; added once a
#: group, never a row
identity_stats = {"rows": 0, "native_rows": 0, "scalar_rows": 0}


def identity_columns(refs: list, alts: list, width: int) -> tuple:
    """``(ref, alt, ref_len, alt_len, h)`` of one group of (ref, alt)
    allele strings: the ``[N, width]`` uint8 rows and int32 true lengths
    of :func:`~annotatedvdb_tpu.types.encode_allele_array` for each side,
    and :func:`identity_hashes` with its over-width override.  An ASCII
    group with the native library loaded takes one native pass; any other
    takes those two functions, which stay the definition — the bytes are
    the same either way."""
    n = len(refs)
    identity_stats["rows"] += n
    ref_text, alt_text = "".join(refs), "".join(alts)
    if ref_text.isascii() and alt_text.isascii():
        ref_len = np.fromiter(map(len, refs), np.int32, count=n)
        alt_len = np.fromiter(map(len, alts), np.int32, count=n)
        got = native_identity.identity_columns(
            ref_text.encode("ascii"), ref_len,
            alt_text.encode("ascii"), alt_len, width,
        )
        if got is not None:
            identity_stats["native_rows"] += n
            ref, alt, h = got
            return ref, alt, ref_len, alt_len, h
    identity_stats["scalar_rows"] += n
    ref, ref_len = encode_allele_array(refs, width)
    alt, alt_len = encode_allele_array(alts, width)
    h = identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts)
    return ref, alt, ref_len, alt_len, h


def identity_hashes(width: int, ref: np.ndarray, alt: np.ndarray,
                    ref_len: np.ndarray, alt_len: np.ndarray,
                    refs=None, alts=None) -> np.ndarray:
    """[N] uint32 identity hashes, host path: numpy FNV over the
    width-bounded allele arrays, with the over-width host-string override
    when the original strings are supplied.  Must stay bit-identical to the
    loader's device hashing (``chunk_hashes``) — store membership compares
    these against load-time hashes."""
    from annotatedvdb_tpu.loaders.vcf_loader import _fnv32_str

    h = allele_hash_np(ref, alt, ref_len, alt_len)
    if refs is not None:
        for i in np.where((np.asarray(ref_len) > width)
                          | (np.asarray(alt_len) > width))[0]:
            h[i] = _fnv32_str(refs[i], alts[i])
    return h


def chunk_hashes(store: VariantStore, chunk: VcfChunk) -> np.ndarray:
    """[N] uint32 identity hashes with the over-width host override."""
    from annotatedvdb_tpu.loaders.vcf_loader import _fnv32_str

    batch = chunk.batch
    if chunk.h_native is not None:
        # tokenizer-computed twin: skip the device kernel + result fetch
        h = chunk.h_native.copy()
    else:
        h = np.array(
            allele_hash_jit(batch.ref, batch.alt, batch.ref_len, batch.alt_len)
        )
    over = (batch.ref_len > store.width) | (batch.alt_len > store.width)
    for i in np.where(over)[0]:
        h[i] = _fnv32_str(chunk.refs[i], chunk.alts[i])
    return h


def chunk_lookup(store: VariantStore, chunk: VcfChunk, h: np.ndarray | None = None):
    """Yield (code, shard, sel, found, idx) per chromosome present in the
    chunk.  ``shard`` is None (with found all-False) for chromosomes the
    store does not hold — callers must not create shards as a side effect of
    a lookup (empty shards would be persisted by the next save; read paths
    can make that structurally impossible by opening with
    ``VariantStore.load(..., readonly=True)``)."""
    batch = chunk.batch
    if h is None:
        h = chunk_hashes(store, chunk)
    for code in np.unique(batch.chrom):
        sel = np.where(batch.chrom == code)[0]
        shard = store.shards.get(int(code))
        if shard is None:
            yield (
                int(code), None, sel,
                np.zeros(sel.shape, bool), np.full(sel.shape, -1, np.int32),
            )
            continue
        found, idx = shard.lookup(
            batch.pos[sel], h[sel], batch.ref[sel], batch.alt[sel],
            batch.ref_len[sel], batch.alt_len[sel],
        )
        yield int(code), shard, sel, found, idx
