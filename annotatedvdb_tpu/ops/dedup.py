"""Within-batch duplicate detection and batch-vs-store membership join.

The reference does both through Postgres: per-variant ``exists`` checks via a
``map_variants()`` SQL round-trip (``Util/lib/python/database/variant.py:287-309``)
and 1000-id bulk lookups via a set-returning function (``:159-191``).  Here:

- within-batch dedup = one lexicographic ``lax.sort`` on (pos, hash) carrying
  the row index, then neighbor compare with full byte confirmation;
- batch-vs-store membership = ``searchsorted`` of query keys into the store's
  sorted (pos, hash) keys (store keys are built once per flush, on device,
  and kept sorted host-side), with hash matches confirmed by byte equality
  against the candidate row.

Chromosome never enters the keys: the store is chromosome-sharded (one shard
owns one chromosome's rows, mirroring the reference's LIST partitions,
``createVariant.sql:24``), so all rows in a batch share a chromosome by the
time they reach these kernels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from annotatedvdb_tpu.parallel.mesh import mesh_pjit


@jax.named_scope("avdb.dedup")
def mark_batch_duplicates(pos, h, ref, alt, ref_len, alt_len):
    """Flag rows that duplicate an earlier row in the batch.

    Returns (is_duplicate [N] bool, in original row order).  'Earlier' means
    smaller original row index — matching the reference's first-wins
    skip-duplicates policy on sequential file order
    (``vcf_variant_loader.py`` duplicate counter / skipExisting flow)."""
    n = pos.shape[0]
    # carry original index through an identity sort that tiebreaks on index:
    # sort by (pos, hash, index) so equal identities are in file order.
    idx = jnp.arange(n, dtype=jnp.int32)
    pos_s, h_s, idx_s = jax.lax.sort((pos, h, idx), num_keys=3)
    ref_s, alt_s = ref[idx_s], alt[idx_s]
    rlen_s, alen_s = ref_len[idx_s], alt_len[idx_s]

    same_key = (pos_s[1:] == pos_s[:-1]) & (h_s[1:] == h_s[:-1])
    same_len = (rlen_s[1:] == rlen_s[:-1]) & (alen_s[1:] == alen_s[:-1])
    same_bytes = jnp.all(ref_s[1:] == ref_s[:-1], axis=1) & jnp.all(
        alt_s[1:] == alt_s[:-1], axis=1
    )
    dup_next = same_key & same_len & same_bytes  # row i+1 duplicates row i
    # chains of equal rows: every row after the first in a run is a duplicate.
    dup_sorted = jnp.concatenate([jnp.zeros((1,), jnp.bool_), dup_next])
    # scatter back to original order
    return jnp.zeros((n,), jnp.bool_).at[idx_s].set(dup_sorted)


@jax.named_scope("avdb.probe")
def lookup_in_sorted(
    store_pos, store_h, store_ref, store_alt, store_rlen, store_alen,
    pos, h, ref, alt, ref_len, alt_len,
):
    """Membership of query rows in a (pos, hash)-sorted store slice.

    Returns (found [N] bool, store_index [N] int32; -1 when absent).  The
    store slice must be sorted by (pos, hash) with unique identities (the
    store dedups on append).  Search is a two-level binary search: global
    ``searchsorted`` on position, then a fixed-depth per-row binary search
    for the hash inside the equal-position run (runs are multi-allelic
    sites), then byte confirmation over the short run of equal (pos, hash)
    keys."""
    m = store_pos.shape[0]
    lo = jnp.searchsorted(store_pos, pos, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(store_pos, pos, side="right").astype(jnp.int32)

    # lower_bound of h in store_h[lo:hi) — 32 halvings cover any run length
    l, r = lo, hi
    for _ in range(32):
        active = l < r
        mid = (l + r) >> 1
        less = store_h[jnp.clip(mid, 0, m - 1)] < h
        l = jnp.where(active & less, mid + 1, l)
        r = jnp.where(active & ~less, mid, r)

    # confirm bytes over the (pos, hash)-equal run; different identities can
    # collide on (pos, hash) only via a 2^-32 hash collision, so the run is
    # effectively 1 row — probe a few to stay exact regardless.
    found = jnp.zeros(pos.shape, jnp.bool_)
    index = jnp.full(pos.shape, -1, jnp.int32)
    for k in range(4):
        i = jnp.clip(l + k, 0, m - 1)
        cand = (
            (l + k < hi)
            & (store_pos[i] == pos)
            & (store_h[i] == h)
            & (store_rlen[i] == ref_len)
            & (store_alen[i] == alt_len)
            & jnp.all(store_ref[i] == ref, axis=1)
            & jnp.all(store_alt[i] == alt, axis=1)
        )
        take = cand & ~found
        found = found | cand
        index = jnp.where(take, i, index)
    return found, index


#: bytes of one query in the packed buffer beside its two alleles: pos,
#: hash, ref_len, alt_len, four bytes each
_QUERY_WORDS_BYTES = 16


def pack_queries(pos, h, ref, alt, ref_len, alt_len, cap: int):
    """A probe's six query columns as ONE host buffer, ``cap`` queries
    wide: ``uint8 [cap * (16 + 2 * width)]``, column after column — pos,
    h, ref, alt, ref_len, alt_len, each contiguous, the 32-bit ones in the
    host's (little-endian) bytes — so six copies fill it and one upload
    carries it.  Rows past the queries are padding: the sentinel position
    (no row's), zeros elsewhere.  :func:`lookup_in_sorted_packed` takes
    it apart on the device."""
    import numpy as np

    from annotatedvdb_tpu.utils.arrays import POS_SENTINEL

    nq, width = ref.shape
    buf = np.zeros(cap * (_QUERY_WORDS_BYTES + 2 * width), np.uint8)
    words = buf[: cap * 8].view(np.int32)
    words[:nq] = pos
    words[nq:cap] = POS_SENTINEL
    words[cap:cap + nq] = np.asarray(h, np.uint32).view(np.int32)
    at = cap * 8
    for allele in (ref, alt):
        buf[at:at + nq * width] = allele.reshape(-1)
        at += cap * width
    words = buf[at:].view(np.int32)
    words[:nq] = ref_len
    words[cap:cap + nq] = alt_len
    return buf


def _unpack_queries(buf, width: int):
    """:func:`pack_queries`'s buffer as its six device columns."""
    cap = buf.shape[0] // (_QUERY_WORDS_BYTES + 2 * width)

    def column(at, dtype=jnp.int32):
        return jax.lax.bitcast_convert_type(
            buf[at:at + cap * 4].reshape(cap, 4), dtype
        )

    alleles = cap * 8
    lens = alleles + 2 * cap * width
    return (
        column(0), column(cap * 4, jnp.uint32),
        buf[alleles:alleles + cap * width].reshape(cap, width),
        buf[alleles + cap * width:lens].reshape(cap, width),
        column(lens), column(lens + cap * 4),
    )


def lookup_in_sorted_packed(
    store_pos, store_h, store_ref, store_alt, store_rlen, store_alen,
    queries,
):
    """:func:`lookup_in_sorted` as the store's device probe calls it: the
    queries arrive as :func:`pack_queries`'s one buffer and the answer
    leaves as one array, the int32 store index (-1 when absent; found is
    ``index >= 0``) — one upload and one fetch a probe, where six arrays
    in and two out made eight transfers; on a v5e's host a small probe
    costs what its transfers cost (1.30 ms against 2.43 at 32 queries:
    PERF.md section 6, PR 29).  The search is :func:`lookup_in_sorted`'s,
    written once."""
    with jax.named_scope("avdb.probe"):
        query = _unpack_queries(queries, store_ref.shape[1])
    _found, index = lookup_in_sorted(
        store_pos, store_h, store_ref, store_alt, store_rlen, store_alen,
        *query,
    )
    return index


@jax.named_scope("avdb.dedup_multi")
def mark_batch_duplicates_multi(chrom, pos, h, ref, alt, ref_len, alt_len):
    """Chromosome-aware :func:`mark_batch_duplicates` for mesh shards that
    own SEVERAL chromosomes (``parallel.distributed.chromosome_owner`` packs
    ~3 per shard on an 8-way mesh): the identity sort carries the chromosome
    as the leading key, so equal (pos, hash) rows of different chromosomes
    never compare as duplicates."""
    n = pos.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    chrom_s, pos_s, h_s, idx_s = jax.lax.sort(
        (chrom.astype(jnp.int32), pos, h, idx), num_keys=4
    )
    ref_s, alt_s = ref[idx_s], alt[idx_s]
    rlen_s, alen_s = ref_len[idx_s], alt_len[idx_s]
    same_key = (
        (chrom_s[1:] == chrom_s[:-1])
        & (pos_s[1:] == pos_s[:-1])
        & (h_s[1:] == h_s[:-1])
    )
    same_len = (rlen_s[1:] == rlen_s[:-1]) & (alen_s[1:] == alen_s[:-1])
    same_bytes = jnp.all(ref_s[1:] == ref_s[:-1], axis=1) & jnp.all(
        alt_s[1:] == alt_s[:-1], axis=1
    )
    dup_next = same_key & same_len & same_bytes
    dup_sorted = jnp.concatenate([jnp.zeros((1,), jnp.bool_), dup_next])
    return jnp.zeros((n,), jnp.bool_).at[idx_s].set(dup_sorted)


#: golden-ratio odd constant decorrelating chromosomes in the mixed hash
#: (the per-shard membership slices hold several chromosomes in ONE
#: (pos, mixed-hash)-sorted run — see ``parallel.device_store``)
CHROM_MIX = 0x9E3779B9


def mix_chrom_hash(h, chrom):
    """Chromosome-salted identity hash for multi-chromosome sorted runs."""
    return h ^ (chrom.astype(jnp.uint32) * jnp.uint32(CHROM_MIX))


@jax.named_scope("avdb.probe_multi")
def lookup_in_sorted_multi(
    store_chrom, store_pos, store_hm, store_ref, store_alt,
    store_rlen, store_alen,
    chrom, pos, hm, ref, alt, ref_len, alt_len,
):
    """Membership in a multi-chromosome shard slice sorted by
    (pos, chrom-mixed hash).  Same two-level search as
    :func:`lookup_in_sorted`; byte confirmation additionally compares the
    chromosome, so a cross-chromosome (pos, mixed-hash) collision cannot
    produce a false hit."""
    m = store_pos.shape[0]
    lo = jnp.searchsorted(store_pos, pos, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(store_pos, pos, side="right").astype(jnp.int32)
    l, r = lo, hi
    for _ in range(32):
        active = l < r
        mid = (l + r) >> 1
        less = store_hm[jnp.clip(mid, 0, m - 1)] < hm
        l = jnp.where(active & less, mid + 1, l)
        r = jnp.where(active & ~less, mid, r)
    found = jnp.zeros(pos.shape, jnp.bool_)
    index = jnp.full(pos.shape, -1, jnp.int32)
    for k in range(4):
        i = jnp.clip(l + k, 0, m - 1)
        cand = (
            (l + k < hi)
            & (store_pos[i] == pos)
            & (store_hm[i] == hm)
            & (store_chrom[i] == chrom)
            & (store_rlen[i] == ref_len)
            & (store_alen[i] == alt_len)
            & jnp.all(store_ref[i] == ref, axis=1)
            & jnp.all(store_alt[i] == alt, axis=1)
        )
        take = cand & ~found
        found = found | cand
        index = jnp.where(take, i, index)
    return found, index


mark_batch_duplicates_jit = jax.jit(mark_batch_duplicates)
mark_batch_duplicates_multi_jit = jax.jit(mark_batch_duplicates_multi)
lookup_in_sorted_jit = jax.jit(lookup_in_sorted)
lookup_in_sorted_packed_jit = jax.jit(lookup_in_sorted_packed)
lookup_in_sorted_multi_jit = jax.jit(lookup_in_sorted_multi)

# the sharded-call surface (pjit with batch-dim-sharded inputs) — the
# in-batch dedup stage of the sharded ingest pipeline.  The identity sort
# is global, so XLA inserts the cross-device collectives itself (jit
# semantics are sharding-independent); pad rows carry unique NEGATIVE
# positions (the insert step's salting trick), so they can never compare
# equal to a real row or each other.  Host twin: mark_batch_duplicates_np.
mark_batch_duplicates_mesh = mesh_pjit(
    mark_batch_duplicates_jit,
    ("neg_unique", "zero", "zero", "zero", "one", "one"),
)


# ---- numpy host twins (ops.TWINS registry; tests/test_twins.py) -------
#
# Each is the same algorithm in host numpy: the lexicographic identity
# sort / two-level sorted probe over the same dtypes, so answers are
# identical arrays.  They are the fallback the serving breaker and the
# remote-link paths can take without a device in reach.


def mark_batch_duplicates_np(pos, h, ref, alt, ref_len, alt_len):
    """Numpy twin of :func:`mark_batch_duplicates`."""
    import numpy as np

    pos = np.asarray(pos)
    h = np.asarray(h)
    n = pos.shape[0]
    idx = np.arange(n)
    order = np.lexsort((idx, h, pos))  # primary key last: (pos, h, idx)
    pos_s, h_s = pos[order], h[order]
    ref_s, alt_s = np.asarray(ref)[order], np.asarray(alt)[order]
    rlen_s = np.asarray(ref_len)[order]
    alen_s = np.asarray(alt_len)[order]
    same_key = (pos_s[1:] == pos_s[:-1]) & (h_s[1:] == h_s[:-1])
    same_len = (rlen_s[1:] == rlen_s[:-1]) & (alen_s[1:] == alen_s[:-1])
    same_bytes = (ref_s[1:] == ref_s[:-1]).all(axis=1) & (
        alt_s[1:] == alt_s[:-1]
    ).all(axis=1)
    dup_sorted = np.concatenate(
        [np.zeros(1, bool), same_key & same_len & same_bytes]
    )
    out = np.zeros(n, bool)
    out[order] = dup_sorted
    return out


def mark_batch_duplicates_multi_np(chrom, pos, h, ref, alt,
                                   ref_len, alt_len):
    """Numpy twin of :func:`mark_batch_duplicates_multi`."""
    import numpy as np

    chrom = np.asarray(chrom, np.int32)
    pos = np.asarray(pos)
    h = np.asarray(h)
    n = pos.shape[0]
    idx = np.arange(n)
    order = np.lexsort((idx, h, pos, chrom))
    chrom_s, pos_s, h_s = chrom[order], pos[order], h[order]
    ref_s, alt_s = np.asarray(ref)[order], np.asarray(alt)[order]
    rlen_s = np.asarray(ref_len)[order]
    alen_s = np.asarray(alt_len)[order]
    same_key = (
        (chrom_s[1:] == chrom_s[:-1])
        & (pos_s[1:] == pos_s[:-1])
        & (h_s[1:] == h_s[:-1])
    )
    same_len = (rlen_s[1:] == rlen_s[:-1]) & (alen_s[1:] == alen_s[:-1])
    same_bytes = (ref_s[1:] == ref_s[:-1]).all(axis=1) & (
        alt_s[1:] == alt_s[:-1]
    ).all(axis=1)
    dup_sorted = np.concatenate(
        [np.zeros(1, bool), same_key & same_len & same_bytes]
    )
    out = np.zeros(n, bool)
    out[order] = dup_sorted
    return out


def lookup_in_sorted_np(
    store_pos, store_h, store_ref, store_alt, store_rlen, store_alen,
    pos, h, ref, alt, ref_len, alt_len,
):
    """Numpy twin of :func:`lookup_in_sorted` (same two-level search and
    fixed confirmation probes)."""
    import numpy as np

    store_pos = np.asarray(store_pos)
    store_h = np.asarray(store_h)
    store_ref, store_alt = np.asarray(store_ref), np.asarray(store_alt)
    store_rlen = np.asarray(store_rlen)
    store_alen = np.asarray(store_alen)
    pos, h = np.asarray(pos), np.asarray(h)
    ref, alt = np.asarray(ref), np.asarray(alt)
    ref_len, alt_len = np.asarray(ref_len), np.asarray(alt_len)
    m = store_pos.shape[0]
    lo = np.searchsorted(store_pos, pos, side="left").astype(np.int32)
    hi = np.searchsorted(store_pos, pos, side="right").astype(np.int32)
    l, r = lo, hi
    for _ in range(32):
        active = l < r
        mid = (l + r) >> 1
        less = store_h[np.clip(mid, 0, m - 1)] < h
        l = np.where(active & less, mid + 1, l)
        r = np.where(active & ~less, mid, r)
    found = np.zeros(pos.shape, bool)
    index = np.full(pos.shape, -1, np.int32)
    for k in range(4):
        i = np.clip(l + k, 0, m - 1)
        cand = (
            (l + k < hi)
            & (store_pos[i] == pos)
            & (store_h[i] == h)
            & (store_rlen[i] == ref_len)
            & (store_alen[i] == alt_len)
            & (store_ref[i] == ref).all(axis=1)
            & (store_alt[i] == alt).all(axis=1)
        )
        take = cand & ~found
        found = found | cand
        index = np.where(take, i.astype(np.int32), index)
    return found, index


def lookup_in_sorted_multi_np(
    store_chrom, store_pos, store_hm, store_ref, store_alt,
    store_rlen, store_alen,
    chrom, pos, hm, ref, alt, ref_len, alt_len,
):
    """Numpy twin of :func:`lookup_in_sorted_multi`."""
    import numpy as np

    store_chrom = np.asarray(store_chrom)
    store_pos = np.asarray(store_pos)
    store_hm = np.asarray(store_hm)
    store_ref, store_alt = np.asarray(store_ref), np.asarray(store_alt)
    store_rlen = np.asarray(store_rlen)
    store_alen = np.asarray(store_alen)
    chrom, pos, hm = np.asarray(chrom), np.asarray(pos), np.asarray(hm)
    ref, alt = np.asarray(ref), np.asarray(alt)
    ref_len, alt_len = np.asarray(ref_len), np.asarray(alt_len)
    m = store_pos.shape[0]
    lo = np.searchsorted(store_pos, pos, side="left").astype(np.int32)
    hi = np.searchsorted(store_pos, pos, side="right").astype(np.int32)
    l, r = lo, hi
    for _ in range(32):
        active = l < r
        mid = (l + r) >> 1
        less = store_hm[np.clip(mid, 0, m - 1)] < hm
        l = np.where(active & less, mid + 1, l)
        r = np.where(active & ~less, mid, r)
    found = np.zeros(pos.shape, bool)
    index = np.full(pos.shape, -1, np.int32)
    for k in range(4):
        i = np.clip(l + k, 0, m - 1)
        cand = (
            (l + k < hi)
            & (store_pos[i] == pos)
            & (store_hm[i] == hm)
            & (store_chrom[i] == chrom)
            & (store_rlen[i] == ref_len)
            & (store_alen[i] == alt_len)
            & (store_ref[i] == ref).all(axis=1)
            & (store_alt[i] == alt).all(axis=1)
        )
        take = cand & ~found
        found = found | cand
        index = np.where(take, i.astype(np.int32), index)
    return found, index
