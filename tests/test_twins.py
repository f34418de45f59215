"""Device/host twin parity suite — the runtime half of the ``ops.TWINS``
contract (AVDB9xx).

Every pair registered in ``annotatedvdb_tpu/ops/__init__.py`` is driven
here, kernel and twin on the SAME inputs, answers compared exactly
(``assert_array_equal``, never allclose: the twins are the bytes the
serving breaker / ``host_only`` / remote-link fallbacks actually serve).
The static analyzer's AVDB903 requires each registered pair to co-appear
in one test file — this file is that proof, by construction: it imports
every kernel and every twin by name.

The registry itself is audited first: every TWINS entry must import, and
every jitted symbol this file exercises must be registered.
"""

from __future__ import annotations

import importlib

import numpy as np
import pytest

from annotatedvdb_tpu.ops import TWINS
from annotatedvdb_tpu.ops.annotate import (
    annotate_kernel_jit,
    annotate_kernel_mesh,
    annotate_kernel_np,
)
from annotatedvdb_tpu.ops.annotate_pallas import annotate_bin_pallas
from annotatedvdb_tpu.ops.binindex import (
    bin_index_kernel_jit,
    bin_index_kernel_mesh,
)
from annotatedvdb_tpu.ops.cadd_join import (
    cadd_join_host,
    cadd_join_kernel,
)
from annotatedvdb_tpu.ops.dedup import (
    lookup_in_sorted_jit,
    lookup_in_sorted_multi_jit,
    lookup_in_sorted_multi_np,
    lookup_in_sorted_np,
    lookup_in_sorted_packed_jit,
    mark_batch_duplicates_jit,
    mark_batch_duplicates_mesh,
    mark_batch_duplicates_multi_jit,
    mark_batch_duplicates_multi_np,
    mark_batch_duplicates_np,
    mix_chrom_hash,
    pack_queries,
)
from annotatedvdb_tpu.ops.export_pack import (
    export_pack_host,
    export_pack_kernel_jit,
)
from annotatedvdb_tpu.ops.hashing import (
    allele_hash_jit,
    allele_hash_mesh,
    allele_hash_np,
)
from annotatedvdb_tpu.ops.intervals import (
    bits_spans_kernel_jit,
    bits_spans_stacked_host,
    bits_spans_stacked_jit,
    interval_spans_host,
)
from annotatedvdb_tpu.ops.stats import (
    STATS_MISSING,
    stats_panel_host,
    stats_panel_kernel_jit,
    windowed_stats_host,
    windowed_stats_kernel_jit,
)
from annotatedvdb_tpu.ops.pack import (
    encode_alleles_nibble,
    inflate_alleles_jit,
    inflate_alleles_np,
    pack_outputs_jit,
    pack_outputs_np,
    pack_vep_outputs_jit,
    pack_vep_outputs_np,
    unpack_outputs,
)
from annotatedvdb_tpu.oracle.binindex import closed_form_bin
from annotatedvdb_tpu.types import encode_allele_array
from annotatedvdb_tpu.utils.arrays import POS_SENTINEL

WIDTH = 8
BASES = "ACGT"


def _random_alleles(rng, n, width=WIDTH, max_len=None):
    """Random in-width allele batch: byte matrices + lengths + strings."""
    max_len = max_len or width
    strs = []
    for _ in range(n):
        k = int(rng.integers(1, max_len + 1))
        strs.append("".join(BASES[i] for i in rng.integers(0, 4, k)))
    mat, lens = encode_allele_array(strs, width)
    return mat, lens, strs


def _allele_batch(rng, n):
    ref, ref_len, _ = _random_alleles(rng, n)
    alt, alt_len, _ = _random_alleles(rng, n)
    pos = np.sort(rng.integers(1, 5_000_000, n)).astype(np.int32)
    return pos, ref, alt, ref_len, alt_len


# ---------------------------------------------------------------------------
# registry audit


def test_every_twins_entry_imports():
    """Each registered name (kernel AND twin) resolves to a callable."""
    for kernel, twin in TWINS.items():
        for dotted in (kernel, twin):
            mod, attr = dotted.rsplit(".", 1)
            obj = getattr(
                importlib.import_module(f"annotatedvdb_tpu.{mod}"), attr
            )
            assert callable(obj), dotted


def test_this_suite_references_every_pair():
    """AVDB903's contract, self-checked: every registered kernel and twin
    name appears in this file's source."""
    src = open(__file__, encoding="utf-8").read()
    for kernel, twin in TWINS.items():
        assert kernel.rsplit(".", 1)[1] in src, kernel
        assert twin.rsplit(".", 1)[1] in src, twin


# ---------------------------------------------------------------------------
# annotate family


def test_annotate_kernel_vs_np_twin():
    rng = np.random.default_rng(7)
    pos, ref, alt, ref_len, alt_len = _allele_batch(rng, 256)
    dev = annotate_kernel_jit(pos, ref, alt, ref_len, alt_len)
    host = annotate_kernel_np(pos, ref, alt, ref_len, alt_len)
    assert set(dev) == set(host)
    for key in dev:
        d = np.asarray(dev[key])
        h = np.asarray(host[key])
        assert d.dtype == h.dtype, (key, d.dtype, h.dtype)
        np.testing.assert_array_equal(d, h, err_msg=key)


def test_annotate_kernel_np_dup_motif_case():
    """The duplication-motif branch, pinned explicitly on both sides
    (random batches rarely produce one)."""
    refs, alts = ["AGG", "ATGTG"], ["AGGGG", "AT"]
    ref, ref_len = encode_allele_array(refs, WIDTH)
    alt, alt_len = encode_allele_array(alts, WIDTH)
    pos = np.array([100, 200], np.int32)
    dev = annotate_kernel_jit(pos, ref, alt, ref_len, alt_len)
    host = annotate_kernel_np(pos, ref, alt, ref_len, alt_len)
    for key in dev:
        np.testing.assert_array_equal(
            np.asarray(dev[key]), np.asarray(host[key]), err_msg=key
        )


def test_annotate_pallas_vs_np_twin():
    """The fused Pallas kernel against the SAME host twin (its annotate
    half must agree field for field; the bin half is pinned against the
    bin kernel/oracle in test_annotate_pallas)."""
    rng = np.random.default_rng(11)
    pos, ref, alt, ref_len, alt_len = _allele_batch(rng, 192)
    pal = annotate_bin_pallas(pos, ref, alt, ref_len, alt_len,
                              block_n=128, interpret=True)
    host = annotate_kernel_np(pos, ref, alt, ref_len, alt_len)
    for key in ("prefix_len", "norm_ref_len", "norm_alt_len",
                "end_location", "location_start", "location_end",
                "variant_class", "is_dup_motif", "needs_digest",
                "host_fallback"):
        np.testing.assert_array_equal(
            np.asarray(pal[key]), np.asarray(host[key]), err_msg=key
        )


# ---------------------------------------------------------------------------
# bin index


def test_bin_index_kernel_vs_oracle_twin():
    rng = np.random.default_rng(13)
    start = rng.integers(1, 240_000_000, 512).astype(np.int64)
    end = start + rng.integers(0, 100_000, 512)
    level, leaf = bin_index_kernel_jit(start, end)
    for i in range(len(start)):
        o_level, o_leaf = closed_form_bin(int(start[i]), int(end[i]))
        assert int(level[i]) == o_level, i
        assert int(leaf[i]) == o_leaf, i


# ---------------------------------------------------------------------------
# cadd join


def test_cadd_join_kernel_vs_host_twin():
    rng = np.random.default_rng(17)
    k_rows = 64
    spos = np.sort(rng.integers(1, 10_000, k_rows)).astype(np.int32)
    spos[-8:] = np.iinfo(np.int32).max  # sentinel padding
    sref, _, _ = _random_alleles(rng, k_rows, max_len=2)
    salt, _, _ = _random_alleles(rng, k_rows, max_len=2)
    n = 128
    vpos = rng.integers(1, 10_000, n).astype(np.int32)
    # half the queries copy a real row (guaranteed hits incl. alleles)
    take = rng.integers(0, k_rows - 8, n // 2)
    vpos[: n // 2] = spos[take]
    vref = np.zeros((n, WIDTH), np.uint8)
    valt = np.zeros((n, WIDTH), np.uint8)
    vref[: n // 2] = sref[take]
    valt[: n // 2] = salt[take]
    r2, _, _ = _random_alleles(rng, n - n // 2, max_len=2)
    a2, _, _ = _random_alleles(rng, n - n // 2, max_len=2)
    vref[n // 2:] = r2
    valt[n // 2:] = a2
    d_matched, d_idx = cadd_join_kernel(vpos, vref, valt, spos, sref, salt)
    h_matched, h_idx = cadd_join_host(vpos, vref, valt, spos, sref, salt)
    np.testing.assert_array_equal(np.asarray(d_matched), h_matched)
    np.testing.assert_array_equal(np.asarray(d_idx), h_idx)
    assert h_matched[: n // 2].all()  # the planted hits actually hit


# ---------------------------------------------------------------------------
# dedup / membership


def _dup_batch(rng, n):
    pos, ref, alt, ref_len, alt_len = _allele_batch(rng, n)
    h = allele_hash_np(ref, alt, ref_len, alt_len)
    # plant exact duplicates (identical identity) and a (pos, h) collision
    # with different bytes (must NOT count as duplicate)
    for i in range(0, n - 8, 7):
        j = i + rng.integers(1, 6)
        pos[j] = pos[i]
        ref[j], alt[j] = ref[i], alt[i]
        ref_len[j], alt_len[j] = ref_len[i], alt_len[i]
        h[j] = h[i]
    return pos, h, ref, alt, ref_len, alt_len


def test_mark_batch_duplicates_vs_np_twin():
    rng = np.random.default_rng(19)
    pos, h, ref, alt, ref_len, alt_len = _dup_batch(rng, 128)
    dev = mark_batch_duplicates_jit(pos, h, ref, alt, ref_len, alt_len)
    host = mark_batch_duplicates_np(pos, h, ref, alt, ref_len, alt_len)
    np.testing.assert_array_equal(np.asarray(dev), host)
    assert host.any()  # the planted duplicates were seen


def test_mark_batch_duplicates_multi_vs_np_twin():
    rng = np.random.default_rng(23)
    pos, h, ref, alt, ref_len, alt_len = _dup_batch(rng, 128)
    chrom = rng.integers(1, 4, 128).astype(np.int32)
    dev = mark_batch_duplicates_multi_jit(
        chrom, pos, h, ref, alt, ref_len, alt_len
    )
    host = mark_batch_duplicates_multi_np(
        chrom, pos, h, ref, alt, ref_len, alt_len
    )
    np.testing.assert_array_equal(np.asarray(dev), host)


def _sorted_store(rng, m):
    pos, ref, alt, ref_len, alt_len = _allele_batch(rng, m)
    h = allele_hash_np(ref, alt, ref_len, alt_len)
    order = np.lexsort((h, pos))
    return (pos[order], h[order], ref[order], alt[order],
            ref_len[order], alt_len[order])


def test_lookup_in_sorted_vs_np_twin():
    rng = np.random.default_rng(29)
    spos, sh, sref, salt, srlen, salen = _sorted_store(rng, 256)
    n = 96
    qpos, qref, qalt, qrlen, qalen = _allele_batch(rng, n)
    qh = allele_hash_np(qref, qalt, qrlen, qalen)
    hit = rng.integers(0, 256, n // 2)
    qpos[: n // 2] = spos[hit]
    qh[: n // 2] = sh[hit]
    qref[: n // 2], qalt[: n // 2] = sref[hit], salt[hit]
    qrlen[: n // 2], qalen[: n // 2] = srlen[hit], salen[hit]
    dev = lookup_in_sorted_jit(
        spos, sh, sref, salt, srlen, salen,
        qpos, qh, qref, qalt, qrlen, qalen,
    )
    host = lookup_in_sorted_np(
        spos, sh, sref, salt, srlen, salen,
        qpos, qh, qref, qalt, qrlen, qalen,
    )
    np.testing.assert_array_equal(np.asarray(dev[0]), host[0])
    np.testing.assert_array_equal(np.asarray(dev[1]), host[1])
    assert host[0][: n // 2].all()


def test_lookup_in_sorted_packed_vs_np_twin():
    """The store's probe: the queries one packed buffer, the index alone
    back (every capacity and run shape: tests/test_probe_launch_collect)."""
    rng = np.random.default_rng(37)
    store = _sorted_store(rng, 256)
    n, cap = 40, 64
    qpos, qref, qalt, qrlen, qalen = _allele_batch(rng, n)
    qh = allele_hash_np(qref, qalt, qrlen, qalen)
    hit = rng.integers(0, 256, n // 2)
    query = [qpos, qh, qref, qalt, qrlen, qalen]
    for q, col in zip(query, store):
        q[: n // 2] = col[hit]
    index = np.asarray(lookup_in_sorted_packed_jit(
        *store, pack_queries(*query, cap)
    ))
    found, host = lookup_in_sorted_np(*store, *query)
    assert index.dtype == host.dtype and index.shape == (cap,)
    np.testing.assert_array_equal(index[:n], host)
    np.testing.assert_array_equal(index[:n] >= 0, found)
    assert found[: n // 2].all() and (index[n:] == -1).all()


def test_lookup_in_sorted_multi_vs_np_twin():
    rng = np.random.default_rng(31)
    spos, sh, sref, salt, srlen, salen = _sorted_store(rng, 256)
    schrom = rng.integers(1, 4, 256).astype(np.int32)
    shm = np.array(mix_chrom_hash(sh, schrom))
    order = np.lexsort((shm, spos))
    schrom, spos, shm = schrom[order], spos[order], shm[order]
    sref, salt = sref[order], salt[order]
    srlen, salen = srlen[order], salen[order]
    n = 96
    qpos, qref, qalt, qrlen, qalen = _allele_batch(rng, n)
    qchrom = rng.integers(1, 4, n).astype(np.int32)
    qhm = np.array(mix_chrom_hash(
        allele_hash_np(qref, qalt, qrlen, qalen), qchrom
    ))
    hit = rng.integers(0, 256, n // 2)
    qchrom[: n // 2] = schrom[hit]
    qpos[: n // 2] = spos[hit]
    qhm[: n // 2] = shm[hit]
    qref[: n // 2], qalt[: n // 2] = sref[hit], salt[hit]
    qrlen[: n // 2], qalen[: n // 2] = srlen[hit], salen[hit]
    dev = lookup_in_sorted_multi_jit(
        schrom, spos, shm, sref, salt, srlen, salen,
        qchrom, qpos, qhm, qref, qalt, qrlen, qalen,
    )
    host = lookup_in_sorted_multi_np(
        schrom, spos, shm, sref, salt, srlen, salen,
        qchrom, qpos, qhm, qref, qalt, qrlen, qalen,
    )
    np.testing.assert_array_equal(np.asarray(dev[0]), host[0])
    np.testing.assert_array_equal(np.asarray(dev[1]), host[1])


# ---------------------------------------------------------------------------
# hashing


def test_allele_hash_vs_np_twin():
    rng = np.random.default_rng(37)
    _pos, ref, alt, ref_len, alt_len = _allele_batch(rng, 512)
    dev = np.asarray(allele_hash_jit(ref, alt, ref_len, alt_len))
    host = allele_hash_np(ref, alt, ref_len, alt_len)
    assert dev.dtype == host.dtype == np.uint32
    np.testing.assert_array_equal(dev, host)


# ---------------------------------------------------------------------------
# intervals (BITS)


def test_bits_spans_kernel_vs_host_twin():
    rng = np.random.default_rng(41)
    m = 512
    pos = np.sort(rng.integers(1, 2_000_000, m)).astype(np.int32)
    q = 128
    starts = rng.integers(1, 2_000_000, q).astype(np.int32)
    ends = (starts + rng.integers(0, 50_000, q)).astype(np.int32)
    # raw kernel on already-clamped in-range inputs == host twin
    d_lo, d_hi, d_level, d_leaf = bits_spans_kernel_jit(pos, starts, ends)
    h_lo, h_hi, h_level, h_leaf = interval_spans_host(pos, starts, ends)
    np.testing.assert_array_equal(np.asarray(d_lo), h_lo)
    np.testing.assert_array_equal(np.asarray(d_hi), h_hi)
    np.testing.assert_array_equal(np.asarray(d_level), h_level)
    np.testing.assert_array_equal(np.asarray(d_leaf), h_leaf)
    assert int(POS_SENTINEL) > 2_000_000  # inputs stayed in-range


def _stats_columns(rng, m):
    pos = np.sort(rng.integers(1, 2_000_000, m)).astype(np.int32)
    af = rng.integers(STATS_MISSING, 1_000_001, m).astype(np.int32)
    cadd = rng.integers(STATS_MISSING, 100_001, m).astype(np.int32)
    rank = rng.integers(STATS_MISSING, 40, m).astype(np.int32)
    return pos, af, cadd, rank


def test_stats_panel_kernel_vs_host_twin():
    """The fused analytics panel: integer-only reductions, so the twin
    is byte-exact (the deeper battery lives in tests/test_stats.py)."""
    rng = np.random.default_rng(42)
    pos, af, cadd, rank = _stats_columns(rng, 512)
    q = 64
    starts = rng.integers(1, 2_000_000, q).astype(np.int32)
    ends = (starts + rng.integers(0, 50_000, q)).astype(np.int32)
    dev = stats_panel_kernel_jit(pos, af, cadd, rank, starts, ends)
    host = stats_panel_host(pos, af, cadd, rank, starts, ends)
    for d, h, name in zip(dev, host, ("lo", "hi", "af_lanes", "af_hist",
                                      "cadd_lanes", "cadd_hist", "ranks")):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(h),
                                      err_msg=name)


def test_windowed_stats_kernel_vs_host_twin():
    rng = np.random.default_rng(43)
    pos, _af, cadd, _rank = _stats_columns(rng, 509)
    q = 48
    starts = rng.integers(1, 2_000_000, q).astype(np.int32)
    ends = (starts + rng.integers(0, 50_000, q)).astype(np.int32)
    dev = windowed_stats_kernel_jit(pos, cadd, starts, ends, windows=6)
    host = windowed_stats_host(pos, cadd, starts, ends, 6)
    for d, h, name in zip(dev, host, ("counts", "present", "lanes")):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(h),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# mesh-sharded kernel surfaces (mesh_pjit): same twins, sharded compute.
# Each mesh surface is driven against ITS registered host twin on an
# odd-sized batch (forces the pad-and-slice path) over the live mesh
# (conftest forces an 8-virtual-device CPU backend).


def test_annotate_kernel_mesh_vs_np_twin():
    rng = np.random.default_rng(61)
    pos, ref, alt, ref_len, alt_len = _allele_batch(rng, 333)
    dev = annotate_kernel_mesh(pos, ref, alt, ref_len, alt_len)
    host = annotate_kernel_np(pos, ref, alt, ref_len, alt_len)
    assert set(dev) == set(host)
    for key in dev:
        np.testing.assert_array_equal(
            np.asarray(dev[key]), np.asarray(host[key]), err_msg=key
        )


def test_allele_hash_mesh_vs_np_twin():
    rng = np.random.default_rng(62)
    _pos, ref, alt, ref_len, alt_len = _allele_batch(rng, 301)
    dev = np.asarray(allele_hash_mesh(ref, alt, ref_len, alt_len))
    host = allele_hash_np(ref, alt, ref_len, alt_len)
    assert dev.dtype == host.dtype == np.uint32
    np.testing.assert_array_equal(dev, host)


def test_bin_index_kernel_mesh_vs_oracle_twin():
    rng = np.random.default_rng(63)
    starts = rng.integers(1, 200_000_000, 203).astype(np.int32)
    ends = (starts + rng.integers(0, 100_000, 203)).astype(np.int32)
    level, leaf = bin_index_kernel_mesh(starts, ends)
    level, leaf = np.asarray(level), np.asarray(leaf)
    for i in range(starts.shape[0]):
        want_level, want_leaf = closed_form_bin(int(starts[i]), int(ends[i]))
        assert (int(level[i]), int(leaf[i])) == (want_level, want_leaf)


def test_mark_batch_duplicates_mesh_vs_np_twin():
    rng = np.random.default_rng(64)
    pos, ref, alt, ref_len, alt_len = _allele_batch(rng, 229)
    # plant duplicate runs so the global sharded sort has real work
    pos[50:60] = pos[40]
    ref[50:60] = ref[40]
    alt[50:60] = alt[40]
    ref_len[50:60] = ref_len[40]
    alt_len[50:60] = alt_len[40]
    h = allele_hash_np(ref, alt, ref_len, alt_len)
    dev = np.asarray(
        mark_batch_duplicates_mesh(pos, h, ref, alt, ref_len, alt_len)
    )
    host = mark_batch_duplicates_np(pos, h, ref, alt, ref_len, alt_len)
    np.testing.assert_array_equal(dev, host)


def test_bits_spans_stacked_vs_host_twin():
    rng = np.random.default_rng(65)
    b, r, q = 8, 256, 32
    pos = np.sort(rng.integers(1, 2_000_000, (b, r)).astype(np.int32),
                  axis=1)
    pos[3, :] = POS_SENTINEL  # an empty (all-pad) group row
    starts = rng.integers(1, 2_000_000, (b, q)).astype(np.int32)
    ends = (starts + rng.integers(0, 50_000, (b, q))).astype(np.int32)
    dev = bits_spans_stacked_jit(pos, starts, ends)
    host = bits_spans_stacked_host(pos, starts, ends)
    for d, h, name in zip(dev, host, ("lo", "hi", "level", "leaf")):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(h),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# pack / transport


def test_pack_outputs_vs_np_twin():
    h = np.array([0x01020304, 0xFFFFFFFF, 0, 0xDEADBEEF], np.uint32)
    leaf = np.array([-1, 2**31 - 1, -(2**31), 1234], np.int32)
    level = np.array([0, 13, 7, 255], np.int32)
    t = np.array([True, False, True, False])
    dev = np.asarray(pack_outputs_jit(h, t, level, leaf, ~t, t))
    host = pack_outputs_np(h, t, level, leaf, ~t, t)
    np.testing.assert_array_equal(dev, host)
    # and the host-packed buffer unpacks exactly like the device one
    d_cols, h_cols = unpack_outputs(dev), unpack_outputs(host)
    for key in d_cols:
        np.testing.assert_array_equal(d_cols[key], h_cols[key], err_msg=key)


def test_inflate_alleles_vs_np_twin():
    probe = np.zeros((4, 7), np.uint8)
    probe[0, :5] = np.frombuffer(b"ACGTN", np.uint8)
    probe[1, :3] = np.frombuffer(b"acg", np.uint8)
    probe[2, :7] = np.frombuffer(b"*.-TGCA", np.uint8)
    probe[3, :1] = np.frombuffer(b"G", np.uint8)
    enc = encode_alleles_nibble(probe, probe[::-1].copy())
    assert enc is not None
    d_ref, d_alt = inflate_alleles_jit(enc[0], enc[1], 7)
    h_ref, h_alt = inflate_alleles_np(enc[0], enc[1], 7)
    np.testing.assert_array_equal(np.asarray(d_ref), h_ref)
    np.testing.assert_array_equal(np.asarray(d_alt), h_alt)
    np.testing.assert_array_equal(h_ref, probe)  # the round trip itself


def test_pack_vep_outputs_vs_np_twin():
    h = np.array([1, 0xCAFEBABE, 2**32 - 1], np.uint32)
    prefix = np.array([0, 3, 255], np.int32)
    fb = np.array([False, True, False])
    dev = np.asarray(pack_vep_outputs_jit(h, prefix, fb))
    host = pack_vep_outputs_np(h, prefix, fb)
    np.testing.assert_array_equal(dev, host)


def test_export_pack_vs_host_twin():
    """Corpus-export batch packing: elementwise int32/int8 arithmetic on
    both sides, so padded-lane masking and bin derivation are byte-exact
    (the corpus-level battery lives in tests/test_export.py)."""
    rng = np.random.RandomState(11)
    b, n_valid = 64, 41
    pos = np.sort(rng.randint(1, 3_000_000, b)).astype(np.int32)
    end = (pos + rng.randint(0, 8, b)).astype(np.int32)
    ref_code = rng.randint(0, 50, b).astype(np.int32)
    alt_code = rng.randint(0, 50, b).astype(np.int32)
    af_fp = rng.randint(-1, 10**6, b).astype(np.int32)
    cadd_fp = rng.randint(-1, 4000, b).astype(np.int32)
    rank_i = rng.randint(-1, 30, b).astype(np.int32)
    dev = export_pack_kernel_jit(pos, end, ref_code, alt_code, af_fp,
                                 cadd_fp, rank_i, n_valid)
    host = export_pack_host(pos, end, ref_code, alt_code, af_fp,
                            cadd_fp, rank_i, n_valid)
    names = ("mask", "bin_level", "leaf_bin", "pos", "ref_code",
             "alt_code", "af_fp", "cadd_fp", "rank_i")
    for d, h, name in zip(dev, host, names):
        d, h = np.asarray(d), np.asarray(h)
        assert d.dtype == h.dtype, name
        np.testing.assert_array_equal(d, h, err_msg=name)
    # padded lanes are uniformly dead on both sides
    assert not np.asarray(dev[0])[n_valid:].any()
    for lane in dev[1:]:
        assert (np.asarray(lane)[n_valid:] == -1).all()


# ---------------------------------------------------------------------------
# the registry stays audited by the static analyzer too


def test_static_rule_knows_these_kernels():
    """The analyzer's kernel discovery and this registry agree (a kernel
    added without a TWINS entry fails avdb_check as AVDB901; this pins
    the discovery side against the live tree)."""
    import os

    from annotatedvdb_tpu.analysis import run_paths
    from annotatedvdb_tpu.analysis.core import ProjectFacts, find_repo_root
    from annotatedvdb_tpu.analysis import rules_twins

    repo = find_repo_root(os.path.dirname(os.path.abspath(__file__)))
    ops_dir = os.path.join(repo, "annotatedvdb_tpu", "ops")
    findings, _n = run_paths([ops_dir], root=repo)
    assert [f for f in findings if f.code.startswith("AVDB9")] == [], [
        f.render() for f in findings
    ]
    # discovery sees exactly the registered kernels
    from annotatedvdb_tpu.analysis.core import FileContext, load_project

    facts = ProjectFacts()
    project = load_project(repo)
    for fn in sorted(os.listdir(ops_dir)):
        if fn.endswith(".py"):
            path = os.path.join(ops_dir, fn)
            with open(path, encoding="utf-8") as f:
                rules_twins.collect(
                    FileContext(path, f.read()), facts, project
                )
    discovered = {name for _p, _l, name in facts.ops_kernels}
    assert discovered == set(TWINS)
