"""Core annotate kernel: left-normalization, end location, variant class.

The reference computes these per variant with Python string slicing
(``Util/lib/python/variant_annotator.py:36-241``).  Here the whole batch is
one branchless XLA program over [N, W] uint8 allele arrays:

- the shared-prefix length is a cumulative-AND scan over the width axis;
- the inversion test is a masked gather of the reversed alt;
- the duplication-motif test is a modular gather comparing ref[1:] against
  whole copies of the inserted motif;
- end location / display positions / class codes are ``jnp.where`` cascades
  reproducing the reference's branch structure exactly.

Everything is elementwise or a small gather along the width axis — XLA fuses
the whole kernel into a few HBM-bandwidth-bound loops, which is what makes
the >=1M variants/sec/chip target (BASELINE.md) reachable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from annotatedvdb_tpu.parallel.mesh import mesh_pjit
from annotatedvdb_tpu.types import MAX_PK_SEQUENCE_LENGTH, VariantClass


@jax.named_scope("avdb.annotate")
def annotate_kernel(pos, ref, alt, ref_len, alt_len):
    """Annotate one batch.

    Args:
      pos:     [N] int32 1-based positions
      ref/alt: [N, W] uint8 raw ASCII alleles (pad 0)
      ref_len/alt_len: [N] int32 true lengths (may exceed W; such rows are
        flagged ``host_fallback`` and their outputs are undefined)

    Returns a dict of [N] arrays: prefix_len, norm_ref_len, norm_alt_len,
    end_location, location_start, location_end, variant_class, is_dup_motif,
    needs_digest, host_fallback.
    """
    n, w = ref.shape
    pos = pos.astype(jnp.int32)
    rlen = ref_len.astype(jnp.int32)
    alen = alt_len.astype(jnp.int32)
    col = jnp.arange(w, dtype=jnp.int32)[None, :]            # [1, W]

    ref_valid = col < rlen[:, None]
    alt_valid = col < alen[:, None]

    snv = (rlen == 1) & (alen == 1)
    mnv_shape = (rlen == alen) & ~snv

    # ---- left-normalization: shared leading run (variant_annotator.py:100-107)
    # scan ref positions; alt running out counts as mismatch.
    match = (ref == alt) & ref_valid & alt_valid
    prefix = jnp.sum(jnp.cumsum(~match, axis=1) == 0, axis=1).astype(jnp.int32)
    prefix = jnp.where(snv, 0, prefix)                        # SNVs untouched
    nr = rlen - prefix
    na = alen - prefix

    # ---- inversion: ref == reverse(alt) for equal-length alleles
    rev_idx = jnp.clip(alen[:, None] - 1 - col, 0, w - 1)
    rev_alt = jnp.take_along_axis(alt, rev_idx, axis=1)
    inversion = mnv_shape & jnp.all((ref == rev_alt) | ~ref_valid, axis=1)

    # ---- end location (variant_annotator.py:36-79)
    end_mnv = jnp.where(inversion, pos + rlen - 1, pos + nr - 1)
    end_ins = jnp.where(
        nr >= 1,
        pos + nr,                                             # indel
        jnp.where((nr == 0) & (rlen > 1), pos + rlen - 1, pos + 1),
    )
    end_del = jnp.where(nr == 0, pos + rlen - 1, pos + nr)
    end = jnp.where(
        snv,
        pos,
        jnp.where(mnv_shape, end_mnv, jnp.where(na >= 1, end_ins, end_del)),
    ).astype(jnp.int32)

    # ---- duplication-motif test (variant_annotator.py:197-201):
    # ref[1:] must be whole copies of the inserted motif alt[prefix:].
    # Implemented as exact tiling: (rlen-1) % na == 0 and
    # ref[1+i] == alt[prefix + (i % na)] for all i < rlen-1.
    orig_len = rlen - 1                                       # len(ref[1:])
    na_safe = jnp.maximum(na, 1)
    motif_idx = jnp.clip(prefix[:, None] + (col % na_safe[:, None]), 0, w - 1)
    motif = jnp.take_along_axis(alt, motif_idx, axis=1)       # tiled inserted motif
    shifted_ref = jnp.concatenate([ref[:, 1:], jnp.zeros((n, 1), jnp.uint8)], axis=1)
    tile_cols = col < orig_len[:, None]
    tiles = jnp.all((shifted_ref == motif) | ~tile_cols, axis=1)
    is_dup = (
        (orig_len > 0)
        & (na > 0)
        & (jnp.remainder(orig_len, na_safe) == 0)
        & tiles
    )

    # ---- class codes (variant_annotator.py:134-241 branch structure)
    ins_side = ~snv & ~mnv_shape & (na >= 1)
    pure_ins = ins_side & (nr == 0) & (end == pos + 1)
    cls = jnp.select(
        [
            snv,
            inversion,
            mnv_shape,
            ins_side & ~pure_ins,
            pure_ins & is_dup,
            pure_ins,
        ],
        [
            jnp.int8(VariantClass.SNV),
            jnp.int8(VariantClass.INVERSION),
            jnp.int8(VariantClass.MNV),
            jnp.int8(VariantClass.INDEL),
            jnp.int8(VariantClass.DUP),
            jnp.int8(VariantClass.INS),
        ],
        default=jnp.int8(VariantClass.DEL),
    )

    # display positions: SNV/MNV anchor at pos; ins/dup/indel/del start at pos+1
    loc_start = jnp.where(cls >= VariantClass.INS, pos + 1, pos).astype(jnp.int32)
    loc_end = end

    return {
        "prefix_len": prefix,
        "norm_ref_len": nr,
        "norm_alt_len": na,
        "end_location": end,
        "location_start": loc_start,
        "location_end": loc_end,
        "variant_class": cls,
        "is_dup_motif": is_dup & ins_side,
        "needs_digest": (rlen + alen) > MAX_PK_SEQUENCE_LENGTH,
        "host_fallback": (rlen > w) | (alen > w),
    }


annotate_kernel_jit = jax.jit(annotate_kernel)


# the sharded-call surface (pjit with batch-dim-sharded inputs): pad rows
# carry sentinel positions + 1-base lengths (the _pad_batch fill) and are
# sliced away; on a single device this IS annotate_kernel_jit.  The
# registered host twin stays annotate_kernel_np (ops.TWINS).
annotate_kernel_mesh = mesh_pjit(
    annotate_kernel_jit, ("sentinel", "zero", "zero", "one", "one")
)


def annotate_kernel_np(pos, ref, alt, ref_len, alt_len):
    """Full numpy twin of :func:`annotate_kernel` — the registered host
    fallback (``ops.TWINS``), bit-exact field for field on in-width rows
    (over-width rows are ``host_fallback`` on both sides and their other
    outputs are undefined by contract).  Parity is pinned by
    ``tests/test_twins.py``; the scalar string oracle
    (``oracle.annotator``) remains the independent truth both are tested
    against."""
    import numpy as _np

    pos = _np.asarray(pos, _np.int32)
    ref = _np.asarray(ref, _np.uint8)
    alt = _np.asarray(alt, _np.uint8)
    rlen = _np.asarray(ref_len, _np.int32)
    alen = _np.asarray(alt_len, _np.int32)
    n, w = ref.shape
    col = _np.arange(w, dtype=_np.int32)[None, :]

    ref_valid = col < rlen[:, None]
    alt_valid = col < alen[:, None]
    snv = (rlen == 1) & (alen == 1)
    mnv_shape = (rlen == alen) & ~snv

    match = (ref == alt) & ref_valid & alt_valid
    prefix = (_np.cumsum(~match, axis=1) == 0).sum(axis=1).astype(_np.int32)
    prefix = _np.where(snv, 0, prefix).astype(_np.int32)
    nr = (rlen - prefix).astype(_np.int32)
    na = (alen - prefix).astype(_np.int32)

    rev_idx = _np.clip(alen[:, None] - 1 - col, 0, w - 1)
    rev_alt = _np.take_along_axis(alt, rev_idx, axis=1)
    inversion = mnv_shape & ((ref == rev_alt) | ~ref_valid).all(axis=1)

    end_mnv = _np.where(inversion, pos + rlen - 1, pos + nr - 1)
    end_ins = _np.where(
        nr >= 1,
        pos + nr,
        _np.where((nr == 0) & (rlen > 1), pos + rlen - 1, pos + 1),
    )
    end_del = _np.where(nr == 0, pos + rlen - 1, pos + nr)
    end = _np.where(
        snv,
        pos,
        _np.where(mnv_shape, end_mnv,
                  _np.where(na >= 1, end_ins, end_del)),
    ).astype(_np.int32)

    orig_len = rlen - 1
    na_safe = _np.maximum(na, 1)
    motif_idx = _np.clip(
        prefix[:, None] + (col % na_safe[:, None]), 0, w - 1
    )
    motif = _np.take_along_axis(alt, motif_idx, axis=1)
    shifted_ref = _np.concatenate(
        [ref[:, 1:], _np.zeros((n, 1), _np.uint8)], axis=1
    )
    tile_cols = col < orig_len[:, None]
    tiles = ((shifted_ref == motif) | ~tile_cols).all(axis=1)
    is_dup = (
        (orig_len > 0)
        & (na > 0)
        & (_np.remainder(orig_len, na_safe) == 0)
        & tiles
    )

    ins_side = ~snv & ~mnv_shape & (na >= 1)
    pure_ins = ins_side & (nr == 0) & (end == pos + 1)
    cls = _np.select(
        [
            snv,
            inversion,
            mnv_shape,
            ins_side & ~pure_ins,
            pure_ins & is_dup,
            pure_ins,
        ],
        [
            _np.int8(VariantClass.SNV),
            _np.int8(VariantClass.INVERSION),
            _np.int8(VariantClass.MNV),
            _np.int8(VariantClass.INDEL),
            _np.int8(VariantClass.DUP),
            _np.int8(VariantClass.INS),
        ],
        default=_np.int8(VariantClass.DEL),
    ).astype(_np.int8)

    loc_start = _np.where(
        cls >= VariantClass.INS, pos + 1, pos
    ).astype(_np.int32)

    return {
        "prefix_len": prefix,
        "norm_ref_len": nr,
        "norm_alt_len": na,
        "end_location": end,
        "location_start": loc_start,
        "location_end": end,
        "variant_class": cls,
        "is_dup_motif": is_dup & ins_side,
        "needs_digest": (rlen + alen) > MAX_PK_SEQUENCE_LENGTH,
        "host_fallback": (rlen > w) | (alen > w),
    }


def vep_identity_np(ref, alt, ref_len, alt_len):
    """Host-side twin of the two annotate outputs the VEP update path
    consumes: ``(prefix_len, host_fallback)``, bit-exact with
    :func:`annotate_kernel` (parity pinned by ``tests/test_pack.py``).
    The path's third input, the allele hash, comes from
    ``ops.hashing.allele_hash_np``.

    Where the measured upload rate is below the store's
    ``DEVICE_MIN_BANDWIDTH`` the update loader runs this instead of the
    device round trip; see ``loaders/vep_loader.py``."""
    import numpy as _np

    ref = _np.asarray(ref, _np.uint8)
    alt = _np.asarray(alt, _np.uint8)
    rlen = _np.asarray(ref_len, _np.int32)
    alen = _np.asarray(alt_len, _np.int32)
    w = ref.shape[1]
    col = _np.arange(w, dtype=_np.int32)[None, :]
    match = (ref == alt) & (col < rlen[:, None]) & (col < alen[:, None])
    prefix = (_np.cumsum(~match, axis=1) == 0).sum(axis=1).astype(_np.int32)
    prefix = _np.where((rlen == 1) & (alen == 1), 0, prefix)
    host_fallback = (rlen > w) | (alen > w)
    return prefix, host_fallback
