"""Pallas TPU kernel: fused annotate + bin-index in one VMEM pass.

Single-kernel fusion of the whole per-variant math the reference spreads over
``VariantAnnotator`` (``Util/lib/python/variant_annotator.py:36-241``) and the
``find_bin_index()`` Postgres round-trip
(``BinIndex/lib/python/bin_index.py:43-75``): left-normalization, inversion
test, duplication-motif test, end location, variant class, and the closed-form
hierarchical bin — one HBM read of the allele arrays, one HBM write of the
per-row outputs.

TPU-native design notes (vs. the jnp kernel in ``ops/annotate.py``):

- **Transposed layout.** Alleles are processed as ``[W, N]`` (width on
  sublanes, variants on lanes) so every per-variant scalar is a ``[1, N]``
  row broadcast and every width-axis scan is a static sublane slice.  The
  lane dimension is the big, 128-aligned batch dimension.
- **Gather-free.** The jnp kernel uses ``take_along_axis`` (dynamic lane
  gathers) for the inversion reverse and the duplication modular gather;
  Mosaic has no efficient dynamic cross-lane gather.  Here both tests are
  reformulated as *static-shift correlation scans*: compute the predicate at
  every static shift/period (a ``[W-s, N]`` compare + masked reduce, W
  unrolled steps) and select the per-row answer with a one-hot reduction
  over shifts.  O(W^2) lane-ops total, all static slices.
- **No wide booleans, no division.** Width-axis predicates are int32 0/1
  arithmetic (``sign``/``clip``) reduced by sums — Mosaic's vector layouts
  reject wide i1 relayouts.  Divisibility (``orig_len % period == 0``) is
  ``OR_m (m * period == orig_len)``.  The allele reversal is an MXU matmul
  against a constant reversal permutation (exact in f32 for byte values).
- **Packed scalar I/O.** Per-variant scalars ride as rows of one
  ``[8, N]`` int32 array in each direction (position/lengths in, the eight
  per-row outputs out), sidestepping Mosaic's (1, N)-block corner cases.

Outputs match ``annotate_kernel`` + ``bin_index_kernel`` bit-for-bit for all
rows not flagged ``host_fallback`` (parity-tested in
``tests/test_annotate_pallas.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from annotatedvdb_tpu.ops.binindex import LEAF_SIZE, NUM_BIN_LEVELS
from annotatedvdb_tpu.types import MAX_PK_SEQUENCE_LENGTH, VariantClass

# lanes per grid step; must be a multiple of 128
DEFAULT_BLOCK_N = 1024

# rows of the packed scalar input
_ROW_POS, _ROW_RLEN, _ROW_ALEN = 0, 1, 2
# rows of the packed output
(_OUT_PREFIX, _OUT_END, _OUT_CLS, _OUT_DUP,
 _OUT_LEVEL, _OUT_LEAF, _OUT_DIGEST, _OUT_FALLBACK) = range(8)


def _kernel(meta_ref, ref_ref, alt_ref, rev_ref, out_ref, *, w: int):
    meta = meta_ref[:, :]                    # [8, N] int32
    pos = meta[_ROW_POS:_ROW_POS + 1, :]     # [1, N]
    rlen = meta[_ROW_RLEN:_ROW_RLEN + 1, :]
    alen = meta[_ROW_ALEN:_ROW_ALEN + 1, :]
    refi = ref_ref[:, :].astype(jnp.int32)   # [W, N]
    alti = alt_ref[:, :].astype(jnp.int32)
    n = pos.shape[1]

    row = jax.lax.broadcasted_iota(jnp.int32, (w, n), dimension=0)

    snv = (rlen == 1) & (alen == 1)
    mnv_shape = (rlen == alen) & ~snv

    in_ref = jnp.clip(rlen - row, 0, 1)      # [W, N] 1 where i < rlen
    in_alt = jnp.clip(alen - row, 0, 1)

    def neq(a, b):
        return jnp.sign(jnp.abs(a - b))      # int32 0/1

    # ---- left-normalization (variant_annotator.py:100-107): length of the
    # shared leading run, via an unrolled running-AND over width rows.
    match = (1 - neq(refi, alti)) * in_ref * in_alt
    run = jnp.ones((1, n), dtype=jnp.int32)
    prefix = jnp.zeros((1, n), dtype=jnp.int32)
    for i in range(w):
        run = run * match[i:i + 1, :]
        prefix = prefix + run
    prefix = jnp.where(snv, 0, prefix)
    nr = rlen - prefix
    na = alen - prefix

    # ---- inversion: ref == reverse(alt) for equal-length alleles.
    # alt_rev[i] = alt[w-1-i] via the precomputed MXU reversal matmul; the
    # length-L reverse sits at sublane offset s = w - L, so test every
    # static offset and one-hot select s == w - rlen.
    alt_rev = jnp.dot(
        rev_ref[:, :], alti.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    inversion = jnp.zeros((1, n), dtype=jnp.bool_)
    for s in range(w):
        m = w - s
        # mismatch at a position that is inside the allele (i < rlen)
        bad = neq(refi[:m, :], alt_rev[s:, :]) * in_ref[:m, :]
        ok = jnp.sum(bad, axis=0, keepdims=True) == 0
        inversion = inversion | (ok & (rlen == m))
    inversion = inversion & mnv_shape

    # ---- end location (variant_annotator.py:36-79)
    end_mnv = jnp.where(inversion, pos + rlen - 1, pos + nr - 1)
    end_ins = jnp.where(
        nr >= 1,
        pos + nr,
        jnp.where((nr == 0) & (rlen > 1), pos + rlen - 1, pos + 1),
    )
    end_del = jnp.where(nr == 0, pos + rlen - 1, pos + nr)
    end = jnp.where(
        snv,
        pos,
        jnp.where(mnv_shape, end_mnv, jnp.where(na >= 1, end_ins, end_del)),
    )

    # ---- duplication-motif test (variant_annotator.py:197-201):
    # ref[1:] is whole copies of the inserted motif alt[prefix:prefix+na].
    # Decomposed gather-free: (a) first copy matches at lag prefix — every
    # prefix in [0, w) is tested, INCLUDING 0: deletion-shaped rows like
    # AC->C have prefix == 0 yet tile (the reference kernel agrees; the
    # twin parity suite caught the lag-0 case missing here),
    # (b) ref[1:] is periodic with period na, (c) na divides rlen - 1.
    orig_len = rlen - 1
    # masks are precomputed full-width and sliced per shift — building fresh
    # [m, N] clip masks from the computed na inside the loop trips a Mosaic
    # layout bug (array.h "limits[i] <= dim(i)" abort)
    in_na = jnp.clip(na - row, 0, 1)                 # [W, N] 1 where i < na
    first_ok = jnp.zeros((1, n), dtype=jnp.bool_)
    for lo in range(w):
        m = min(w - lo, w - 1)
        bad = neq(refi[1:1 + m, :], alti[lo:lo + m, :]) * in_na[:m, :]
        ok = jnp.sum(bad, axis=0, keepdims=True) == 0
        first_ok = first_ok | (ok & (prefix == lo))
    periodic = jnp.zeros((1, n), dtype=jnp.bool_)
    for p in range(1, w):
        m = w - 1 - p
        if m <= 0:
            ok = jnp.ones((1, n), dtype=jnp.bool_)
        else:
            # position k = 1 + p + i must satisfy k < rlen, i.e. in_ref[k]
            bad = neq(refi[1 + p:1 + p + m, :], refi[1:1 + m, :]) * in_ref[1 + p:1 + p + m, :]
            ok = jnp.sum(bad, axis=0, keepdims=True) == 0
        periodic = periodic | (ok & (na == p))
    divisible = jnp.zeros((1, n), dtype=jnp.bool_)
    for mlt in range(1, w + 1):
        divisible = divisible | (mlt * na == orig_len)
    is_dup = (orig_len > 0) & (na > 0) & divisible & first_ok & periodic

    # ---- class codes (variant_annotator.py:134-241 branch structure)
    ins_side = ~snv & ~mnv_shape & (na >= 1)
    pure_ins = ins_side & (nr == 0) & (end == pos + 1)
    cls = jnp.where(
        snv, jnp.int32(VariantClass.SNV),
        jnp.where(
            inversion, jnp.int32(VariantClass.INVERSION),
            jnp.where(
                mnv_shape, jnp.int32(VariantClass.MNV),
                jnp.where(
                    ins_side & ~pure_ins, jnp.int32(VariantClass.INDEL),
                    jnp.where(
                        pure_ins & is_dup, jnp.int32(VariantClass.DUP),
                        jnp.where(
                            pure_ins, jnp.int32(VariantClass.INS),
                            jnp.int32(VariantClass.DEL),
                        ),
                    ),
                ),
            ),
        ),
    )

    # ---- closed-form bin index (ops/binindex.py) on [pos, end]
    a = (pos - 1) // LEAF_SIZE
    b = (end - 1) // LEAF_SIZE
    x = a ^ b
    mism = jnp.zeros((1, n), dtype=jnp.int32)
    for k in range(NUM_BIN_LEVELS):
        mism = mism + ((x >> k) != 0).astype(jnp.int32)
    level = NUM_BIN_LEVELS - mism

    out = jnp.concatenate(
        [
            prefix,
            end,
            cls,
            (is_dup & ins_side).astype(jnp.int32),
            level,
            a,
            ((rlen + alen) > MAX_PK_SEQUENCE_LENGTH).astype(jnp.int32),
            ((rlen > w) | (alen > w)).astype(jnp.int32),
        ],
        axis=0,
    )
    out_ref[:, :] = out


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def annotate_bin_pallas(pos, ref, alt, ref_len, alt_len,
                        block_n: int = DEFAULT_BLOCK_N, interpret: bool = False):
    """Fused annotate + bin-index via the Pallas kernel.

    Same inputs as :func:`annotate_kernel` ([N] scalars, [N, W] uint8
    alleles); returns the :func:`annotate_kernel` dict plus ``bin_level`` /
    ``leaf_bin``.  ``interpret=True`` runs the Mosaic interpreter (CPU
    parity tests)."""
    n, w = ref.shape
    n_pad = -(-n // block_n) * block_n
    pad = n_pad - n

    meta = jnp.zeros((8, n_pad), dtype=jnp.int32)
    # pad lanes look like 1bp SNVs at position 1 so no scan sees garbage
    meta = meta.at[_ROW_POS, :].set(1).at[_ROW_RLEN, :].set(1).at[_ROW_ALEN, :].set(1)
    meta = meta.at[_ROW_POS, :n].set(pos.astype(jnp.int32))
    meta = meta.at[_ROW_RLEN, :n].set(ref_len.astype(jnp.int32))
    meta = meta.at[_ROW_ALEN, :n].set(alt_len.astype(jnp.int32))
    refT = jnp.pad(ref, ((0, pad), (0, 0))).T     # [W, N_pad]
    altT = jnp.pad(alt, ((0, pad), (0, 0))).T
    rev = jnp.asarray(np.eye(w, dtype=np.float32)[::-1])  # reversal permutation

    grid = (n_pad // block_n,)
    outs = pl.pallas_call(
        functools.partial(_kernel, w=w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8, block_n), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((w, block_n), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((w, block_n), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((w, w), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((8, block_n), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8, n_pad), jnp.int32),
        interpret=interpret,
        name="avdb_annotate_bin",
    )(meta, refT, altT, rev)

    prefix = outs[_OUT_PREFIX, :n]
    end = outs[_OUT_END, :n]
    cls = outs[_OUT_CLS, :n]
    rlen = ref_len.astype(jnp.int32)
    alen = alt_len.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    return {
        "prefix_len": prefix,
        "norm_ref_len": rlen - prefix,
        "norm_alt_len": alen - prefix,
        "end_location": end,
        "location_start": jnp.where(cls >= VariantClass.INS, pos + 1, pos).astype(jnp.int32),
        "location_end": end,
        "variant_class": cls.astype(jnp.int8),
        "is_dup_motif": outs[_OUT_DUP, :n].astype(bool),
        "bin_level": outs[_OUT_LEVEL, :n].astype(jnp.int8),
        "leaf_bin": outs[_OUT_LEAF, :n],
        "needs_digest": outs[_OUT_DIGEST, :n].astype(bool),
        "host_fallback": outs[_OUT_FALLBACK, :n].astype(bool),
    }
