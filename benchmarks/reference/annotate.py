"""The plain reference for what a load computes per row.

Scalar definitions copied from the program's oracle
(``annotatedvdb_tpu/oracle/annotator.py``: ``normalize_alleles``,
``infer_end_location``; ``oracle/binindex.py``: ``closed_form_bin``,
``closed_form_path``) and the identity hash as ``ops/hashing`` documents it
(32-bit FNV-1a), as they stood when the benchmark was defined.  Imports
nothing of the program.

:func:`reference_columns` applies the scalar definitions to every row: the
end location is ``pos`` plus an offset that depends on (ref, alt) alone, so
the scalar function runs once per distinct allele pair and numpy does the
rest.
"""

from __future__ import annotations

import numpy as np

LEAF_SIZE = 15_625  # the level-13 bin of the 14-level bin tree

#: INFO FREQ population the generator writes
FREQ_POPULATION = "GnomAD"


def _leading_match_len(ref: str, alt: str) -> int:
    n = 0
    for i in range(len(ref)):
        if i < len(alt) and ref[i] == alt[i]:
            n += 1
        else:
            break
    return n


def normalize_alleles(ref: str, alt: str) -> tuple[str, str]:
    """Left-normalize a ref/alt pair (1bp/1bp SNVs untouched)."""
    if len(ref) == 1 and len(alt) == 1:
        return ref, alt
    p = _leading_match_len(ref, alt)
    if p == 0:
        return ref, alt
    return ref[p:], alt[p:]


def infer_end_location(ref: str, alt: str, pos: int) -> int:
    """dbSNP-convention end location per variant shape."""
    pos = int(pos)
    r_len, a_len = len(ref), len(alt)
    norm_ref, norm_alt = normalize_alleles(ref, alt)
    nr, na = len(norm_ref), len(norm_alt)
    if r_len == 1 and a_len == 1:  # SNV
        return pos
    if r_len == a_len:  # MNV
        if ref == alt[::-1]:  # inversion
            return pos + r_len - 1
        return pos + nr - 1  # substitution
    if na >= 1:  # insertion side
        if nr >= 1:  # indel
            return pos + nr
        if r_len > 1:  # pure insertion anchored left of the event
            return pos + r_len - 1
        return pos + 1
    if nr == 0:  # deletion side
        return pos + r_len - 1
    return pos + nr


def closed_form_bin(start: int, end: int) -> tuple[int, int]:
    """(level, leaf bin) of the deepest ``(lower, upper]`` bin that holds
    the whole interval."""
    a = (start - 1) // LEAF_SIZE
    b = (end - 1) // LEAF_SIZE
    level = 13 - min(13, (a ^ b).bit_length())
    return level, a


def closed_form_path(chrom_label: str, level: int, leaf_bin: int) -> str:
    """ltree path of a (level, leaf bin) pair."""
    parts = [chrom_label]
    for l in range(1, level + 1):
        g = leaf_bin >> (13 - l)
        b = g + 1 if l == 1 else (g & 1) + 1
        parts.append(f"L{l}.B{b}")
    return ".".join(parts)


def fnv1a(ref_len, alt_len, ref, alt) -> np.ndarray:
    """32-bit FNV-1a over (ref_len, alt_len, the zero-padded ref bytes, the
    zero-padded alt bytes)."""
    h = np.full(ref.shape[0], 2166136261, np.uint32)
    for byte in [ref_len & 0xFF, alt_len & 0xFF, *ref.T, *alt.T]:
        h = (h ^ byte.astype(np.uint32)) * np.uint32(16777619)
    return h


def padded(alleles: np.ndarray, width: int) -> np.ndarray:
    """``S8`` alleles -> [n, width] uint8, zero-padded."""
    out = np.zeros((alleles.shape[0], width), np.uint8)
    out[:, :8] = np.frombuffer(alleles.tobytes(), np.uint8).reshape(-1, 8)
    return out


def reference_columns(pos, ref, alt, width: int) -> dict:
    """Every computed column of the given rows (``S8`` alleles), by the
    scalar definitions above: identity hash, allele lengths, bin level,
    leaf bin, digest flag, padded allele matrices."""
    ref_len = np.char.str_len(ref).astype(np.int32)
    alt_len = np.char.str_len(alt).astype(np.int32)
    ref_m, alt_m = padded(ref, width), padded(alt, width)
    pairs, inverse = np.unique(
        np.rec.fromarrays([ref, alt]), return_inverse=True
    )
    offset = np.array([
        infer_end_location(r.decode(), a.decode(), 0)
        for r, a in pairs.tolist()
    ], np.int64)
    start = pos.astype(np.int64)
    end = start + offset[inverse]
    a = (start - 1) // LEAF_SIZE
    b = (end - 1) // LEAF_SIZE
    x = a ^ b
    bits = np.zeros(x.shape, np.int64)  # bit_length, elementwise
    nz = x > 0
    bits[nz] = np.floor(np.log2(x[nz])).astype(np.int64) + 1
    return {
        "h": fnv1a(ref_len, alt_len, ref_m, alt_m),
        "ref_len": ref_len, "alt_len": alt_len,
        "bin_level": 13 - np.minimum(13, bits), "leaf_bin": a,
        "needs_digest": (ref_len + alt_len) > 50,
        "ref": ref_m, "alt": alt_m,
    }
