"""Native identity columns: a lookup group's allele rows and hashes in one
pass.

Drives ``avdb_identity_columns`` (``native/avdb_native.cpp``): from a
group's ref strings joined with no padding and their lengths, and the same
for the alts, the ``[n, width]`` allele rows ``types.encode_allele_array``
gives and the identity hash ``loaders/lookup.py`` ``identity_hashes``
gives, over-width rows included.  ASCII bytes only: the caller keeps the
scalar route for anything else.
"""

from __future__ import annotations

import functools

import numpy as np

from annotatedvdb_tpu import native
from annotatedvdb_tpu.ops.hashing import FNV_PRIME


@functools.lru_cache(maxsize=None)
def _prime_powers(width: int) -> tuple[np.ndarray, int]:
    """prime^k for k in [0, width], wrapping at 32 bits — what the pass
    folds a row's zero padding with — and the table's address."""
    pp = np.empty(width + 1, np.uint32)
    pp[0] = 1
    with np.errstate(over="ignore"):
        for k in range(1, width + 1):
            pp[k] = pp[k - 1] * FNV_PRIME
    return pp, pp.ctypes.data


def identity_columns(ref_bytes: bytes, ref_len: np.ndarray,
                     alt_bytes: bytes, alt_len: np.ndarray, width: int
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray] | None:
    """``(ref, alt, h)`` for the rows whose alleles are ``ref_bytes`` and
    ``alt_bytes`` cut at the int32 lengths ``ref_len`` / ``alt_len`` (one
    a row, in row order).  None where the library is not loaded."""
    lib = native.load()
    if lib is None:
        return None
    n = int(ref_len.size)
    for lens in (ref_len, alt_len):
        if lens.shape != (n,) or lens.dtype != np.int32 \
                or not lens.flags.c_contiguous:
            raise ValueError("one contiguous int32 length a row")
    if width < 1:
        raise ValueError("a width of at least one byte")
    pp, pp_at = _prime_powers(width)
    # both allele matrices in one allocation; addresses are taken once
    # each (an array's ctypes view costs a microsecond or two a call)
    rows = np.empty((2, n, width), np.uint8)
    h = np.empty(n, np.uint32)
    rows_at = rows.ctypes.data
    if lib.avdb_identity_columns(
            ref_bytes, ref_len.ctypes.data, len(ref_bytes),
            alt_bytes, alt_len.ctypes.data, len(alt_bytes),
            n, width, pp_at, pp.size,
            rows_at, rows_at + n * width, h.ctypes.data) < 0:
        raise ValueError("allele lengths do not cut the joined bytes")
    return rows[0], rows[1], h
