"""Native-engine FREQ sidecar: a chunk's flagged rows' values in one pass.

Drives ``avdb_freq_texts`` (``native/avdb_native.cpp``): for each row, from
its INFO span in the scanner's window, the text ``io/vcf.py``
``freq_sidecar(info, n_alts)[alt_index]`` gives — written only where the
pass can prove the bytes equal, "no value" where that gives None, and
declined otherwise (the caller then asks ``freq_sidecar``, the definition
and the oracle).  ctypes releases the GIL for the call, so the store
writer's thread runs beside it.
"""

from __future__ import annotations

import ctypes
import threading

import numpy as np

from annotatedvdb_tpu import native

#: a row's outcome in the pass's status column
NONE, WRITTEN, DECLINED = 0, 1, 2

#: one text buffer a thread, grown to the largest chunk seen (as
#: ``native/mapping.py``'s line buffer)
_pool = threading.local()


def _text_buffer(cap: int) -> np.ndarray:
    buf = getattr(_pool, "buf", None)
    if buf is None or buf.size < cap:
        buf = _pool.buf = np.empty(cap + cap // 4 + 1, np.uint8)
    return buf


def _ptr(a: np.ndarray) -> ctypes.c_void_p:
    return a.ctypes.data_as(ctypes.c_void_p)


def freq_texts(window: bytes, info_off: np.ndarray, info_len: np.ndarray,
               n_alts: np.ndarray, alt_index: np.ndarray
               ) -> tuple[np.ndarray, list] | None:
    """``(status, texts)`` for the rows whose INFO is
    ``window[info_off[i]:info_off[i] + info_len[i]]`` (a length <= 0: no
    INFO, no value): one :data:`NONE` / :data:`WRITTEN` / :data:`DECLINED`
    a row, and the text of each written row, in row order.  None where the
    library is not loaded — every row is then the caller's."""
    lib = native.load()
    if lib is None:
        return None
    n = int(info_off.size)
    off = np.ascontiguousarray(info_off, np.int64)
    length = np.ascontiguousarray(info_len, np.int32)
    alts = np.ascontiguousarray(n_alts, np.int32)
    ordinal = np.ascontiguousarray(alt_index, np.int32)
    if not off.shape == length.shape == alts.shape == ordinal.shape == (n,):
        raise ValueError("one INFO span, alt count and ordinal a row")
    held = length > 0
    if (off[held] < 0).any() or (
            off[held] + length[held] > len(window)).any():
        raise ValueError("an INFO span outside the window")
    # the C side's own bound a row: 3 + 18 * (info_len + 1)
    cap = int(np.sum(3 + 18 * (length[held].astype(np.int64) + 1)))
    out = _text_buffer(cap)
    status = np.empty(n, np.uint8)
    total = lib.avdb_freq_texts(window, n, _ptr(off), _ptr(length),
                                _ptr(alts), _ptr(ordinal), _ptr(status),
                                _ptr(out), cap)
    if total < 0:  # the bound above is the C side's own: unreachable
        raise RuntimeError("FREQ text buffer too small")
    texts = out[:total].tobytes().decode("ascii").split("\n")
    texts.pop()  # after the last newline
    return status, texts
