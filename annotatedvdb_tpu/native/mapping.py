"""Native-engine mapping sidecar: a chunk's lines from its columns.

Drives ``avdb_mapping_fast_rows`` / ``avdb_mapping_lines``
(``native/avdb_native.cpp``): one pass that writes, for every row whose
line is a function of the chunk's columns alone,

    {"<chr>:<pos>:<ref>:<alt>": [{"primary_key": "<id>[:rs<N>]", "bin_index": "<path>"}]}

as bytes, and copies every other row's line — rendered by the scalar route
(``io/egress.py`` ``mapping_lines``), the definition and the oracle — into
its place in row order.  ctypes releases the GIL for both calls, so the
store writer's thread runs beside them.
"""

from __future__ import annotations

import ctypes
import threading

import numpy as np

from annotatedvdb_tpu import native
from annotatedvdb_tpu.types import VariantBatch

#: bytes of a fast row's line beside its alleles and its bin path: 45 of
#: punctuation, two ids of a 2-byte label, a 10-digit position and three
#: colons, ":rs" and 19 digits (the bound ``avdb_mapping_lines`` checks)
_ROW_BOUND = 45 + 2 * 15 + 22


#: one line buffer a thread, grown to the largest chunk seen: a fresh
#: ~20 MB allocation a chunk is first-touch page faults on every page
_pool = threading.local()


def _line_buffer(cap: int) -> np.ndarray:
    buf = getattr(_pool, "buf", None)
    if buf is None or buf.size < cap:
        buf = _pool.buf = np.empty(cap + cap // 4 + 1, np.uint8)
    return buf


def _ptr(a: np.ndarray) -> ctypes.c_void_p:
    return a.ctypes.data_as(ctypes.c_void_p)


def _rows(batch: VariantBatch) -> tuple[list, tuple]:
    """The shared leading arguments of both calls, and the arrays they
    point into (kept alive by the caller for the call's duration)."""
    n, width = batch.n, batch.width
    if (batch.ref.shape != (n, width) or batch.alt.shape != (n, width)
            or any(x.shape != (n,) for x in (batch.chrom, batch.pos,
                                             batch.ref_len, batch.alt_len))):
        raise ValueError("a batch whose columns disagree on their shape")
    held = (
        np.ascontiguousarray(batch.chrom, np.int8),
        np.ascontiguousarray(batch.pos, np.int32),
        np.ascontiguousarray(batch.ref, np.uint8),
        np.ascontiguousarray(batch.alt, np.uint8),
        np.ascontiguousarray(batch.ref_len, np.int32),
        np.ascontiguousarray(batch.alt_len, np.int32),
    )
    return [batch.n, batch.width, *map(_ptr, held)], held


def fast_rows(batch: VariantBatch, candidates: np.ndarray) -> np.ndarray | None:
    """The rows of ``candidates`` (bool, one a row: what the caller's flag
    columns leave) that the native pass can write: chromosome code 1..25,
    a position >= 0, both alleles within the batch's width, zero-padded,
    and made of bytes a JSON string carries verbatim.  None where the
    library is not loaded — every row is then the scalar route's."""
    lib = native.load()
    if lib is None:
        return None
    fast = np.array(candidates, np.uint8)  # a private copy: C clears in place
    if fast.shape != (batch.n,):
        raise ValueError("one candidate flag a row")
    args, _held = _rows(batch)
    lib.avdb_mapping_fast_rows(*args, _ptr(fast))
    return fast.view(np.bool_)


def mapping_lines(batch: VariantBatch, rs_number: np.ndarray,
                  path_idx: np.ndarray, paths: list, fast: np.ndarray,
                  slow_lines: list) -> np.ndarray:
    """The chunk's mapping lines as one uint8 array in row order: a view
    into this thread's pooled buffer, valid until the thread's next call
    (write it out, or copy it, first).

    ``fast`` is :func:`fast_rows`' mask; ``paths`` the chunk's distinct
    bin paths and ``path_idx`` each row's index into them; ``slow_lines``
    the ASCII line (no newline) of every row that is not fast, in row
    order."""
    lib = native.load()
    n = batch.n
    fast = np.ascontiguousarray(fast, np.bool_)
    n_fast = int(np.count_nonzero(fast))
    if len(slow_lines) != n - n_fast:
        raise ValueError(
            f"{len(slow_lines)} rendered lines for {n - n_fast} slow rows"
        )
    rs_number = np.ascontiguousarray(rs_number, np.int64)
    path_idx = np.ascontiguousarray(path_idx, np.int64)
    if not fast.shape == rs_number.shape == path_idx.shape == (n,):
        raise ValueError("one mask flag, rs number and path index a row")
    table = [p.encode("ascii") for p in paths]
    path_off = np.zeros(len(table) + 1, np.int64)
    np.cumsum(np.fromiter(map(len, table), np.int64, len(table)),
              out=path_off[1:])
    path_bytes = np.frombuffer(b"".join(table) or b"\0", np.uint8)
    slow = ("\n".join(slow_lines) + "\n").encode("ascii") if slow_lines else b""
    slow_end = np.cumsum(
        np.fromiter(map(len, slow_lines), np.int64, len(slow_lines)) + 1
    )
    slow_bytes = np.frombuffer(slow or b"\0", np.uint8)
    alleles = batch.ref_len[fast].sum(dtype=np.int64) \
        + batch.alt_len[fast].sum(dtype=np.int64)
    longest = int(np.diff(path_off).max()) if table else 0
    cap = n_fast * (_ROW_BOUND + longest) + 2 * int(alleles) + len(slow)
    out = _line_buffer(cap)
    args, _held = _rows(batch)
    total = lib.avdb_mapping_lines(
        *args, _ptr(rs_number), _ptr(path_idx), _ptr(path_bytes),
        _ptr(path_off), len(table), _ptr(fast), _ptr(slow_bytes),
        _ptr(slow_end), _ptr(out), cap,
    )
    if total == -2:
        raise ValueError("a row marked fast that fast_rows would not keep, "
                         "or a path index outside the table")
    if total < 0:  # the bound above is the C side's own: unreachable
        raise RuntimeError("mapping line buffer too small")
    return out[:total]
