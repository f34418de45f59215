"""The generator of YCSB workload E's traffic against the generated store:
where each scan starts, how far it reads, and which operations insert.

- **Start keys** (:func:`scatter`): YCSB E draws a scan's start key Zipf
  over the key space and hashes key numbers to scatter the popular ones;
  here rank ``r`` of the Zipf draw names stored row ``scatter[r]``, a
  seeded permutation of the stored rows (the point cell's scatter), the
  ranks drawn over the stored count (YCSB D's count rule: the window's
  few thousand inserts are under 0.1 % of it).
- **Scan length** (:func:`client_draws`): uniform over 1 ..
  ``max_scan_length`` (YCSB's ``scanlengthdistribution=uniform``,
  ``maxscanlength``).
- **A scan** (:func:`scan_head`): ``GET /region/<chr>:<pos>-<pos+W-1>``
  with ``limit=N`` — the first ``N`` records in key order (position, then
  identity hash) from the start row's position, in a window of ``W``
  bases; the envelope's ``count`` is the window's.
- **Inserts**: ``traffic/inserts.py``'s new records, in the order they are
  handed out (YCSB E inserts with ``insertorder=hashed``: new keys land
  all over the key space, as these do, at positions uniform over each
  chromosome's stored span).

Pure numpy; imports nothing of the program.
"""

from __future__ import annotations

import numpy as np


def scatter(n_rows: int, seed: int) -> np.ndarray:
    """[n_rows]: the stored row that holds each Zipf rank."""
    return np.random.default_rng([int(seed), 8]).permutation(n_rows)


def client_draws(cdf: np.ndarray, n_ops: int, insert_proportion: float,
                 max_scan_length: int, seed: int, client: int) -> tuple:
    """(kinds, ranks, lengths) of one client's ``n_ops`` operations: kind 1
    an insert, 0 a scan of ``length`` records from the stored row of Zipf
    rank ``rank``."""
    rng = np.random.default_rng([int(seed), 9, int(client)])
    kinds = (rng.random(n_ops) < float(insert_proportion)).astype(np.int8)
    ranks = np.minimum(np.searchsorted(cdf, rng.random(n_ops)),
                       cdf.shape[0] - 1).astype(np.int64)
    lengths = rng.integers(1, int(max_scan_length) + 1, n_ops)
    return kinds, ranks, lengths.astype(np.int64)


def scan_window(pos: int, window_bp: int) -> tuple[int, int]:
    """(start, end) of a scan from ``pos``: ``window_bp`` bases, both ends
    included."""
    return int(pos), int(pos) + int(window_bp) - 1


def scan_head(label: str, pos: int, window_bp: int, length: int) -> bytes:
    """The bytes of one scan on a keep-alive connection."""
    start, end = scan_window(pos, window_bp)
    return (f"GET /region/{label}:{start}-{end}?limit={int(length)} "
            f"HTTP/1.1\r\nHost: bench\r\n\r\n").encode()
