"""The generator of YCSB workload D's traffic against the generated store:
the records its inserts write, the order its reads call "latest", and each
client's draws.

- **New records** (:func:`new_records`): variants of the dbSNP-shaped
  generator's mix (``traffic/vcf.py``: about 85 % SNVs, 1-6-base
  insertions and deletions; an rs number on every record, a FREQ entry on
  about 10 %), at positions uniform over each chromosome's stored span,
  drawn from a seed stream disjoint from the store's and checked absent
  from it (and from each other): an insert of this traffic is never
  shadowed.
- **Recency** (:func:`recency`): YCSB's key order for ``latest``.  The
  stored rows come first, in a seeded permutation (YCSB hashes key numbers
  to scatter them); inserts follow in the order they were acknowledged.
- **Draws** (:func:`client_draws`): per client, the kind of each
  operation (insert with ``insert_proportion``) and, for a read, its Zipf
  rank back from the newest acknowledged record (rank 0 = the newest).

Pure numpy; imports nothing of the program.
"""

from __future__ import annotations

import json

import numpy as np

from traffic.vcf import SYNTH_FREQ_POPULATION

_BASES = np.frombuffer(b"ACGT", dtype=np.uint8)

#: rs numbers of new records start here: above every generated line's
RS_BASE = 3_000_000_000


def new_records(exp, n: int, seed: int) -> dict:
    """``n`` records absent from the store ``exp`` describes, as columns
    (``chrom`` index, ``pos``, ``ref``/``alt`` ``S8``, ``rs``, ``freq``
    NaN = none), in insert order."""
    rng = np.random.default_rng([int(seed), 4])
    kept = exp.kept
    n_chrom = len(exp.chromosomes)
    key = kept["chrom"].astype(np.int64) << 32 | kept["pos"].astype(np.int64)
    out = {name: [] for name in ("chrom", "pos", "ref", "alt", "freq")}
    seen: set = set()
    while len(seen) < n:
        m = 2 * (n - len(seen)) + 64
        chrom = rng.integers(0, n_chrom, m).astype(np.int8)
        lo = np.array([int(kept["pos"][kept["chrom"] == c][0])
                       for c in range(n_chrom)])
        hi = np.array([exp.last_pos(c) for c in range(n_chrom)])
        pos = rng.integers(lo[chrom], hi[chrom] + 1).astype(np.int32)
        shape = rng.random(m)
        is_ins = (shape >= 0.85) & (shape < 0.925)
        is_del = shape >= 0.925
        b0 = rng.integers(0, 4, m)
        snv_alt = (b0 + 1 + rng.integers(0, 3, m)) % 4
        tail_len = rng.integers(1, 7, m)
        tail = _BASES[rng.integers(0, 4, (m, 6))]
        tail[np.arange(6)[None, :] >= tail_len[:, None]] = 0
        long_allele = np.zeros((m, 8), np.uint8)
        long_allele[:, 0] = _BASES[b0]
        long_allele[:, 1:7] = tail
        short = np.zeros((m, 8), np.uint8)
        short[:, 0] = _BASES[b0]
        snv = np.zeros((m, 8), np.uint8)
        snv[:, 0] = _BASES[snv_alt]
        ref = np.where(is_del[:, None], long_allele, short).view("S8")[:, 0]
        alt = np.where(is_ins[:, None], long_allele,
                       np.where(is_del[:, None], short, snv)).view("S8")[:, 0]
        freq = np.where(rng.random(m) < 0.1,
                        rng.integers(1, 5000, m) / 10_000.0, np.nan)
        # the stored rows at each drawn (chromosome, position)
        want = chrom.astype(np.int64) << 32 | pos.astype(np.int64)
        first = np.searchsorted(key, want, "left")
        last = np.searchsorted(key, want, "right")
        for j in range(m):
            ident = (int(chrom[j]), int(pos[j]), bytes(ref[j]),
                     bytes(alt[j]))
            if ident in seen or any(
                    kept["ref"][i] == ref[j] and kept["alt"][i] == alt[j]
                    for i in range(first[j], last[j])):
                continue
            seen.add(ident)
            for name, col in (("chrom", chrom), ("pos", pos), ("ref", ref),
                              ("alt", alt), ("freq", freq)):
                out[name].append(col[j])
            if len(seen) == n:
                break
    cols = {name: np.array(values, dtype=dtype) for (name, values), dtype
            in zip(out.items(), (np.int8, np.int32, "S8", "S8", np.float64))}
    cols["rs"] = RS_BASE + np.arange(n, dtype=np.int64)
    return cols


def ident(chromosomes, cols: dict, j: int) -> str:
    """``chr:pos:ref:alt`` of record ``j`` of ``cols`` (a new record's
    columns, or the stored rows' in recency order)."""
    return (f"{chromosomes[int(cols['chrom'][j])]}:{int(cols['pos'][j])}:"
            f"{cols['ref'][j].decode()}:{cols['alt'][j].decode()}")


def annotations(freq: float) -> dict | None:
    """The annotation a new record carries: a FREQ entry the way a load
    stores the generator's, or none."""
    if freq != freq:  # NaN
        return None
    return {"allele_frequencies": {SYNTH_FREQ_POPULATION: {"gmaf": freq}}}


def upsert_body(chromosomes, cols: dict, j: int) -> bytes:
    """The ``POST /variants/upsert`` body that inserts record ``j``."""
    entry = {"id": ident(chromosomes, cols, j),
             "ref_snp": int(cols["rs"][j])}
    ann = annotations(float(cols["freq"][j]))
    if ann is not None:
        entry["annotations"] = ann
    return json.dumps({"variants": [entry]}).encode()


def recency(n_rows: int, seed: int) -> np.ndarray:
    """[n_rows]: the stored row at each place of the recency order, oldest
    first (the newest stored row is the last)."""
    return np.random.default_rng([int(seed), 5]).permutation(n_rows)


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    """The cumulative distribution of Zipf ranks 0..n-1 (rank r with
    probability proportional to ``(r + 1) ** -theta``)."""
    weights = np.arange(1, n + 1, dtype=np.float64) ** -float(theta)
    return np.cumsum(weights / weights.sum())


def client_draws(cdf: np.ndarray, n_ops: int, insert_proportion: float,
                 seed: int, client: int) -> tuple[np.ndarray, np.ndarray]:
    """(kinds, ranks) of one client's ``n_ops`` operations: kind 1 is an
    insert, 0 a read of the record ``rank`` places back from the newest
    acknowledged one."""
    rng = np.random.default_rng([int(seed), 6, int(client)])
    kinds = (rng.random(n_ops) < float(insert_proportion)).astype(np.int8)
    ranks = np.searchsorted(cdf, rng.random(n_ops)).astype(np.int64)
    return kinds, np.minimum(ranks, cdf.shape[0] - 1)
