"""The VCF generator of the load and store-building traffic.

A copy of ``annotatedvdb_tpu/io/synth.py`` (``synth_vcf_rows``,
``first_wins``, ``write_synth_vcf``) as it stood when the benchmark was
defined, so that a later change to the program cannot change the traffic.
``benchmarks/tests`` pins the copy to the original byte for byte while the
original exists.  Pure numpy; imports nothing of the program.

:class:`Expected` is what a first-wins load of the generated rows must hold
(from ``chip_smoke.py``).
"""

from __future__ import annotations

import numpy as np

_BASES = np.frombuffer(b"ACGT", dtype=np.uint8)


#: INFO FREQ population written by :func:`write_synth_vcf`
SYNTH_FREQ_POPULATION = "GnomAD"


def synth_vcf_rows(n_lines: int, seed: int = 0,
                   chromosomes: tuple = ("1",)) -> dict:
    """The rows of the synthetic gnomAD/dbSNP-shaped VCF
    :func:`write_synth_vcf` writes, as numpy columns — ONE generator for
    the bench legs and ``chip_smoke.py``, so what a check expects and what
    a file holds cannot drift apart.

    ``n_lines`` data lines, split evenly into one position-sorted block per
    chromosome (positions start at 10,000 and step 1..5): ~85% SNVs, a tail
    of 1..6-base insertions and deletions, 1% multi-allelic sites (a second
    single-base alt on a deletion line — a quarter of them repeat the first
    alt, which is what first-wins dedup is for), ``RS=`` on ~30% of lines
    and a ``FREQ=`` entry on ~10%.

    Returns one entry per ROW (a line's alts expanded, file order):
    ``line`` (0-based data-line index), ``chrom`` (label index into
    ``chromosomes``), ``pos``, ``ref``/``alt`` (``S8`` bytes), ``rs`` (the
    ``rs<N>`` ID number), ``multi`` (line carries >1 alt), ``info_rs``
    (line has ``RS=``), ``freq`` (this alt's FREQ value, NaN = none)."""
    rng = np.random.default_rng(seed)
    n = int(n_lines)
    n_chrom = len(chromosomes)
    chrom = np.minimum(np.arange(n) * n_chrom // max(n, 1), n_chrom - 1)
    step = rng.integers(1, 6, n)
    run = np.cumsum(step)
    # restart the position walk at each chromosome block
    first = np.r_[0, np.flatnonzero(np.diff(chrom)) + 1]
    base = np.repeat(run[first] - step[first], np.diff(np.r_[first, n]))
    pos = (10_000 + run - base).astype(np.int32)

    shape = rng.random(n)
    is_ins = (shape >= 0.85) & (shape < 0.925)
    is_del = shape >= 0.925
    multi = shape > 0.99
    b0 = rng.integers(0, 4, n)
    snv_alt = (b0 + 1 + rng.integers(0, 3, n)) % 4
    tail_len = rng.integers(1, 7, n)
    tail = _BASES[rng.integers(0, 4, (n, 6))]
    tail[np.arange(6)[None, :] >= tail_len[:, None]] = 0
    long_allele = np.zeros((n, 8), np.uint8)
    long_allele[:, 0] = _BASES[b0]
    long_allele[:, 1:7] = tail
    short = np.zeros((n, 8), np.uint8)
    short[:, 0] = _BASES[b0]
    snv = np.zeros((n, 8), np.uint8)
    snv[:, 0] = _BASES[snv_alt]
    ref = np.where(is_del[:, None], long_allele, short)
    alt = np.where(is_ins[:, None], long_allele,
                   np.where(is_del[:, None], short, snv))
    alt2 = np.zeros((n, 8), np.uint8)
    alt2[:, 0] = _BASES[rng.integers(0, 4, n)]
    info_rs = shape < 0.3
    has_freq = rng.random(n) < 0.1
    # 4-decimal frequencies in (0, 1): never the "0"/"." the parser skips
    f1 = rng.integers(1, 5000, n) / 10_000.0
    f2 = rng.integers(1, 5000, n) / 10_000.0

    line = np.arange(n)
    take2 = np.flatnonzero(multi)
    order = np.argsort(np.r_[line, line[take2]], kind="stable")

    def rows(one, two=None):
        two = one[take2] if two is None else two[take2]
        return np.concatenate([one, two])[order]

    return {
        "line": rows(line),
        "chrom": rows(chrom).astype(np.int8),
        "pos": rows(pos),
        "ref": rows(ref.view("S8")[:, 0]),
        "alt": rows(alt.view("S8")[:, 0], alt2.view("S8")[:, 0]),
        "rs": rows(line).astype(np.int64),
        "multi": rows(multi),
        "info_rs": rows(info_rs),
        "freq": rows(np.where(has_freq, f1, np.nan),
                     np.where(has_freq, f2, np.nan)),
    }


def first_wins(rows: dict) -> np.ndarray:
    """[rows] bool: the rows a first-wins load of :func:`synth_vcf_rows`
    keeps — the first occurrence of each (chrom, pos, ref, alt)."""
    ident = np.rec.fromarrays(
        [rows["chrom"], rows["pos"], rows["ref"], rows["alt"]]
    )
    _, first = np.unique(ident, return_index=True)
    keep = np.zeros(rows["pos"].shape[0], np.bool_)
    keep[first] = True
    return keep


def write_synth_vcf(path: str, n_lines: int, seed: int = 0,
                    chromosomes: tuple = ("1",)) -> dict:
    """Write the VCF of :func:`synth_vcf_rows` and return its rows."""
    rows = synth_vcf_rows(n_lines, seed, chromosomes)
    keep = np.r_[True, np.diff(rows["line"]) > 0]  # first row of each line
    second = np.flatnonzero(~keep)
    alt_col = rows["alt"][keep].astype("U10")  # room for ",<base>"
    alt_col[rows["line"][second]] = np.char.add(
        np.char.add(alt_col[rows["line"][second]], ","),
        rows["alt"][second].astype("U8"),
    )
    freq2 = dict(zip(rows["line"][second].tolist(),
                     rows["freq"][second].tolist()))
    labels = [str(c) for c in chromosomes]
    with open(path, "w", buffering=1 << 22) as fh:
        fh.write("##fileformat=VCFv4.2\n")
        fh.write("#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
        out = []
        for i, (c, p, r, a, has_rs, f) in enumerate(zip(
            rows["chrom"][keep].tolist(), rows["pos"][keep].tolist(),
            rows["ref"][keep].astype("U8").tolist(), alt_col.tolist(),
            rows["info_rs"][keep].tolist(), rows["freq"][keep].tolist(),
        )):
            info = [f"RS={i}"] if has_rs else []
            if f == f:  # not NaN: the line carries FREQ
                values = f"{1 - f:.4f},{f:.4f}"
                if i in freq2:
                    values += f",{freq2[i]:.4f}"
                info.append(f"FREQ={SYNTH_FREQ_POPULATION}:{values}")
            out.append(
                f"{labels[c]}\t{p}\trs{i}\t{r}\t{a}\t.\t.\t"
                f"{';'.join(info) or '.'}"
            )
            if len(out) >= 65536:
                fh.write("\n".join(out) + "\n")
                out = []
        if out:
            fh.write("\n".join(out) + "\n")
    return rows


class Expected:
    """What a first-wins load of the generated rows must hold."""

    def __init__(self, rows: dict, chromosomes: tuple):
        self.rows = rows
        self.chromosomes = tuple(chromosomes)
        keep = first_wins(rows)
        self.n_rows = int(keep.sum())
        self.kept = {k: v[keep] for k, v in rows.items()}

    def idents(self, index=None) -> np.ndarray:
        """``chr:pos:ref:alt`` of the kept rows ``index`` (all of them when
        None), as a unicode array."""
        k = self.kept if index is None else {
            name: self.kept[name][index]
            for name in ("chrom", "pos", "ref", "alt")}
        labels = np.array(self.chromosomes)[k["chrom"]]
        out = np.char.add(labels, ":")
        out = np.char.add(out, k["pos"].astype("U11"))
        for col in ("ref", "alt"):
            out = np.char.add(np.char.add(out, ":"), k[col].astype("U8"))
        return out

    def ident(self, i: int) -> str:
        """``chr:pos:ref:alt`` of kept row ``i``."""
        k = self.kept
        return (f"{self.chromosomes[int(k['chrom'][i])]}:{int(k['pos'][i])}:"
                f"{k['ref'][i].decode()}:{k['alt'][i].decode()}")

    def last_pos(self, chrom_index: int) -> int:
        """The last generated position of a chromosome block."""
        return int(self.kept["pos"][self.kept["chrom"] == chrom_index][-1])
