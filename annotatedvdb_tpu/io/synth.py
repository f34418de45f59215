"""Synthetic variant batches with a realistic shape mix (bench/dryrun input).

gnomAD-like composition: mostly SNVs, a tail of small insertions/deletions/
MNVs.  Pure numpy so it runs identically on any backend without touching JAX.
"""

from __future__ import annotations

import numpy as np

from annotatedvdb_tpu.types import VariantBatch

_BASES = np.frombuffer(b"ACGT", dtype=np.uint8)


def synthetic_batch(
    n: int,
    width: int = 16,
    snv_fraction: float = 0.85,
    seed: int = 7,
) -> VariantBatch:
    rng = np.random.default_rng(seed)
    chrom = rng.integers(1, 26, n).astype(np.int8)
    pos = rng.integers(1, 240_000_000, n).astype(np.int32)

    fill_ref = _BASES[rng.integers(0, 4, (n, width))]
    fill_alt = _BASES[rng.integers(0, 4, (n, width))]

    shape = rng.random(n)
    indel_len = rng.integers(2, width + 1, n)
    is_del = (shape >= snv_fraction) & (shape < snv_fraction + (1 - snv_fraction) / 2)
    is_ins = shape >= snv_fraction + (1 - snv_fraction) / 2

    ref_len = np.where(is_del, indel_len, 1).astype(np.int32)
    alt_len = np.where(is_ins, indel_len, 1).astype(np.int32)
    # anchored indels: alt (resp. ref) starts with the shared anchor base
    fill_alt[:, 0] = np.where(is_ins | is_del, fill_ref[:, 0], fill_alt[:, 0])

    cols = np.arange(width)[None, :]
    ref = np.where(cols < ref_len[:, None], fill_ref, 0).astype(np.uint8)
    alt = np.where(cols < alt_len[:, None], fill_alt, 0).astype(np.uint8)
    return VariantBatch(chrom, pos, ref, alt, ref_len, alt_len)


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


def batch_chunk(batch: VariantBatch, line_start: int = 1):
    """Wrap a :class:`VariantBatch` as a minimal :class:`~annotatedvdb_tpu.io.vcf.VcfChunk`
    (tests/dryruns drive loader internals with synthetic batches)."""
    from annotatedvdb_tpu.io.vcf import VcfChunk
    from annotatedvdb_tpu.types import decode_allele

    n = batch.n
    refs = [decode_allele(batch.ref[i], int(batch.ref_len[i])) for i in range(n)]
    alts = [decode_allele(batch.alt[i], int(batch.alt_len[i])) for i in range(n)]
    return VcfChunk(
        batch=batch,
        refs=refs,
        alts=alts,
        ref_snp=[None] * n,
        variant_id=[
            f"{int(batch.chrom[i])}:{int(batch.pos[i])}:{refs[i]}:{alts[i]}"
            for i in range(n)
        ],
        is_multi_allelic=np.zeros(n, np.bool_),
        frequencies=[None] * n,
        rs_position=[None] * n,
        info=[None] * n,
        line_number=np.arange(line_start, line_start + n, dtype=np.int64),
        counters={"line": n},
        rs_number=np.full(n, -1, np.int64),
        rs_weird=np.zeros(n, np.bool_),
        has_freq=np.zeros(n, np.bool_),
    )


def synthetic_cadd_setup(cadd_dir: str, n_variants: int, table_positions: int,
                         seed: int = 7, width: int = 16):
    """One chr1 store of SNVs plus a matching gzipped CADD SNV table (3 alt
    rows per position) — shared by the CADD throughput gate and bench leg so
    the bench always measures exactly what the gate pins.

    Returns ``(store, expected_matches)``: matching is by unordered allele
    set (the reference's allele-set compare, ``cadd_updater.py:200-217``),
    and the table at each position carries (base, x) for every x != base —
    so a variant matches iff the position's cycling base is one of its two
    alleles."""
    import gzip
    import os
    import random

    from annotatedvdb_tpu.ops.hashing import allele_hash_jit
    from annotatedvdb_tpu.store import VariantStore

    rng = random.Random(seed)
    store = VariantStore(width=width)
    sh = store.shard(1)
    pos = np.sort(np.array(
        rng.sample(range(10_000, 10_000 + table_positions), n_variants),
        np.int32,
    ))
    ref = np.zeros((n_variants, width), np.uint8)
    alt = np.zeros((n_variants, width), np.uint8)
    bases = np.frombuffer(b"ACGT", np.uint8)
    ri = np.array([rng.randrange(4) for _ in range(n_variants)])
    off = np.array([rng.randrange(1, 4) for _ in range(n_variants)])
    rr = bases[ri]
    aa = bases[(ri + off) % 4]  # always a REAL base distinct from ref
    ref[:, 0] = rr
    alt[:, 0] = aa
    ones = np.ones(n_variants, np.int32)
    h = np.asarray(allele_hash_jit(ref, alt, ones, ones))
    sh.append({"pos": pos, "h": h, "ref_len": ones, "alt_len": ones},
              ref, alt)

    os.makedirs(cadd_dir, exist_ok=True)
    with gzip.open(os.path.join(cadd_dir, "whole_genome_SNVs.tsv.gz"),
                   "wt", compresslevel=1) as f:
        f.write("## CADD\n#Chrom\tPos\tRef\tAlt\tRawScore\tPHRED\n")
        lines = []
        for p in range(10_000, 10_000 + table_positions):
            b = "ACGT"[p % 4]
            for a in "ACGT":
                if a != b:
                    lines.append(f"1\t{p}\t{b}\t{a}\t0.5\t10.0")
            if len(lines) > 200_000:
                f.write("\n".join(lines) + "\n")
                lines = []
        if lines:
            f.write("\n".join(lines) + "\n")
    with gzip.open(os.path.join(cadd_dir, "gnomad.genomes.r3.0.indel.tsv.gz"),
                   "wt") as f:
        f.write("## CADD\n#Chrom\tPos\tRef\tAlt\tRawScore\tPHRED\n")
    table_base = bases[pos % 4]
    expected = int(((rr == table_base) | (aa == table_base)).sum())
    return store, expected
