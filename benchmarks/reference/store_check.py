"""Does a committed store hold what a first-wins load of the generated file
must hold?  Every row, every column the load computes.

Two sides, kept apart so that the control and the planted faults can take
the program's place:

- :func:`expected_view` — the reference: the generator's rows, deduplicated
  first-wins, with every computed column from ``reference/annotate.py``.
  Imports nothing of the program.
- :func:`read_store` — the program's side: the store as its own reader
  (``VariantStore.load``, the only reader of its format) returns it from
  disk, after the load process has exited.

:func:`compare` takes two such views and returns the numbers compared.  All
are exact counts; every limit is 0 (:data:`LIMITS`).
"""

from __future__ import annotations

import numpy as np

from reference.annotate import FREQ_POPULATION, reference_columns

#: name -> limit of every number :func:`compare` returns
LIMITS = {
    "row_count_gap": 0,
    "identity_mismatches": 0,
    "field_mismatches": 0,
    "frequency_mismatches": 0,
}

IDENTITY = ("pos", "h", "ref_len", "alt_len")
FIELDS = ("ref_snp", "is_multi_allelic", "bin_level", "leaf_bin",
          "needs_digest")


def dedup_mask(rows: dict, keep: str = "first") -> np.ndarray:
    """[rows] bool: the occurrence of each (chrom, pos, ref, alt) that a
    ``keep``-wins load keeps.  ``"first"`` is what the configuration
    guarantees; ``"last"`` is the control that breaks the guarantee."""
    ident = np.rec.fromarrays(
        [rows["chrom"], rows["pos"], rows["ref"], rows["alt"]]
    )
    n = ident.shape[0]
    if keep == "first":
        _, index = np.unique(ident, return_index=True)
    else:
        _, index = np.unique(ident[::-1], return_index=True)
        index = n - 1 - index
    mask = np.zeros(n, np.bool_)
    mask[index] = True
    return mask


def expected_view(rows: dict, chromosomes: tuple, width: int,
                  keep: str = "first") -> dict:
    """{chromosome label: columns} of what the store must hold, each
    chromosome ordered by (pos, hash) as a compacted store orders it.
    ``freq`` is the row's frequency, NaN for none."""
    mask = dedup_mask(rows, keep)
    kept = {k: v[mask] for k, v in rows.items()}
    view = {}
    for ci, label in enumerate(chromosomes):
        m = kept["chrom"] == ci
        pos = kept["pos"][m]
        cols = reference_columns(pos, kept["ref"][m], kept["alt"][m], width)
        order = np.lexsort((cols["h"], pos))
        cols.update(pos=pos, ref_snp=kept["rs"][m],
                    is_multi_allelic=kept["multi"][m], freq=kept["freq"][m])
        view[label] = {k: v[order] for k, v in cols.items()}
    return view


def _plain(value):
    fresh = getattr(value, "fresh", None)
    return fresh() if fresh is not None else value


def read_store(store_dir: str, chromosomes: tuple) -> tuple[dict, int]:
    """(view, width) of the store on disk, in :func:`expected_view`'s
    shape, through the program's own reader."""
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.types import chromosome_code

    store = VariantStore.load(store_dir, readonly=True)
    view = {}
    for label in chromosomes:
        shard = store.shards.get(chromosome_code(label))
        if shard is None or shard.n == 0:
            continue
        shard.compact()  # in memory: one (pos, hash)-sorted segment
        seg = shard.segments[0]
        cols = {name: np.asarray(seg.cols[name])
                for name in IDENTITY + FIELDS}
        cols["ref"], cols["alt"] = np.asarray(seg.ref), np.asarray(seg.alt)
        freq = np.full(seg.n, np.nan)
        column = seg.obj.get("allele_frequencies")
        if column is not None:
            for j in np.flatnonzero(column != None).tolist():  # noqa: E711
                value = _plain(column[j])
                try:
                    freq[j] = float(value[FREQ_POPULATION]["gmaf"])
                except (KeyError, TypeError, ValueError):
                    freq[j] = -1.0  # a value, but not the generator's shape
        cols["freq"] = freq
        view[label] = cols
    extra = sorted(set(store.shards) - {chromosome_code(c)
                                        for c in chromosomes})
    if any(store.shards[c].n for c in extra):
        view["_other"] = {"pos": np.zeros(
            sum(store.shards[c].n for c in extra), np.int32)}
    return view, store.width


def compare(got: dict, want: dict) -> dict:
    """The numbers compared, by the names of :data:`LIMITS`."""
    out = dict.fromkeys(LIMITS, 0)
    for label in sorted(set(got) | set(want)):
        g, w = got.get(label), want.get(label)
        n_got = 0 if g is None else int(g["pos"].shape[0])
        n_want = 0 if w is None else int(w["pos"].shape[0])
        if n_got != n_want:
            # rows cannot be paired: every row of the chromosome counts
            out["row_count_gap"] += abs(n_got - n_want)
            out["identity_mismatches"] += max(n_got, n_want)
            continue
        bad = np.zeros(n_want, np.bool_)
        for name in IDENTITY:
            bad |= g[name] != w[name]
        for name in ("ref", "alt"):
            bad |= (g[name] != w[name]).any(axis=1)
        out["identity_mismatches"] += int(bad.sum())
        bad = np.zeros(n_want, np.bool_)
        for name in FIELDS:
            bad |= g[name] != w[name]
        out["field_mismatches"] += int(bad.sum())
        both_none = np.isnan(g["freq"]) & np.isnan(w["freq"])
        out["frequency_mismatches"] += int(
            (~both_none & (g["freq"] != w["freq"])).sum()
        )
    return out
