"""A lookup group's identity columns in one native pass.

``loaders/lookup.py`` ``identity_columns`` builds a chromosome group's
allele rows, true lengths and identity hash for the serving engine's point
and bulk lookups and for the memtable's upserts: one ``avdb_identity_columns``
pass (``native/identity.py``) for an ASCII group, else
``types.encode_allele_array`` twice + ``identity_hashes`` — the definition
and the oracle.  Whatever the route, the five arrays must be the scalar
route's byte for byte, or a query would miss the row its load wrote."""

import random

import numpy as np
import pytest

from annotatedvdb_tpu import native
from annotatedvdb_tpu.loaders import TpuVcfLoader
from annotatedvdb_tpu.loaders import lookup
from annotatedvdb_tpu.loaders.lookup import identity_columns, identity_hashes
from annotatedvdb_tpu.serve import QueryEngine, StaticSnapshots
from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore
from annotatedvdb_tpu.store.memtable import build_rows
from annotatedvdb_tpu.types import encode_allele_array

needs_native = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no g++)"
)

WIDTHS = [8, 49]
GROUP_SIZES = [1, 2, 31, 32, 33, 1536, 4608]
FIELDS = ("ref", "alt", "ref_len", "alt_len", "h")


def lengths(width: int) -> list:
    return [1, 2, width - 1, width, width + 1, 300]


def scalar(refs: list, alts: list, width: int) -> tuple:
    ref, ref_len = encode_allele_array(refs, width)
    alt, alt_len = encode_allele_array(alts, width)
    h = identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts)
    return ref, alt, ref_len, alt_len, h


def assert_same(got: tuple, want: tuple) -> None:
    assert len(got) == len(want) == len(FIELDS)
    for name, g, w in zip(FIELDS, got, want):
        assert g.dtype == w.dtype, name
        assert g.shape == w.shape, name
        assert g.tobytes() == w.tobytes(), name


def alleles(rng: random.Random, sizes: list) -> list:
    return ["".join(rng.choice("ACGTN") for _ in range(k)) for k in sizes]


def stats_delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in lookup.identity_stats.items()}


@needs_native
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("n", GROUP_SIZES)
def test_native_route_is_the_scalar_route_by_group_size(n, width):
    rng = random.Random(n * 100 + width)
    choices = lengths(width)
    refs = alleles(rng, [rng.choice(choices) for _ in range(n)])
    alts = alleles(rng, [rng.choice(choices) for _ in range(n)])
    before = dict(lookup.identity_stats)
    got = identity_columns(refs, alts, width)
    assert stats_delta(before) == {"rows": n, "native_rows": n,
                                   "scalar_rows": 0}
    assert_same(got, scalar(refs, alts, width))


@needs_native
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("k", range(6))
@pytest.mark.parametrize("side", ["ref", "alt"])
def test_native_route_is_the_scalar_route_by_allele_length(side, k, width):
    """Every row of the group has one side's allele of the given length
    (over-width rows take the full-string hash); the other side varies."""
    size = lengths(width)[k]
    rng = random.Random(size * 7 + width)
    fixed = alleles(rng, [size] * 40)
    other = alleles(rng, [rng.choice(lengths(width)) for _ in range(40)])
    refs, alts = (fixed, other) if side == "ref" else (other, fixed)
    got = identity_columns(refs, alts, width)
    assert_same(got, scalar(refs, alts, width))
    fixed_len = got[2] if side == "ref" else got[3]
    assert fixed_len.tolist() == [size] * 40


@needs_native
def test_over_width_rows_take_the_full_string_hash():
    """Two rows that agree within the width hash apart: the pass hashes
    every byte of an over-width allele, not its truncated row."""
    width = 8
    refs = ["A" * 20 + "C", "A" * 20 + "G"]
    ref, alt, _rl, _al, h = identity_columns(refs, ["T", "T"], width)
    assert ref[0].tobytes() == ref[1].tobytes() == b"A" * width
    assert h[0] != h[1]
    from annotatedvdb_tpu.loaders.vcf_loader import _fnv32_str
    assert h.tolist() == [int(_fnv32_str(r, "T")) for r in refs]


@needs_native
def test_empty_group_and_empty_alleles():
    got = identity_columns([], [], 49)
    assert_same(got, scalar([], [], 49))
    got = identity_columns(["", "A"], ["C", ""], 8)
    assert_same(got, scalar(["", "A"], ["C", ""], 8))


def test_non_ascii_group_takes_the_scalar_route():
    refs, alts = ["A", "Ä", "C"], ["G", "T", "ÇC"]
    before = dict(lookup.identity_stats)
    got = identity_columns(refs, alts, 8)
    assert stats_delta(before) == {"rows": 3, "native_rows": 0,
                                   "scalar_rows": 3}
    assert_same(got, scalar(refs, alts, 8))


def test_without_the_library_the_scalar_route_runs(monkeypatch):
    monkeypatch.setattr(native, "load", lambda: None)
    rng = random.Random(5)
    refs = alleles(rng, [rng.choice(lengths(8)) for _ in range(33)])
    alts = alleles(rng, [rng.choice(lengths(8)) for _ in range(33)])
    before = dict(lookup.identity_stats)
    got = identity_columns(refs, alts, 8)
    assert stats_delta(before) == {"rows": 33, "native_rows": 0,
                                   "scalar_rows": 33}
    assert_same(got, scalar(refs, alts, 8))


@needs_native
def test_lengths_that_do_not_cut_the_bytes_are_refused():
    from annotatedvdb_tpu.native import identity as native_identity

    lens = np.array([1, 2], np.int32)
    with pytest.raises(ValueError):
        native_identity.identity_columns(b"AC", lens, b"GTT", lens, 8)
    with pytest.raises(ValueError):
        native_identity.identity_columns(
            b"A", np.array([2, -1], np.int32), b"GTT", lens, 8)


class CountingStats(dict):
    """``identity_stats`` that counts its writes."""

    def __init__(self, *a):
        super().__init__(*a)
        self.writes = 0

    def __setitem__(self, key, value):
        self.writes += 1
        super().__setitem__(key, value)


def test_counters_move_once_a_group(monkeypatch):
    stats = CountingStats(lookup.identity_stats)
    monkeypatch.setattr(lookup, "identity_stats", stats)
    identity_columns(["A"] * 500, ["C"] * 500, 49)
    # ``rows`` and the route's count: two writes for the group, not 1,000
    assert stats.writes == 2
    parsed = [{"code": c, "pos": 100 + i, "ref": "A", "alt": "G"}
              for i, c in enumerate([1, 1, 2, 3, 3, 3])]
    stats.writes = 0
    build_rows(parsed, 49)
    assert stats.writes == 2 * 3  # three chromosome groups


# -- a loaded row is found by a query on the new route ----------------------


VCF_ROWS = [
    ("1", 1000, "A", "G"),
    ("1", 1001, "AC", "A"),
    ("1", 1002, "A" * 12 + "C", "A"),        # ref over the width
    ("1", 1003, "A" * 12 + "G", "A"),        # same truncated row, other hash
    ("2", 500, "C", "T,CTTTTTTTTTTTTT"),      # alt over the width
    ("2", 501, "GGGGGGGG", "G"),              # exactly the width
    ("X", 77, "T", "TA"),
]


@needs_native
def test_a_loaded_row_is_found_on_the_native_route(tmp_path, monkeypatch):
    width = 8
    vcf = tmp_path / "in.vcf"
    lines = ["##fileformat=VCFv4.2",
             "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO"]
    lines += [f"{c}\t{p}\t.\t{r}\t{a}\t.\t.\t." for c, p, r, a in VCF_ROWS]
    vcf.write_text("\n".join(lines) + "\n")
    store = VariantStore(width=width)
    ledger = AlgorithmLedger(str(tmp_path / "ledger.jsonl"))
    loader = TpuVcfLoader(store, ledger, batch_size=16, log=lambda *a: None)
    monkeypatch.setenv("AVDB_INGEST_ENGINE", "native")
    loader.load_file(str(vcf), commit=True)
    loader.close()
    ids = [f"{c}:{p}:{r}:{a}" for c, p, r, alts in VCF_ROWS
           for a in alts.split(",")]
    absent = ["1:1000:A:T", "2:500:C:TTTTTTTTTTTTTTT", "X:77:T:TAA"]
    engine = QueryEngine(StaticSnapshots(store))
    before = dict(lookup.identity_stats)
    got = engine.lookup_many(ids + absent)
    assert all(text is not None for text in got[:len(ids)]), got
    assert got[len(ids):] == [None] * len(absent)
    assert stats_delta(before) == {"rows": len(ids) + len(absent),
                                   "native_rows": len(ids) + len(absent),
                                   "scalar_rows": 0}
