"""An object column is visited only where it holds a value (PR 31).

``Segment.build`` takes an object column as a per-row list or as
``SparseValues``; ``sidecar_lines`` walks the rows that hold a value for
``save()`` and for the compactor.  Whatever the input form or the share of
rows with a value, the persisted bytes must equal what a writer that visits
EVERY row through ``sidecar_line`` gives — that per-row walk lives here, as
the reference, and nowhere in the package."""

import dataclasses
import json
import os
import zlib

import numpy as np
import pytest

from annotatedvdb_tpu import native
from annotatedvdb_tpu.io.vcf import VcfBatchReader
from annotatedvdb_tpu.loaders import TpuVcfLoader
from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore, compact_store
from annotatedvdb_tpu.store.variant_store import (
    OBJECT_COLUMNS,
    RawJson,
    Segment,
    SparseValues,
    sidecar_line,
)

WIDTH = 8
FREQ, DIGEST, LONG = "allele_frequencies", "_digest_pk", "_long_alleles"


# -- the per-row reference ---------------------------------------------------


def reference_sidecar(expected: dict, n: int) -> bytes:
    """The sidecar of ``n`` rows whose values are ``expected[column][row]``
    (per-row lists): every row visited, one ``sidecar_line`` call each."""
    present = [c for c in OBJECT_COLUMNS
               if any(v is not None for v in expected.get(c, ()))]
    out = []
    for i in range(n):
        line = sidecar_line(((c, expected[c][i]) for c in present), i)
        if line is not None:
            out.append(line.encode())
    return b"".join(out)


def file_record(path: str) -> dict:
    with open(path, "rb") as f:
        data = f.read()
    return {"bytes": len(data), "crc32": zlib.crc32(data)}


def read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def segment_files(store_dir: str) -> list:
    """[(stem, npz path, jsonl path)] of a saved store, in stem order."""
    stems = sorted(f[:-4] for f in os.listdir(store_dir)
                   if f.endswith(".npz") and not f.startswith("."))
    return [(s, os.path.join(store_dir, s + ".npz"),
             os.path.join(store_dir, s + ".ann.jsonl")) for s in stems]


def assert_saved_as_reference(store_dir: str, expected: dict, n: int):
    """One saved segment: sidecar bytes and both integrity records."""
    [(stem, npz, jsonl)] = segment_files(store_dir)
    assert read(jsonl) == reference_sidecar(expected, n)
    with open(os.path.join(store_dir, "manifest.json")) as f:
        integrity = json.load(f)["integrity"]
    assert integrity[stem] == {"npz": file_record(npz),
                               "jsonl": file_record(jsonl)}


# -- building blocks ---------------------------------------------------------


def numeric_rows(pos) -> tuple:
    pos = np.asarray(pos, np.int32)
    n = pos.shape[0]
    rows = {"pos": pos, "h": (pos.astype(np.uint32) * np.uint32(2654435761)),
            "ref_len": np.ones(n, np.int32), "alt_len": np.ones(n, np.int32)}
    return rows, np.full((n, WIDTH), 65, np.uint8), \
        np.full((n, WIDTH), 67, np.uint8)


def value(column: str, tag: int):
    """A stored value of the column's kind; ``tag`` makes it the row's."""
    if column == DIGEST:
        return f"digest{tag:06d}"
    if column == LONG:
        return ("A" * (WIDTH + 1 + tag % 3), "C" * (WIDTH + 2))
    if tag % 2:
        return RawJson(f'{{"GnomAD": {{"af": 0.{tag:04d}}}}}')
    return {"GnomAD": {"af": tag / 10000.0}, "n": tag}


def held_rows(n: int, pct: int, salt: int = 0) -> list:
    if pct == 0:
        return []
    if pct == 100:
        return list(range(n))
    return list(range(salt % 10, n, 10))


def column_values(column: str, n: int, pct: int, salt: int = 0) -> list:
    """The column as a per-row list: a value on ``pct`` % of ``n`` rows."""
    out = [None] * n
    for i in held_rows(n, pct, salt):
        out[i] = value(column, i + 7 * salt)
    return out


def as_sparse(values: list) -> SparseValues:
    at = [i for i, v in enumerate(values) if v is not None]
    return SparseValues(np.asarray(at, np.int64), [values[i] for i in at])


def build(pos, expected: dict, form) -> Segment:
    rows, ref, alt = numeric_rows(pos)
    given = {c: form(v) for c, v in expected.items()}
    return Segment.build(
        rows, ref, alt,
        annotations={c: v for c, v in given.items()
                     if c not in (DIGEST, LONG)},
        digest_pk=given.get(DIGEST), long_alleles=given.get(LONG),
    )


def save_segments(store_dir: str, segments: list) -> VariantStore:
    store = VariantStore(width=WIDTH)
    for seg in segments:
        store.shard(1).append_segment(seg)
    store.save(store_dir)
    return store


def merged_expectation(parts: list) -> tuple:
    """(pos, expected) of the stable merge of ``parts`` — [(pos, expected
    per-row lists)], older first on equal keys — computed row by row."""
    tagged = []
    for k, (pos, expected) in enumerate(parts):
        rows, _ref, _alt = numeric_rows(pos)
        key = (rows["pos"].astype(np.uint64) << np.uint64(32)) \
            | rows["h"].astype(np.uint64)
        tagged += [(int(key[i]), k, i) for i in range(len(pos))]
    tagged.sort()
    columns = sorted({c for _pos, e in parts for c in e})
    merged = {c: [parts[k][1].get(c, [None] * len(parts[k][0]))[i]
                  for _key, k, i in tagged] for c in columns}
    return [parts[k][0][i] for _key, k, i in tagged], merged


# -- (a) save(): every share of rows, every object column --------------------


@pytest.mark.parametrize("columns", [
    (FREQ,), (DIGEST,), (LONG,), (FREQ, DIGEST),
], ids=lambda c: "+".join(c))
@pytest.mark.parametrize("pct", [0, 10, 100])
def test_saved_segment_matches_per_row_reference(tmp_path, pct, columns):
    n = 257
    pos = list(range(1000, 1000 + n))
    expected = {c: column_values(c, n, pct, salt=k)
                for k, c in enumerate(columns)}
    dirs = {}
    for name, form in (("sparse", as_sparse), ("list", list)):
        dirs[name] = str(tmp_path / name)
        store = save_segments(dirs[name], [build(pos, expected, form)])
        seg = store.shard(1).segments[0]
        for c in OBJECT_COLUMNS:  # a column without a value stays None
            assert (seg.obj[c] is None) == (pct == 0 or c not in columns)
        assert_saved_as_reference(dirs[name], expected, n)
    [(_s, npz_a, _j)] = segment_files(dirs["sparse"])
    [(_s, npz_b, _j)] = segment_files(dirs["list"])
    assert read(npz_a) == read(npz_b)


@pytest.mark.parametrize("form", [as_sparse, list], ids=["sparse", "list"])
def test_unsorted_input_lands_on_its_sorted_row(tmp_path, form):
    """``Segment.build`` sorts its rows; a value follows its row."""
    n = 120
    order = np.random.default_rng(5).permutation(n)
    pos = (2000 + order).tolist()
    given = {FREQ: column_values(FREQ, n, 10), LONG: column_values(LONG, n, 10, 3)}
    expected = {c: [v[int(np.flatnonzero(order == i)[0])] for i in range(n)]
                for c, v in given.items()}
    d = str(tmp_path / "vdb")
    save_segments(d, [build(pos, given, form)])
    assert_saved_as_reference(d, expected, n)


def test_a_none_among_sparse_values_is_a_row_without_a_value(tmp_path):
    n = 30
    values = column_values(FREQ, n, 10)
    sparse = SparseValues(np.array([0, 4, 10, 20]),
                          [values[0], None, values[10], values[20]])
    d = str(tmp_path / "vdb")
    save_segments(d, [build(range(100, 100 + n), {FREQ: sparse}, lambda v: v)])
    assert_saved_as_reference(d, {FREQ: values}, n)
    hollow = build(range(100, 100 + n),
                   {FREQ: SparseValues(np.array([3]), [None])}, lambda v: v)
    assert hollow.obj[FREQ] is None


# -- (b) merges keep the row set ---------------------------------------------


def _parts(shape: str) -> list:
    """[(pos, expected)]: segments to merge, oldest first."""
    if shape == "disjoint":
        spans = [range(100, 190), range(300, 420)]
    elif shape == "interleaved":
        spans = [range(100, 400, 2), range(101, 380, 3)]
    elif shape == "many-chain":
        spans = [range(100, 160), range(200, 330), range(400, 470)]
    else:  # many-tree: the middle part overlaps both neighbours
        spans = [range(100, 300, 2), range(150, 500, 5), range(301, 520, 3)]
    out = []
    for k, span in enumerate(spans):
        pos = list(span)
        expected = {FREQ: column_values(FREQ, len(pos), 10, salt=k)}
        if k != 1:  # one part has no long alleles at all
            expected[LONG] = column_values(LONG, len(pos), 10, salt=k + 4)
        out.append((pos, expected))
    return out


@pytest.mark.parametrize("form", [as_sparse, list], ids=["sparse", "list"])
@pytest.mark.parametrize(
    "shape", ["disjoint", "interleaved", "many-chain", "many-tree"])
def test_merged_segment_matches_per_row_reference(tmp_path, shape, form):
    parts = _parts(shape)
    segments = [build(pos, expected, form) for pos, expected in parts]
    merged = (Segment.merge(*segments) if len(segments) == 2
              else Segment.merge_many(segments))
    pos, expected = merged_expectation(parts)
    assert merged.cols["pos"].tolist() == pos
    d = str(tmp_path / "vdb")
    save_segments(d, [merged])
    assert_saved_as_reference(d, expected, len(pos))


def test_filtered_segment_matches_per_row_reference(tmp_path):
    n = 200
    pos = list(range(500, 500 + n))
    expected = {FREQ: column_values(FREQ, n, 10),
                DIGEST: column_values(DIGEST, n, 10, 5)}
    keep = np.arange(n) % 3 != 0
    d = str(tmp_path / "vdb")
    save_segments(d, [build(pos, expected, as_sparse).filter(keep)])
    kept = {c: [v[i] for i in range(n) if keep[i]] for c, v in expected.items()}
    assert_saved_as_reference(d, kept, int(keep.sum()))


# -- (c) a value set after the build is written ------------------------------


@pytest.mark.parametrize("how", ["fresh-column", "held-column", "obj-dense"])
def test_value_set_after_the_build_is_written(tmp_path, how):
    n = 90
    pos = list(range(100, 100 + n))
    expected = {FREQ: column_values(FREQ, n, 10)}
    d = str(tmp_path / "vdb")
    store = save_segments(d, [build(pos, expected, as_sparse)])
    assert_saved_as_reference(d, expected, n)
    shard = store.shard(1)
    if how == "fresh-column":  # a column no row of the segment held
        rows, column = [3, 40, 41], "cadd_scores"
        values = [{"CADD_phred": 1.5 + i} for i in rows]
        shard.update_annotation(np.asarray(rows), column, values)
    elif how == "held-column":  # rows beside the ones the build was given
        rows, column = [1, 2, 55], FREQ
        values = [{"TOPMED": {"af": i / 100}} for i in rows]
        shard.update_annotation(np.asarray(rows), column, values)
    else:  # the array itself, as the shard's flat views hand it out
        rows, column = [7, 8], "vep_output"
        values = [RawJson('{"most_severe": "intron_variant"}'), {"k": [1, 2]}]
        seg = shard.segments[0]
        col = seg.obj_dense(column)
        for i, v in zip(rows, values):
            col[i] = v
        seg.dirty = True
    expected.setdefault(column, [None] * n)
    for i, v in zip(rows, values):
        assert expected[column][i] is None
        expected[column][i] = v
    store.save(d)
    assert_saved_as_reference(d, expected, n)
    loaded = VariantStore.load(d).shard(1)
    assert [loaded.get_ann(column, i) for i in rows] == values


# -- (d) the compactor writes what save() writes -----------------------------


@pytest.mark.parametrize("chunk_rows", [1024, None], ids=["chunked", "whole"])
def test_compacted_sidecar_equals_saved_sidecar(tmp_path, chunk_rows):
    """Same rows, two writers: ``doctor compact``'s output, inflated, is
    byte for byte what ``save()`` gives for the one merged segment."""
    spans = [range(100, 1900), range(2000, 3300), range(3300, 3301),
             range(4000, 5500)]
    parts = []
    for k, span in enumerate(spans):
        pos = list(span)
        parts.append((pos, {
            FREQ: column_values(FREQ, len(pos), 10, salt=k),
            LONG: column_values(LONG, len(pos), 10 if k % 2 else 0, salt=k),
        }))
    fragmented = str(tmp_path / "fragmented")
    store = VariantStore(width=WIDTH)
    for pos, expected in parts:  # a save an append: one file pair each
        store.shard(1).append_segment(build(pos, expected, as_sparse))
        store.save(fragmented)
    assert len(segment_files(fragmented)) == len(parts)
    report = compact_store(fragmented, chunk_rows=chunk_rows, min_stems=2)
    assert report["status"] == "compacted", report
    [(_stem, _npz, jsonl)] = segment_files(fragmented)
    compacted = zlib.decompress(read(jsonl))

    pos, expected = merged_expectation(parts)
    assert compacted == reference_sidecar(expected, len(pos))
    merged_dir = str(tmp_path / "merged")
    save_segments(merged_dir, [Segment.merge_many(
        [build(p, e, list) for p, e in parts])])
    [(_stem, _npz, saved)] = segment_files(merged_dir)
    assert compacted == read(saved)


# -- (e) load-vcf: a reader that flags its FREQ rows, and one that does not --


def write_vcf(path, n_lines: int = 1500, freq_every: int = 10) -> None:
    """FREQ on one line in ``freq_every``, multi-allelic sites, two
    chromosomes, an over-width allele (retained strings) and alleles long
    enough for a digest primary key."""
    rng = np.random.default_rng(31)
    bases = "ACGT"
    with open(path, "w") as fh:
        fh.write("##fileformat=VCFv4.2\n")
        fh.write("#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
        pos = 900
        for k in range(n_lines):
            pos += int(rng.integers(1, 5))
            ref = bases[int(rng.integers(4))]
            alt = bases[(bases.index(ref) + 1 + int(rng.integers(3))) % 4]
            if k % 59 == 0:
                alt += "," + bases[(bases.index(ref) + 2) % 4] + "T"
            if k % 401 == 7:
                ref = ref + "ACGT" * 15  # wider than the store's 49
            if k % 333 == 5:
                alt = alt + "G" * 30
            n_alts = alt.count(",") + 1
            info = f"RS={k}" if k % 3 == 0 else "."
            if k % freq_every == 0:
                freqs = ",".join(f"{0.001 * (k % 9 + j + 1):.4f}"
                                 for j in range(n_alts))
                info = f"RS={k};FREQ=GnomAD:0.9,{freqs}|TOPMED:0.8," \
                    + ",".join(["."] * n_alts)
            chrom = "1" if k % 5 else "2"
            fh.write(f"{chrom}\t{pos}\trs{k}\t{ref}\t{alt}\t.\t.\t{info}\n")


def load_directory(tmp_path, vcf, tag, monkeypatch, engine,
                   flagged=True) -> dict:
    """One committed load through ``engine``; every persisted file's bytes
    (the manifest less its per-store uid).  ``flagged=False`` takes the
    reader's ``has_freq`` away, so the loader hands ``Segment.build`` the
    per-row list."""
    store = VariantStore(width=49)
    ledger = AlgorithmLedger(str(tmp_path / f"ledger.{tag}.jsonl"))
    loader = TpuVcfLoader(store, ledger, batch_size=256, log=lambda *a: None)
    save_dir = str(tmp_path / f"vdb.{tag}")
    mapping = str(tmp_path / f"mapping.{tag}")
    with monkeypatch.context() as patch:
        patch.setenv("AVDB_INGEST_ENGINE", engine)
        if not flagged:
            emit = VcfBatchReader._emit
            patch.setattr(
                VcfBatchReader, "_emit",
                lambda self, rows, counters: dataclasses.replace(
                    emit(self, rows, counters), has_freq=None),
            )
        loader.load_file(vcf, commit=True, mapping_path=mapping,
                         persist=lambda: store.save(save_dir))
        store.save(save_dir)
        loader.close()
    out = {"mapping": read(mapping)}
    for name in sorted(os.listdir(save_dir)):
        data = read(os.path.join(save_dir, name))
        if name == "manifest.json":
            doc = json.loads(data)
            doc.pop("store_uid", None)
            data = json.dumps(doc, sort_keys=True).encode()
        out[name] = data
    return out


@pytest.mark.parametrize("engine", ["native", "python"])
def test_load_vcf_store_is_the_per_row_list_store(tmp_path, monkeypatch,
                                                  engine):
    if engine == "native" and not native.available():
        pytest.skip("native library unavailable (no g++)")
    vcf = str(tmp_path / "in.vcf")
    write_vcf(vcf)
    per_row = load_directory(tmp_path, vcf, "list", monkeypatch, "python",
                             flagged=False)
    flagged = load_directory(tmp_path, vcf, engine, monkeypatch, engine)
    assert list(flagged) == list(per_row)
    for name in per_row:
        assert flagged[name] == per_row[name], f"{name} bytes diverge"
    sidecars = b"".join(v for k, v in per_row.items()
                        if k.endswith(".ann.jsonl"))
    for column in (FREQ, DIGEST, LONG):  # the file exercises all three
        assert f'"{column}":'.encode() in sidecars, column


# -- (f) the counter ---------------------------------------------------------


@pytest.mark.parametrize("freq_every,dense", [(10, False), (1, True)],
                         ids=["sparse", "dense"])
def test_sidecar_counter_in_the_run_record(tmp_path, freq_every, dense):
    from annotatedvdb_tpu.cli import load_vcf

    vcf = tmp_path / "in.vcf"
    write_vcf(vcf, 1200, freq_every)
    rc = load_vcf.main(["--fileName", str(vcf), "--storeDir",
                        str(tmp_path / "vdb"), "--commit", "--commitAfter",
                        "256", "--logFilePath", str(tmp_path / "load.log")])
    assert rc == 0
    runs = [json.loads(line) for line in
            (tmp_path / "vdb" / "ledger.jsonl").read_text().splitlines()]
    [run] = [r for r in runs if r.get("type") == "run"]
    sidecar = run["execution"]["sidecar"]
    rows = VariantStore.load(str(tmp_path / "vdb")).n
    assert sidecar["rows"] == rows > 1200
    assert sidecar["visited"] == sidecar["lines"] > 0
    if dense:
        assert sidecar["visited"] == rows
    else:
        assert sidecar["visited"] < rows / 5
