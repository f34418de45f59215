"""The mapping sidecar's lines are written from the chunk's columns (PR 37).

``load-vcf`` writes ``<file>.mapping`` with one native pass a chunk
(``native/mapping.py``) for the rows whose line is a function of the
columns; every other row — and every row where the native library or the
reader's flag columns are missing — goes through the scalar route
(``io/egress.py`` ``mapping_lines``).  Whatever the route, the file's bytes
must be the scalar route's; a per-row writer of the native line lives here,
as the reference, and nowhere in the package."""

import hashlib
import json
import os

import numpy as np
import pytest

from annotatedvdb_tpu import native
from annotatedvdb_tpu.io import egress
from annotatedvdb_tpu.io.synth import write_synth_vcf
from annotatedvdb_tpu.loaders import TpuVcfLoader
from annotatedvdb_tpu.native import mapping as native_mapping
from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore
from annotatedvdb_tpu.types import VariantBatch, chromosome_label

WIDTH = 49
CHUNK = 256

needs_native = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no g++)"
)


def read(path) -> bytes:
    with open(path, "rb") as f:
        return f.read()


# -- the input: generator-shaped, with every slow class ----------------------


def write_vcf(path, per_chromosome: int = 5500) -> int:
    """A dbSNP-shaped VCF over four chromosome labels (1, X, Y, MT): ~85 %
    SNVs, short indels, ``rs<k>`` ids or ``.`` with ``RS=`` on some lines —
    and, planted among them, every row class the native pass leaves to the
    scalar route, a run of lines that fills whole chunks with slow rows
    only, runs that hold none, and a run of repeated lines whose chunks
    insert no row at all.  Returns the data lines written."""
    rng = np.random.default_rng(36)
    bases = "ACGT"
    k = 0
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("##fileformat=VCFv4.2\n")
        fh.write("#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
        for chrom in ("1", "X", "Y", "MT"):
            pos = 0
            for j in range(per_chromosome):
                pos += 1 if j == 0 else int(rng.integers(1, 120))
                ref = bases[int(rng.integers(4))]
                alt = bases[(bases.index(ref) + 1 + int(rng.integers(3))) % 4]
                shape = rng.random()
                if shape > 0.925:
                    ref += "".join(rng.choice(list(bases), rng.integers(1, 7)))
                elif shape > 0.85:
                    alt += "".join(rng.choice(list(bases), rng.integers(1, 7)))
                vid, info = f"rs{k}", "."
                if j % 3 == 1:
                    vid, info = ".", (f"RS={k}" if j % 2 else ".")
                # -- the slow classes, each on all four chromosomes --
                if j % 997 == 11:
                    vid = f"var_{k}"                      # verbatim id
                elif j % 997 == 23:
                    vid = f'we"ird\\id_é{k}'         # needs json.dumps
                elif j % 499 == 31:
                    alt += "," + bases[(bases.index(ref[0]) + 2) % 4] + "T"
                elif j % 499 == 37:
                    vid, alt = ".", alt + ",G" + alt      # multi, no id
                elif j % 997 == 41:
                    vid = f"chr_rs_{k}"                   # weird refsnp
                elif j % 997 == 43:
                    vid = f"rs00{k}"                      # zero-padded
                elif j % 997 == 47:
                    alt = alt[0] + "G" * 30               # digest PK, rs
                    ref = ref[0] + "C" * 25
                elif j % 997 == 53:
                    vid, info = ".", "."                  # digest PK, no rs
                    alt = alt[0] + "T" * 48
                    ref = ref[0] + "A" * 10
                elif j % 997 == 59:
                    ref = ref[0] + "ACGT" * 15            # over the width
                elif j % 997 == 61:
                    vid, alt = ".", alt[0] + "CA" * 30    # over, no rs
                elif j % 997 == 67:
                    alt = alt[0] + '"'                    # allele bytes a
                elif j % 997 == 71:                       # JSON string
                    ref = ref[0] + "\\"                   # cannot carry
                elif j % 997 == 73:
                    vid = "rs7"                           # 1-digit rs
                elif j % 997 == 79:
                    vid = "rs1234567890"                  # 10-digit rs
                elif j % 997 == 83:
                    vid, info = ".", "RS=4"               # 1-digit RS=
                elif 2000 <= j < 2000 + 3 * CHUNK:
                    vid = f"site_{k}"     # chunks of slow rows only
                if j == per_chromosome - 1:
                    pos = 123456789                       # 9-digit position
                line = f"{chrom}\t{pos}\t{vid}\t{ref}\t{alt}\t.\t.\t{info}\n"
                # chunks of duplicates only: no row inserted, no line written
                fh.write(line * (3 * CHUNK if j == 4000 else 1))
                k += 1
    return k


@pytest.fixture(scope="module")
def vcf(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("mapping") / "in.vcf")
    assert write_vcf(path) >= 20_000
    return path


def load(tmp_path, vcf, tag, monkeypatch, *, engine, pipeline="overlapped",
         route="native", spy=None, **loader_options) -> dict:
    """One committed load; the mapping file's bytes, the store directory's
    (the manifest less its per-store uid), and the counters.  ``route``:
    ``native`` as the program runs; ``scalar`` with the fast mask taken
    away (every row through the scalar strings); ``no-library`` with
    ``native.load()`` giving None."""
    store = VariantStore(width=WIDTH)
    ledger = AlgorithmLedger(str(tmp_path / f"ledger.{tag}.jsonl"))
    loader = TpuVcfLoader(store, ledger, batch_size=CHUNK,
                          log=lambda *a: None, **loader_options)
    save_dir = str(tmp_path / f"vdb.{tag}")
    mapping = str(tmp_path / f"mapping.{tag}")
    with monkeypatch.context() as patch:
        patch.setenv("AVDB_INGEST_ENGINE", engine)
        patch.setenv("AVDB_PIPELINE", pipeline)
        if route == "scalar":
            patch.setattr(native_mapping, "fast_rows", lambda *a: None)
        elif route == "no-library":
            patch.setattr(native, "load", lambda: None)
        if spy is not None:
            patch.setattr(native_mapping, "mapping_lines", spy)
        counters = loader.load_file(vcf, commit=True, mapping_path=mapping,
                                    persist=lambda: store.save(save_dir))
        store.save(save_dir)
        loader.close()
    out = {"mapping": read(mapping), "counters": counters}
    for name in sorted(os.listdir(save_dir)):
        data = read(os.path.join(save_dir, name))
        if name == "manifest.json":
            doc = json.loads(data)
            doc.pop("store_uid", None)
            data = json.dumps(doc, sort_keys=True).encode()
        out[name] = data
    return out


# -- (a) the native route's file is the scalar route's -----------------------


@needs_native
@pytest.mark.parametrize("pipeline", ["serial", "overlapped"])
@pytest.mark.parametrize("engine", ["native", "python"])
def test_native_route_file_is_the_scalar_route_file(tmp_path, monkeypatch,
                                                    vcf, engine, pipeline):
    chunks = []
    real = native_mapping.mapping_lines

    def spy(batch, rs, idx, paths, fast, slow_lines):
        chunks.append((int(fast.sum()), len(slow_lines)))
        return real(batch, rs, idx, paths, fast, slow_lines)

    scalar = load(tmp_path, vcf, "scalar", monkeypatch, engine=engine,
                  pipeline=pipeline, route="scalar")
    fast = load(tmp_path, vcf, "native", monkeypatch, engine=engine,
                pipeline=pipeline, spy=spy)
    assert fast["mapping"] == scalar["mapping"]
    assert fast["counters"] == scalar["counters"]
    for name in scalar:  # the store never depended on the route
        assert fast[name] == scalar[name], f"{name} diverges"
    lines = fast["mapping"].decode("ascii").splitlines()
    assert len(lines) == fast["counters"]["variant"] > 20_000
    # every class the writer of the file planted is in it
    text = fast["mapping"].decode("ascii")
    for needle in ('{"var_', r'{"we\"ird\\id_\u', '{"chr_rs_',
                   ':rs00', '"X:', '"Y:', '"M:', ':123456789:',
                   ':rs7"', ':rs1234567890"', ':rs4"', '{"site_',
                   r'\""', r'\\:', '"1:1:'):
        assert needle in text, needle
    keys = [json.loads(l).popitem()[1][0]["primary_key"] for l in lines]
    digests = [k for k in keys if len(k.split(":")[2]) == 32
               and not set(k.split(":")[2]) <= set("ACGT")]
    assert any(":rs" in k for k in digests)
    assert any(":rs" not in k for k in digests)
    # both extremes happened: a chunk with no fast row, one with no slow row
    assert any(n_fast == 0 and n_slow for n_fast, n_slow in chunks)
    assert any(n_fast and n_slow == 0 for n_fast, n_slow in chunks)
    assert any(n_fast and n_slow for n_fast, n_slow in chunks)
    # and chunks of repeated lines inserted no row: nothing was written for
    # them (the file has a line a stored row, checked above)
    assert fast["counters"]["duplicates"] >= 4 * (3 * CHUNK - 1)


@needs_native
def test_every_row_with_strings_still_splices_the_slow_rows(
        tmp_path, monkeypatch, vcf):
    """``store_display_attributes`` builds every row's allele strings; the
    native pass still writes the fast rows, and only the slow rows' lines
    are rendered."""
    scalar = load(tmp_path, vcf, "scalar", monkeypatch, engine="native",
                  route="scalar", store_display_attributes=True)
    fast = load(tmp_path, vcf, "native", monkeypatch, engine="native",
                store_display_attributes=True)
    plain = load(tmp_path, vcf, "plain", monkeypatch, engine="native")
    assert fast["mapping"] == scalar["mapping"] == plain["mapping"]
    for name in scalar:
        assert fast[name] == scalar[name], f"{name} diverges"


# -- (b) without the native library ------------------------------------------


def test_no_native_library_gives_the_same_file(tmp_path, monkeypatch, vcf):
    bare = load(tmp_path, vcf, "bare", monkeypatch, engine="python",
                route="no-library")
    usual = load(tmp_path, vcf, "usual", monkeypatch, engine="python")
    assert bare["mapping"] == usual["mapping"]
    for name in usual:
        assert bare[name] == usual[name], f"{name} diverges"


def test_library_that_fails_to_build_is_a_slower_load_not_a_failed_one(
        tmp_path, monkeypatch, vcf):
    """Part 0 of PR 37 (H3): on a host where the library's first build
    fails — no compiler, a compile error, a binary that does not load —
    ``native.load()`` gives None through its own error path, the tokenizer
    and the mapping file both take the Python route, and the load ends rc 0
    with the same bytes."""
    def no_compiler(*a, **k):
        raise RuntimeError("native build of avdb_native failed:\ng++: not found")

    usual = load(tmp_path, vcf, "usual", monkeypatch, engine="python")
    with monkeypatch.context() as patch:
        patch.setattr(native, "_lib", None)
        patch.setattr(native, "_lib_error", None)
        patch.setattr(native, "build_shared_lib", no_compiler)
        assert native.load() is None and not native.available()
        before = dict(egress.mapping_stats)
        bare = load(tmp_path, vcf, "bare", monkeypatch, engine="auto")
        tally = egress.mapping_state(before)
    assert tally["native_rows"] == 0
    assert tally["rows"] == tally["scalar_rows"] == bare["counters"]["variant"]
    assert bare["mapping"] == usual["mapping"]
    assert bare["counters"] == usual["counters"]
    for name in usual:
        assert bare[name] == usual[name], f"{name} diverges"


# -- (c) the counter ---------------------------------------------------------


@pytest.mark.parametrize("library", [True, False], ids=["native", "bare"])
def test_mapping_counter_in_the_run_record(tmp_path, monkeypatch, vcf,
                                           library):
    from annotatedvdb_tpu.cli import load_vcf

    if library and not native.available():
        pytest.skip("native library unavailable (no g++)")
    src = tmp_path / "in.vcf"
    src.write_bytes(read(vcf))
    if not library:
        monkeypatch.setattr(native, "load", lambda: None)
        monkeypatch.setenv("AVDB_INGEST_ENGINE", "python")
    rc = load_vcf.main(["--fileName", str(src), "--storeDir",
                        str(tmp_path / "vdb"), "--commit", "--commitAfter",
                        "4096", "--logFilePath", str(tmp_path / "load.log")])
    assert rc == 0
    runs = [json.loads(line) for line in
            (tmp_path / "vdb" / "ledger.jsonl").read_text().splitlines()]
    [run] = [r for r in runs if r.get("type") == "run"]
    mapping = run["execution"]["mapping"]
    lines = read(str(src) + ".mapping").count(b"\n")
    assert mapping["rows"] == mapping["native_rows"] + mapping["scalar_rows"]
    assert mapping["rows"] == lines == run["counters"]["variant"] > 20_000
    if library:
        assert mapping["native_rows"] > 0.8 * mapping["rows"]
        assert mapping["scalar_rows"] > 3 * CHUNK
    else:
        assert mapping["native_rows"] == 0


# -- (d) the wrapper against a per-row reference ------------------------------


def reference_line(batch: VariantBatch, i: int, rs: int, path: str) -> bytes:
    """The line the native pass writes for row ``i``, one row at a time."""
    ref = bytes(batch.ref[i, :batch.ref_len[i]]).decode("ascii")
    alt = bytes(batch.alt[i, :batch.alt_len[i]]).decode("ascii")
    vid = f"{chromosome_label(batch.chrom[i])}:{int(batch.pos[i])}:{ref}:{alt}"
    pk = vid + (f":rs{rs}" if rs >= 0 else "")
    return json.dumps(
        {vid: [{"primary_key": pk, "bin_index": path}]}
    ).encode("ascii") + b"\n"


def reference_fast(batch: VariantBatch, i: int) -> bool:
    plain = set(range(0x20, 0x7F)) - {ord('"'), ord("\\")}
    return bool(
        1 <= batch.chrom[i] <= 25 and batch.pos[i] >= 0
        and 0 <= batch.ref_len[i] <= batch.width
        and 0 <= batch.alt_len[i] <= batch.width
        and set(batch.ref[i, :batch.ref_len[i]].tolist()) <= plain
        and set(batch.alt[i, :batch.alt_len[i]].tolist()) <= plain
        # decode_alleles reads a cell up to its last non-zero byte
        and not batch.ref[i, batch.ref_len[i]:].any()
        and not batch.alt[i, batch.alt_len[i]:].any()
    )


@needs_native
def test_wrapper_against_the_per_row_reference():
    rng = np.random.default_rng(7)
    rows = [
        ("1", 1, "A", "C"), ("22", 999999999, "ACGT", "A"),
        ("X", 2147483647, "G", "GTTTT"), ("Y", 10, "N", "*"),
        ("M", 16569, "acgt", "a.-"), ("9", 5, "A", "C T"),
        ("1", 7, 'A"', "C"), ("1", 8, "A", "C\\"), ("1", 9, "A\x7f", "C"),
        ("1", 10, "A\tC", "G"), ("2", 11, "A" * 8, "C" * 8),
        ("2", 12, "A" * 9, "C"),   # over the width of 8
        ("3", 13, "A", "C" * 20),  # over the width of 8
    ]
    rows += [(str(1 + int(rng.integers(22))), int(rng.integers(1, 10 ** 9)),
              "ACGT"[int(rng.integers(4))],
              "ACGT"[int(rng.integers(4))] * int(rng.integers(1, 8)))
             for _ in range(500)]
    batch = VariantBatch.from_tuples(rows, width=8)
    n = batch.n
    batch.ref[23, 5] = ord("G")  # a cell not zero-padded past its length:
    batch.chrom[20] = 0    # not a chromosome code
    batch.chrom[21] = 26
    batch.pos[22] = -5
    candidates = np.ones(n, np.bool_)
    candidates[30:40] = False  # the caller's flag columns
    candidates[-1] = False     # the last row is a slow one
    fast = native_mapping.fast_rows(batch, candidates)
    expect = np.array([bool(candidates[i]) and reference_fast(batch, i)
                       for i in range(n)])
    assert fast.dtype == np.bool_ and (fast == expect).all()
    assert candidates[30:40].sum() == 0  # the caller's mask is not written
    for i in (5,):   # a space is printable: a fast row
        assert fast[i]
    for i in (6, 7, 8, 9, 11, 12, 20, 21, 22, 23):
        assert not fast[i], i
    rs = np.where(rng.random(n) < 0.5, -1,
                  rng.integers(0, 2 ** 62, n)).astype(np.int64)
    rs[0], rs[1], rs[2] = 0, 9, 9223372036854775807
    paths = ["chr1.L1.B1", "chr22.L1.B1.L2.B2.L3.B1", "chrX"]
    path_idx = rng.integers(0, len(paths), n)
    slow_rows = np.flatnonzero(~fast)
    slow_lines = [f"<slow line of row {i}>" * (1 + i % 3) for i in slow_rows]
    data = native_mapping.mapping_lines(
        batch, rs, path_idx, paths, fast, slow_lines
    )
    expected, ends = [], []
    slow_at = iter(slow_lines)
    for i in range(n):
        expected.append(
            reference_line(batch, i, int(rs[i]), paths[path_idx[i]])
            if fast[i] else next(slow_at).encode() + b"\n"
        )
        ends.append(sum(map(len, expected)))
    data = data.tobytes()
    assert data == b"".join(expected)          # splice order, every byte
    assert len(data) == ends[-1] and b"\0" not in data  # exactly filled
    # the scalar route agrees with the reference on the fast rows
    at = np.flatnonzero(fast)
    vids = egress.metaseq_ids(
        VariantBatch(*(np.take(x, at, axis=0) for x in batch))
    ).tolist()
    pks = [v + (f":rs{rs[i]}" if rs[i] >= 0 else "")
           for v, i in zip(vids, at)]
    scalar = egress.mapping_lines(vids, pks, [paths[path_idx[i]] for i in at])
    assert [s.encode() + b"\n" for s in scalar] == [expected[i] for i in at]
    # a count of rendered lines that is not the count of slow rows
    with pytest.raises(ValueError):
        native_mapping.mapping_lines(batch, rs, path_idx, paths, fast,
                                     slow_lines[:-1])
    # a mask that is not fast_rows' own, a path index off the table, and
    # columns of another length are refused, not read out of bounds
    forged = fast.copy()
    forged[12] = True  # over the width
    with pytest.raises(ValueError):
        native_mapping.mapping_lines(batch, rs, path_idx, paths, forged,
                                     slow_lines[1:])
    with pytest.raises(ValueError):
        native_mapping.mapping_lines(batch, rs, path_idx + len(paths), paths,
                                     fast, slow_lines)
    with pytest.raises(ValueError):
        native_mapping.mapping_lines(batch, rs[:-1], path_idx, paths, fast,
                                     slow_lines)
    # no slow row at all, and no fast row at all
    only = np.flatnonzero(fast)[:50]
    part = VariantBatch(*(np.take(x, only, axis=0) for x in batch))
    data = native_mapping.mapping_lines(
        part, rs[only], path_idx[only], paths, np.ones(50, np.bool_), []
    )
    assert data.tobytes() == b"".join(expected[i] for i in only)
    data = native_mapping.mapping_lines(
        part, rs[only], path_idx[only], paths, np.zeros(50, np.bool_),
        ["x"] * 50,
    )
    assert data.tobytes() == b"x\n" * 50


@needs_native
def test_wrapper_buffer_is_bounded_and_reused(monkeypatch):
    """The output bound is computed in Python and checked again in C: a
    cap one byte short gives -1 (the wrapper raises) and nothing past the
    cap is touched; and a thread's second call reuses the first's buffer,
    so what a caller copied out of the first is its own."""
    batch = VariantBatch.from_tuples(
        [("1", 10, "A", "C"), ("X", 20, "AC", "A"), ("2", 30, "G", "T")],
        width=8,
    )
    rs = np.array([-1, 5, 77], np.int64)
    paths = ["chr1.L1.B1", "chrX.L1.B1.L2.B2"]
    idx = np.array([0, 1, 0], np.int64)
    fast = np.array([True, False, True])
    first = native_mapping.mapping_lines(batch, rs, idx, paths, fast, ["s"])
    kept = first.tobytes()
    assert kept == (reference_line(batch, 0, -1, paths[0]) + b"s\n"
                    + reference_line(batch, 2, 77, paths[0]))
    second = native_mapping.mapping_lines(
        batch, rs, idx[::-1].copy(), paths, ~fast, ["t", "u"]
    )
    assert second.tobytes() == (
        b"t\n" + reference_line(batch, 1, 5, paths[1]) + b"u\n"
    )
    assert np.shares_memory(first, second)  # one buffer a thread
    assert kept != first.tobytes()          # the view moved on, the copy not
    # one row, whose path is the table's longest: Python's bound is the C
    # side's exactly, so one byte less must be refused
    one = VariantBatch(*(x[:1] for x in batch))
    buf = native_mapping._line_buffer(4096)
    buf[:] = 0xAA
    monkeypatch.setattr(native_mapping, "_ROW_BOUND",
                        native_mapping._ROW_BOUND - 1)
    with pytest.raises(RuntimeError):
        native_mapping.mapping_lines(one, rs[:1], idx[:1], paths[:1],
                                     np.ones(1, np.bool_), [])
    assert (buf == 0xAA).all()


# -- (e) the cell's generator: 300,000 records, both routes ------------------


@needs_native
def test_cell_generator_store_and_mapping_do_not_depend_on_the_route(
        tmp_path, monkeypatch):
    """PR 31's check, repeated: the same 300,000 records of the load cell's
    generator through the scalar route (the parent's text) and the native
    one — every persisted file and the mapping file, byte for byte."""
    vcf = str(tmp_path / "cell.vcf")
    write_synth_vcf(vcf, 300_000, seed=36, chromosomes=("1", "2", "22"))
    out = {}
    fast_rows = native_mapping.fast_rows
    for route in ("scalar", "native"):
        monkeypatch.setattr(native_mapping, "fast_rows",
                            fast_rows if route == "native"
                            else (lambda *a: None))
        store = VariantStore(width=WIDTH)
        ledger = AlgorithmLedger(str(tmp_path / f"ledger.{route}.jsonl"))
        loader = TpuVcfLoader(store, ledger, log=lambda *a: None)
        save_dir = str(tmp_path / f"vdb.{route}")
        mapping = str(tmp_path / f"mapping.{route}")
        loader.load_file(vcf, commit=True, mapping_path=mapping,
                         persist=lambda: store.save(save_dir))
        store.save(save_dir)
        loader.close()
        digest = hashlib.sha256(read(mapping))
        names = sorted(n for n in os.listdir(save_dir)
                       if n != "manifest.json")
        for name in names:
            digest.update(name.encode() + read(os.path.join(save_dir, name)))
        out[route] = (names, digest.hexdigest(), read(mapping).count(b"\n"))
    assert out["native"] == out["scalar"]
    assert out["native"][2] > 300_000  # multi-allelic lines are two rows
