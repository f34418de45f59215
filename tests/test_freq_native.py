"""A FREQ-bearing row's frequency sidecar is written from the chunk's INFO
spans in one native pass (PR 39).

The native engine's chunks hand the load's build stage their flagged rows'
FREQ values through ``VcfChunk.freq_values``: one ``avdb_freq_texts`` pass
(``native/freq.py``) writes each row whose text it can prove equal to
``io/vcf.py`` ``freq_sidecar``'s, and every row it declines goes through
``freq_sidecar`` itself — the definition and the oracle.  Whatever the
route, a row's value and the store's bytes must be the scalar route's."""

import json
import os
import random

import numpy as np
import pytest

from annotatedvdb_tpu import native
from annotatedvdb_tpu.io import vcf as io_vcf
from annotatedvdb_tpu.io.vcf import VcfBatchReader, freq_sidecar
from annotatedvdb_tpu.loaders import TpuVcfLoader
from annotatedvdb_tpu.native import freq as native_freq
from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore
from test_ingest_spine import FREQ_CASES

WIDTH = 49
CHUNK = 256

needs_native = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no g++)"
)


def read(path) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def native_pass(items: list) -> list:
    """The pass's outcome for each ``(info, n_alts, alt_index)``: the text,
    None, or ``"declined"``.  The INFO strings sit in one window between
    tabs, as the scanner leaves them."""
    window, off, length = b"", [], []
    for info, _, _ in items:
        raw = info.encode("utf-8")
        off.append(len(window) + 3)
        length.append(len(raw))
        window += b"\t.\t" + raw
    window += b"\t\n"
    status, texts = native_freq.freq_texts(
        window, np.array(off, np.int64), np.array(length, np.int32),
        np.array([n for _, n, _ in items], np.int32),
        np.array([a for _, _, a in items], np.int32),
    )
    assert status.shape == (len(items),)
    assert (status == native_freq.WRITTEN).sum() == len(texts)
    written = iter(texts)
    return [next(written) if s == native_freq.WRITTEN
            else None if s == native_freq.NONE else "declined"
            for s in status.tolist()]


def scalar(info: str, n_alts: int, alt_index: int):
    """``freq_sidecar``'s text for the row (what the native chunk's scalar
    route sees: the window decoded as ASCII, undecodable bytes replaced)."""
    text = info.encode("utf-8").decode("ascii", errors="replace")
    value = freq_sidecar(text, n_alts)[alt_index]
    return None if value is None else value.text


def rows_of(infos: list) -> list:
    """One ``(info, n_alts, alt_index)`` a row: every alt of each line."""
    return [(info, n, a) for info, n in infos for a in range(n)]


def assert_declined_or_equal(items: list) -> list:
    got = native_pass(items)
    for (info, n, a), g in zip(items, got):
        if g != "declined":
            assert g == scalar(info, n, a), (info, n, a)
    return got


# -- (a) the pass against freq_sidecar ---------------------------------------

#: FREQ_CASES the pass must decline: escapes, '#', a non-ASCII name, a
#: repeated population
DECLINED_CASES = {
    "FREQ=A B:0.1|dbGaP\\x2cX:0.2", "FREQ=Ké:0.25", "FREQ=X:0.1|X:0.2",
    "FREQ=GnomAD#0.3",
}


@needs_native
@pytest.mark.parametrize("info,n_alts", FREQ_CASES)
def test_freq_cases(info, n_alts):
    got = assert_declined_or_equal(rows_of([(info, n_alts)]))
    if info in DECLINED_CASES:
        assert set(got) == {"declined"}
    else:
        assert "declined" not in got


#: a value as written in a FREQ slot -> the number of its sidecar text, or
#: None where the pass declines it
VALUE_FORMS = [
    ("0.1000", "0.1"), ("1.0000", "1.0"), ("0.0000", "0.0"),
    ("-0.000", "-0.0"), ("+0.0", "0.0"), ("0.0", "0.0"), ("5.", "5.0"),
    (".5", "0.5"), ("-.5", "-0.5"), ("007", "7"), ("-007", "-7"),
    ("+5", "5"), ("-0", "0"), ("00", "0"), ("123456789012345678901",
                                           "123456789012345678901"),
    ("0.0001", "0.0001"), ("0.000123", "0.000123"),
    ("1200.00", "1200.0"), ("9999999999999990.0", "9999999999999990.0"),
    ("123456789.012345", "123456789.012345"),
    ("0.00001", None), ("0.00009", None), ("1e-05", None), ("1e16", None),
    ("1E5", None), ("10000000000000000.0", None),
    ("1234567890.123456", None), ("0.1234567890123456", None),
    ("0.12345678901234567", None), ("inf", None), ("nan", None),
    (" 1", None), ("1 ", None), ("", None), ("+", None), ("-.", None),
    ("1_0", None), ("0x10", None), ("1.2.3", None), ("١", None),
]


@needs_native
@pytest.mark.parametrize("value,text", VALUE_FORMS)
def test_value_forms(value, text):
    info = f"RS=1;FREQ=GnomAD:0.5,{value}|TOPMED:0.9,0.1"
    [got] = native_pass([(info, 1, 0)])
    if text is None:
        assert got == "declined"
    else:
        assert got == scalar(info, 1, 0) == (
            f'{{"GnomAD": {{"gmaf": {text}}}, "TOPMED": {{"gmaf": 0.1}}}}'
        )


def fuzz_value(rng: random.Random) -> str:
    kind = rng.randrange(12)
    if kind == 0:
        return rng.choice([".", "0", "", "-0", "+0", "00", "1e-05", "1e16",
                           "inf", "nan", "1E5", " 1", "0.0000", "-0.000",
                           "5.", ".5", "-.5", "+.", "9" * 17, "0.00009"])
    sign = rng.choice(["", "", "", "-", "+"])
    if kind < 4:  # an integer, maybe zero-led
        return (sign + "0" * rng.randrange(3)
                + str(rng.randrange(10 ** rng.randrange(1, 20))))
    digits = "".join(rng.choice("0123456789")
                     for _ in range(rng.randrange(1, 18)))
    cut = rng.randrange(len(digits) + 1)
    return sign + digits[:cut] + "." + digits[cut:]


def fuzz_info(rng: random.Random) -> str:
    pops = []
    for _ in range(rng.randrange(4)):
        name = rng.choice(["GnomAD", "TOPMED", "A B", "x", "", "a=b",
                           "1000G", "dup", "dup", "dbGaP_PopFreq", "p.q-r"])
        values = ",".join(fuzz_value(rng) for _ in range(rng.randrange(1, 5)))
        pops.append(f"{name}:{values}" if rng.random() > 0.05 else name)
    head = rng.choice(["", "RS=5;", "FREQ=Z:0.1,0.2;", "X#1;",
                       "A\\x2cB;", "FREQX=1;", "RSPOS=9;"])
    tail = rng.choice(["", "", ";FOO", ";FREQ=Q:.,0.5", ";FREQ=", ";"])
    return head + "FREQ=" + "|".join(pops) + tail


@needs_native
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_seeded_fuzz(seed):
    rng = random.Random(seed)
    infos = [(fuzz_info(rng), rng.randrange(1, 5)) for _ in range(2500)]
    got = assert_declined_or_equal(rows_of(infos))
    # every outcome happened, and the pass wrote most rows it could
    assert {"declined", None} <= set(got)
    assert sum(isinstance(g, str) and g != "declined" for g in got) > 500


@needs_native
def test_no_info_is_no_value_and_a_span_past_the_window_is_refused():
    got = native_pass([("FREQ=X:0.1,0.2", 1, 0), ("", 1, 0)])
    assert got == ['{"X": {"gmaf": 0.2}}', None]
    with pytest.raises(ValueError, match="outside the window"):
        native_freq.freq_texts(b"FREQ=X:0.1,0.2", np.array([4]),
                               np.array([40], np.int32), np.array([1]),
                               np.array([0]))


# -- (b) the column the build stage assembles --------------------------------


def write_vcf(path, n_lines: int = 4000, seed: int = 39) -> None:
    """A dbSNP-shaped VCF whose FREQ entries hold every form above: plain
    generator values on most lines, and, planted among them, fuzzed
    entries, multi-allelic sites, '.' alts, lines without FREQ.  ASCII
    only, so the Python engine reads the same text."""
    rng = random.Random(seed)
    bases = "ACGT"
    with open(path, "w", encoding="ascii") as fh:
        fh.write("##fileformat=VCFv4.2\n")
        fh.write("#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
        for chrom in ("1", "2", "X"):
            pos = 100
            for j in range(n_lines):
                pos += rng.randrange(1, 50)
                ref = rng.choice(bases)
                alts = [b for b in bases if b != ref]
                alt = ",".join(rng.sample(alts, rng.choice([1, 1, 1, 2, 3])))
                if j % 41 == 7:
                    alt += ",."
                info = [f"RS={j}"] if j % 3 else []
                shape = rng.random()
                if shape < 0.3:
                    f = rng.random()
                    info.append(f"FREQ=GnomAD:{1 - f:.4f},{f:.4f}")
                elif shape < 0.5:
                    info.append(fuzz_info(rng).replace("Ké", "Ke"))
                fh.write(f"{chrom}\t{pos}\trs{j}\t{ref}\t{alt}\t.\t.\t"
                         f"{';'.join(info) or '.'}\n")


@pytest.fixture(scope="module")
def vcf(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("freq") / "in.vcf")
    write_vcf(path)
    return path


@needs_native
def test_build_column_is_the_scalar_column(vcf):
    before = dict(io_vcf.freq_stats)
    chunks = list(VcfBatchReader(vcf, batch_size=CHUNK, engine="native"))
    again = list(VcfBatchReader(vcf, batch_size=CHUNK, engine="native"))
    n_flagged = 0
    for chunk, fresh in zip(chunks, again):
        rows = np.flatnonzero(chunk.has_freq)
        n_flagged += rows.size
        got = chunk.freq_values(rows)
        # the all-scalar column, from a chunk whose cache nothing filled
        want = [fresh.frequencies[i] for i in rows.tolist()]
        assert got.dtype == object and got.shape == rows.shape
        for g, w in zip(got.tolist(), want):
            assert (g is None) == (w is None)
            if w is not None:
                assert g.text == w.text
    tally = io_vcf.freq_state(before)
    assert tally["rows"] == n_flagged > 1000
    assert tally["rows"] == tally["native_rows"] + tally["scalar_rows"]
    assert tally["scalar_rows"] > 0 and tally["native_rows"] > tally["rows"] / 2


def test_python_engine_chunks_index_their_list(vcf):
    before = dict(io_vcf.freq_stats)
    [chunk, *_] = VcfBatchReader(vcf, batch_size=CHUNK, engine="python")
    rows = np.flatnonzero(chunk.has_freq)
    got = chunk.freq_values(rows)
    assert got.tolist() == [chunk.frequencies[i] for i in rows.tolist()]
    assert io_vcf.freq_state(before) == {
        "rows": rows.size, "native_rows": 0, "scalar_rows": rows.size,
    }


# -- (c) the store's bytes, whatever the route -------------------------------


def load(tmp_path, vcf, tag, monkeypatch, *, engine, route="native") -> dict:
    """One committed load; the mapping file's bytes, the store directory's
    (the manifest less its per-store uid), and the counters.  ``route``:
    ``native`` as the program runs, ``scalar`` with the pass taken away
    (every flagged row through ``freq_sidecar``)."""
    store = VariantStore(width=WIDTH)
    ledger = AlgorithmLedger(str(tmp_path / f"ledger.{tag}.jsonl"))
    loader = TpuVcfLoader(store, ledger, batch_size=CHUNK,
                          log=lambda *a: None)
    save_dir = str(tmp_path / f"vdb.{tag}")
    mapping = str(tmp_path / f"mapping.{tag}")
    with monkeypatch.context() as patch:
        patch.setenv("AVDB_INGEST_ENGINE", engine)
        if route == "scalar":
            patch.setattr(native_freq, "freq_texts", lambda *a: None)
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


@needs_native
@pytest.mark.parametrize("engine", ["native", "python"])
def test_native_route_store_is_the_scalar_route_store(tmp_path, monkeypatch,
                                                      vcf, engine):
    before = dict(io_vcf.freq_stats)
    fast = load(tmp_path, vcf, "native", monkeypatch, engine=engine)
    tally = io_vcf.freq_state(before)
    slow = load(tmp_path, vcf, "scalar", monkeypatch, engine=engine,
                route="scalar")
    assert fast.keys() == slow.keys()
    for name in slow:
        assert fast[name] == slow[name], f"{name} diverges"
    sidecars = [name for name in fast if name.endswith(".ann.jsonl")]
    assert sidecars and b'"gmaf": ' in b"".join(fast[n] for n in sidecars)
    if engine == "native":
        assert tally["native_rows"] > tally["scalar_rows"] > 0
    else:  # the Python engine's chunks index their list
        assert tally["native_rows"] == 0 < tally["scalar_rows"]


@pytest.mark.parametrize("library", [True, False], ids=["native", "bare"])
def test_freq_counter_and_sidecars_in_a_cli_load(tmp_path, monkeypatch, vcf,
                                                 library):
    """A ``load-vcf --commit`` with the library and one without it (the
    Python tokenizer, every flagged row through ``freq_sidecar``) write the
    same ``.ann.jsonl`` sidecars and ``.mapping`` file; the run record's
    ``execution.freq`` counts every flagged row once."""
    from annotatedvdb_tpu.cli import load_vcf

    if library and not native.available():
        pytest.skip("native library unavailable (no g++)")
    out = {}
    for tag in ("bare", "lib") if library else ("bare",):
        src = tmp_path / tag / "in.vcf"
        src.parent.mkdir()
        src.write_bytes(read(vcf))
        with monkeypatch.context() as patch:
            if tag == "bare":
                patch.setattr(native, "load", lambda: None)
                patch.setenv("AVDB_INGEST_ENGINE", "python")
            rc = load_vcf.main([
                "--fileName", str(src), "--storeDir", str(src.parent / "vdb"),
                "--commit", "--commitAfter", "4096",
                "--logFilePath", str(src.parent / "load.log"),
            ])
        assert rc == 0
        runs = [json.loads(line) for line in
                (src.parent / "vdb" / "ledger.jsonl").read_text().splitlines()]
        [run] = [r for r in runs if r.get("type") == "run"]
        freq = run["execution"]["freq"]
        assert freq["rows"] == freq["native_rows"] + freq["scalar_rows"] > 1000
        vdb = src.parent / "vdb"
        out[tag] = (freq, read(str(src) + ".mapping"), {
            name: read(str(vdb / name)) for name in sorted(os.listdir(vdb))
            if name.endswith(".ann.jsonl")
        })
    assert out["bare"][0]["native_rows"] == 0
    if library:
        assert out["lib"][0]["rows"] == out["bare"][0]["rows"]
        assert out["lib"][0]["native_rows"] > out["lib"][0]["scalar_rows"] > 0
        assert out["lib"][1] == out["bare"][1]
        assert out["lib"][2] and out["lib"][2] == out["bare"][2]
