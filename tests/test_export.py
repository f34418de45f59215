"""Training-corpus export battery (``annotatedvdb_tpu/export``).

The contract under test: one ``(store, plan, seed)`` triple maps to ONE
byte-exact corpus — same seed ⇒ byte-identical parts and manifest, across
re-runs, the ``host_only`` numpy twin, and a resume after a real SIGKILL
mid-part-commit — with the shuffled emission order a pure permutation of
the ``--ordered`` plan order, the ragged tail explicitly masked, the
per-chromosome allele dictionaries round-tripping to the rendered
strings, and ``GET /export/stream`` answering byte-identically on both
front ends.  The device/twin pin names and calls BOTH
``export_pack_kernel_jit`` and ``export_pack_host`` (the ops.TWINS
contract), and the ``bench.py --export`` record schema is exercised
against the strict checker.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from annotatedvdb_tpu.config import StoreConfig
from annotatedvdb_tpu.export import core as export_core
from annotatedvdb_tpu.export.core import run_export
from annotatedvdb_tpu.export.stream import emission_order
from annotatedvdb_tpu.export.writer import read_manifest, read_part
from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.types import chromosome_label, encode_allele_array
from conftest import start_server, stop_server

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

from check_bench_schema import validate_record  # noqa: E402

WIDTH = 8
CHROMS = (1, 8)
BASES = ("A", "C", "G", "T")
SEED = 3
BATCH_ROWS = 16
PART_BYTES = "2k"  # 16-row batches -> 2 batches/part -> 8 parts


def _rows_for(code: int, base_pos: int, n: int, salt: int):
    rows = []
    for i in range(n):
        k = (i + salt) % 4
        rows.append({
            "chrom": code, "pos": base_pos + 977 * i,
            "ref": BASES[k], "alt": BASES[(k + 1) % 4],
            "cadd": round(0.5 * i + code, 2) if i % 3 == 0 else None,
            "rank": (i % 30) + 1 if i % 4 == 0 else None,
            "af": round((i % 50) / 50.0, 4) if i % 2 == 0 else None,
        })
    return rows


def _build_store(store_dir: str):
    store = VariantStore(width=WIDTH)
    truth: list[dict] = []
    for code in CHROMS:
        shard = store.shard(code)
        for run, base in enumerate((500, 120_000, 2_000_000)):
            rows = _rows_for(code, base, 40, salt=run)
            refs = [r["ref"] for r in rows]
            alts = [r["alt"] for r in rows]
            ref, ref_len = encode_allele_array(refs, WIDTH)
            alt, alt_len = encode_allele_array(alts, WIDTH)
            h = identity_hashes(WIDTH, ref, alt, ref_len, alt_len,
                                refs, alts)
            shard.append(
                {"pos": np.asarray([r["pos"] for r in rows], np.int32),
                 "h": h, "ref_len": ref_len, "alt_len": alt_len},
                ref, alt,
                annotations={
                    "cadd_scores": [
                        {"CADD_phred": r["cadd"]} if r["cadd"] is not None
                        else None for r in rows
                    ],
                    "adsp_most_severe_consequence": [
                        {"conseq": "missense_variant", "rank": r["rank"]}
                        if r["rank"] is not None else None for r in rows
                    ],
                    "allele_frequencies": [
                        {"GnomAD": {"af": r["af"]}}
                        if r["af"] is not None else None for r in rows
                    ],
                },
            )
            truth.extend(rows)
    store.save(store_dir)
    return truth


def _corpus_bytes(out_dir: str) -> dict:
    out = {}
    for name in sorted(os.listdir(out_dir)):
        if name.endswith(".npz") or name == "corpus.manifest.json":
            with open(os.path.join(out_dir, name), "rb") as f:
                out[name] = f.read()
    return out


def _all_batches(out_dir: str) -> list[dict]:
    """Every committed batch across parts, in file order: one dict of
    per-batch scalars + row arrays each."""
    manifest = read_manifest(out_dir)
    batches = []
    for part in manifest["parts"]:
        arrays = read_part(os.path.join(out_dir, part["file"]))
        for b in range(arrays["n_valid"].shape[0]):
            batches.append({
                "chrom_code": int(arrays["chrom_code"][b]),
                "n_valid": int(arrays["n_valid"][b]),
                "seq": int(arrays["seq"][b]),
                **{name: arrays[name][b]
                   for name in export_core.ROW_FIELDS},
            })
    return batches


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    """(store_dir, truth, store, ledger, ref_dir): the uninterrupted
    whole-store reference export every determinism test compares to."""
    store_dir = str(tmp_path_factory.mktemp("export_store"))
    truth = _build_store(store_dir)
    store, ledger = StoreConfig(store_dir).open(create=False,
                                                readonly=True)
    ref_dir = str(tmp_path_factory.mktemp("export_ref"))
    summary = run_export(store, ledger, store_dir, ref_dir, seed=SEED,
                         batch_rows=BATCH_ROWS, part_bytes=PART_BYTES)
    assert summary["complete"] and summary["rows"] == len(truth)
    return store_dir, truth, store, ledger, ref_dir


# ---------------------------------------------------------------------------
# determinism: seed replay, host twin, shuffle-vs-ordered


def test_same_seed_rerun_byte_identical(exported, tmp_path):
    """Same (store, plan, seed) ⇒ byte-identical corpus; a different
    seed permutes emission and must change part bytes."""
    store_dir, _truth, store, ledger, ref_dir = exported
    want = _corpus_bytes(ref_dir)
    replay = str(tmp_path / "replay")
    run_export(store, ledger, store_dir, replay, seed=SEED,
               batch_rows=BATCH_ROWS, part_bytes=PART_BYTES)
    assert _corpus_bytes(replay) == want

    other = str(tmp_path / "other_seed")
    run_export(store, ledger, store_dir, other, seed=SEED + 1,
               batch_rows=BATCH_ROWS, part_bytes=PART_BYTES)
    got = _corpus_bytes(other)
    assert set(got) == set(want)  # same shape: same parts, same names
    assert any(got[n] != want[n] for n in want if n.endswith(".npz"))


def test_host_twin_corpus_byte_identical(exported, tmp_path):
    """``host_only=True`` routes every batch through the numpy twin and
    the corpus bytes must not move — the kernel/twin contract at the
    whole-subsystem level."""
    store_dir, _truth, store, ledger, ref_dir = exported
    twin = str(tmp_path / "twin")
    run_export(store, ledger, store_dir, twin, seed=SEED,
               batch_rows=BATCH_ROWS, part_bytes=PART_BYTES,
               host_only=True)
    assert _corpus_bytes(twin) == _corpus_bytes(ref_dir)


def test_export_pack_device_and_host_twins_byte_equal():
    """The ops.TWINS pin: ``export_pack_kernel_jit`` (device) and
    ``export_pack_host`` (numpy) produce byte-identical outputs, dtype
    for dtype, on a batch with a ragged tail and missing features."""
    from annotatedvdb_tpu.ops.export_pack import (
        export_pack_host,
        export_pack_kernel_jit,
    )

    B, n_valid = 32, 21
    rng = np.random.RandomState(7)
    pos = np.full(B, 1, np.int32)
    pos[:n_valid] = rng.randint(1, 2_000_000, n_valid)
    end = pos + np.where(rng.rand(B) < 0.3, 40, 0).astype(np.int32)
    ref_code = np.full(B, -1, np.int32)
    ref_code[:n_valid] = rng.randint(0, 4, n_valid)
    alt_code = np.full(B, -1, np.int32)
    alt_code[:n_valid] = rng.randint(0, 4, n_valid)
    feats = []
    for _ in range(3):
        col = np.full(B, -1, np.int32)
        present = rng.rand(n_valid) < 0.6
        col[:n_valid] = np.where(present,
                                 rng.randint(0, 10_000, n_valid), -1)
        feats.append(col)
    args = (pos, end, ref_code, alt_code, *feats, np.int32(n_valid))
    dev = [np.asarray(a) for a in export_pack_kernel_jit(*args)]
    host = [np.asarray(a) for a in export_pack_host(*args)]
    assert len(dev) == len(host) == 9
    for d, h in zip(dev, host):
        assert d.dtype == h.dtype and d.tobytes() == h.tobytes()
    # padded lanes uniformly masked: False / -1 beyond n_valid
    mask = dev[0]
    assert mask[:n_valid].all() and not mask[n_valid:].any()
    for col in dev[1:]:
        assert (col[n_valid:] == -1).all()


def test_shuffle_is_permutation_of_ordered_plan(exported, tmp_path):
    """The shuffled corpus is a pure permutation: its ``seq`` tags are
    the prefetcher's disjoint-block order (``emission_order`` replays it
    exactly), non-identity, and reordering its batches by ``seq``
    reproduces the ``--ordered`` corpus batch for batch."""
    store_dir, _truth, store, ledger, ref_dir = exported
    ordered_dir = str(tmp_path / "ordered")
    run_export(store, ledger, store_dir, ordered_dir, seed=SEED,
               batch_rows=BATCH_ROWS, part_bytes=PART_BYTES, ordered=True)
    shuffled = _all_batches(ref_dir)
    ordered = _all_batches(ordered_dir)
    assert len(shuffled) == len(ordered)
    seqs = [b["seq"] for b in shuffled]
    assert sorted(seqs) == list(range(len(ordered)))
    assert seqs != list(range(len(ordered)))  # seed 3 really permutes
    assert seqs == emission_order(len(ordered), SEED)
    assert [b["seq"] for b in ordered] == list(range(len(ordered)))
    by_seq = sorted(shuffled, key=lambda b: b["seq"])
    for got, want in zip(by_seq, ordered):
        assert got["chrom_code"] == want["chrom_code"]
        assert got["n_valid"] == want["n_valid"]
        for name in export_core.ROW_FIELDS:
            np.testing.assert_array_equal(got[name], want[name], err_msg=name)


# ---------------------------------------------------------------------------
# batch shape: ragged tail, allele dictionary


def test_ragged_tail_mask_and_padding(exported):
    """Each chromosome's last batch is ragged (120 rows into 16-row
    batches): the validity mask covers exactly ``n_valid`` rows and every
    padded lane is the -1 sentinel (empty string on the ltree path)."""
    _dir, truth, _store, _ledger, ref_dir = exported
    per_chrom = len(truth) // len(CHROMS)
    tail = per_chrom % BATCH_ROWS
    assert 0 < tail < BATCH_ROWS  # the fixture really has a ragged tail
    ragged = [b for b in _all_batches(ref_dir) if b["n_valid"] == tail]
    assert len(ragged) == len(CHROMS)
    for b in ragged:
        n = b["n_valid"]
        assert b["mask"][:n].all() and not b["mask"][n:].any()
        assert b["bin_level"][:n].min() >= 0
        for name in ("bin_level", "leaf_bin", "pos", "ref_code",
                     "alt_code", "af_fp", "cadd_fp", "rank_i"):
            assert (b[name][n:] == -1).all(), name
        assert (b["bin_index"][:n] != "").all()
        assert (b["bin_index"][n:] == "").all()


def test_allele_dict_round_trip_equals_rendered_alleles(exported):
    """Decoding every valid row's ``ref_code``/``alt_code`` through the
    manifest's per-chromosome dictionary reproduces the exact allele
    strings loaded into the store — and every truth row is present."""
    _dir, truth, _store, _ledger, ref_dir = exported
    manifest = read_manifest(ref_dir)
    want = {(r["chrom"], r["pos"]): (r["ref"], r["alt"]) for r in truth}
    seen = set()
    for b in _all_batches(ref_dir):
        alleles = manifest["alleles"][chromosome_label(b["chrom_code"])]
        for i in range(b["n_valid"]):
            key = (b["chrom_code"], int(b["pos"][i]))
            decoded = (alleles[int(b["ref_code"][i])],
                       alleles[int(b["alt_code"][i])])
            assert decoded == want[key], key
            seen.add(key)
    assert seen == set(want)


# ---------------------------------------------------------------------------
# resume after a real SIGKILL (the CLI, a subprocess, no finally blocks)


def test_resume_after_sigkill_via_cli_byte_identical(exported, tmp_path):
    """The real ``avdb export`` CLI armed ``export.commit:3:kill`` dies
    mid-part-commit (true SIGKILL: no cleanup ran), stranding a
    committed-part prefix plus tmp debris; ``--resume`` prunes the
    debris, skips the committed parts, and the final corpus — manifest
    included — is byte-identical to the uninterrupted reference."""
    store_dir, _truth, _store, _ledger, ref_dir = exported
    out_dir = str(tmp_path / "out")
    argv = [
        sys.executable, "-m", "annotatedvdb_tpu", "export",
        "--storeDir", store_dir, "--out", out_dir, "--commit",
        "--seed", str(SEED), "--batchRows", str(BATCH_ROWS),
        "--partBytes", PART_BYTES,
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               AVDB_FAULT="export.commit:3:kill")
    p = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=480)
    assert p.returncode == -signal.SIGKILL, (
        f"expected SIGKILL death, rc={p.returncode}\n{p.stderr[-2000:]}"
    )
    names = os.listdir(out_dir)
    assert any(".export.tmp" in f for f in names)
    assert "corpus.manifest.json" not in names  # manifest commits LAST

    env.pop("AVDB_FAULT")
    p = subprocess.run(argv + ["--resume"], env=env, capture_output=True,
                       text=True, timeout=480)
    assert p.returncode == 0, p.stderr[-2000:]
    summary = json.loads(p.stdout.strip().splitlines()[-1])
    assert summary["complete"] and summary["resumed_parts"] >= 1
    assert _corpus_bytes(out_dir) == _corpus_bytes(ref_dir)


# ---------------------------------------------------------------------------
# GET /export/stream: byte parity with the shared builder


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=20
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


@pytest.fixture()
def server(exported):
    store_dir, _truth, _store, _ledger, _ref = exported
    srv = start_server(store_dir=store_dir)
    try:
        yield srv
    finally:
        stop_server(srv)


def _stream_oracle(engine, query: str):
    """(status, body) from the shared grammar + builder called directly
    (``parse_stream_query``, ``stream_payload``): the 400 body is the
    error either raises."""
    from annotatedvdb_tpu.export.stream import (
        parse_stream_query,
        stream_payload,
    )

    try:
        return 200, stream_payload(engine, parse_stream_query(query))[0]
    except ValueError as err:  # QueryError subclasses ValueError
        return 400, json.dumps({"error": str(err)})


def test_export_stream_byte_parity_with_the_builder(server):
    """Oracle: ``_stream_oracle`` over a fresh engine on the same store."""
    from annotatedvdb_tpu.serve import QueryEngine

    port = server.server_address[1]
    engine = QueryEngine(server.ctx.manager, region_cache_size=0)
    queries = [
        "region=1:1-3000000&batch_rows=16&seed=3",           # shuffled
        "region=1:1-3000000&batch_rows=16&seed=3&batch=5",
        "region=1:1-3000000&batch_rows=16&ordered=1&batch=7",
        "region=chr8:100000-150000&batch_rows=8",
        "region=1:1-3000000&batch_rows=16&seed=4",           # reseeded
    ]
    for q in queries:
        st1, b1 = _get(port, f"/export/stream?{q}")
        assert st1 == 200, (q, b1)
        assert (st1, b1) == _stream_oracle(engine, q), q
        doc = json.loads(b1)
        n = doc["n_valid"]
        mask = doc["arrays"]["mask"]
        assert sum(mask) == n and all(mask[:n])
        assert doc["tokens_per_row"] == export_core.TOKENS_PER_ROW
    # kind=export counted
    _st, metrics = _get(port, "/metrics")
    assert 'avdb_query_requests_total{kind="export"}' in metrics


def test_export_stream_shuffled_batch_matches_emission_order(server):
    """The route's "seed S, batch K" is the SAME permutation the bulk
    exporter would emit: fetching shuffled slot K equals fetching plan
    batch ``emission_order(n, S)[K]`` in ordered mode, byte for byte in
    the arrays."""
    port = server.server_address[1]
    base = "region=1:1-3000000&batch_rows=16"
    _st, first = _get(port, f"/export/stream?{base}&seed=3")
    n_batches = json.loads(first)["n_batches"]
    order = emission_order(n_batches, 3)
    for k in (0, 3, n_batches - 1):
        _s1, shuffled = _get(port, f"/export/stream?{base}&seed=3&batch={k}")
        _s2, ordered = _get(
            port, f"/export/stream?{base}&ordered=1&batch={order[k]}")
        sdoc, odoc = json.loads(shuffled), json.loads(ordered)
        assert sdoc["seq"] == order[k] == odoc["batch"]
        assert sdoc["arrays"] == odoc["arrays"]
        assert sdoc["alleles"] == odoc["alleles"]


def test_export_stream_error_parity(server):
    """Oracle: ``_stream_oracle`` — every refusal is a 400 whose body is
    the grammar's or the builder's own error."""
    from annotatedvdb_tpu.serve import QueryEngine

    port = server.server_address[1]
    engine = QueryEngine(server.ctx.manager, region_cache_size=0)
    for q in (
        "",                                        # missing region
        "region=nope",                             # bad grammar
        "region=1:9-3",                            # inverted span
        "region=1:1-100&batch_rows=4",             # below the floor
        "region=1:1-100&batch_rows=99999",         # above the cap
        "region=21:1-100",                         # chromosome not in store
        "region=1:1-3000000&batch_rows=16&batch=500",  # batch out of range
    ):
        st1, b1 = _get(port, f"/export/stream?{q}")
        assert st1 == 400, (q, b1)
        assert (st1, b1) == _stream_oracle(engine, q), q


# ---------------------------------------------------------------------------
# bench --export record schema (tools/check_bench_schema.py, strict)


GOOD_EXPORT = {
    "metric": "export_tokens_per_sec",
    "value": 612000.0,
    "unit": "tokens/sec",
    "vs_baseline": 0.612,
    "backend": "cpu",
    "platform_pin": "cpu",
    "mode": "export",
    "export": {
        "rows": 120_000,
        "seed": 11,
        "batch_rows": 4096,
        "one_shot": {
            "tokens_per_sec": 612000.0, "device_idle_frac": 0.08,
            "rows": 120_000, "tokens": 960_000, "parts": 3,
            "seconds": 1.57, "complete": True,
        },
        "replay_identical": True,
        "host_twin_identical": True,
        "resume": {"killed_rc": -9, "resume_rc": 0, "identical": True},
    },
}


def test_bench_export_schema_good_record_passes():
    assert validate_record(GOOD_EXPORT) == []


@pytest.mark.parametrize("mutate,needle", [
    (lambda r: r["export"].update(replay_identical=False),
     "replay_identical"),
    (lambda r: r["export"].update(host_twin_identical=False),
     "host_twin_identical"),
    (lambda r: r["export"]["resume"].update(resume_rc=1), "resume_rc"),
    (lambda r: r["export"]["resume"].update(identical=False), "identical"),
    (lambda r: r["export"]["resume"].update(killed_rc=0),
     "SIGKILL never landed"),
    (lambda r: r.pop("export"), "export block"),
    (lambda r: r["export"]["one_shot"].update(device_idle_frac=1.4),
     "device_idle_frac"),
    (lambda r: r.update(unit="rows/sec"), "unit"),
    (lambda r: r["export"].pop("one_shot"), "one_shot"),
])
def test_bench_export_schema_catches_drift(mutate, needle):
    import copy

    bad = copy.deepcopy(GOOD_EXPORT)
    mutate(bad)
    errors = validate_record(bad)
    assert any(needle in e for e in errors), (needle, errors)


def test_bench_export_schema_errored_record_still_validates():
    """A failed bench leg records {"error": ...} instead of the export
    block — that is a VALID record (the run is evidence), not drift."""
    failed = {
        "metric": "export_tokens_per_sec", "value": 0.0,
        "unit": "tokens/sec", "vs_baseline": 0.0, "backend": "cpu",
        "mode": "export", "error": "RuntimeError: device lost",
    }
    assert validate_record(failed) == []
