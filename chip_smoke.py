#!/usr/bin/env python
"""Load -> store -> serve on the chip, through the entry points users run.

``python chip_smoke.py`` (no arguments, one chip) drives the main path once
at a size a deployment would call real and checks every answer:

1. *generate*    a seeded, position-sorted VCF (``io/synth.write_synth_vcf``,
                 2,097,152 records over chromosomes 1, 2 and 22);
2. *load*        ``load-vcf --commit`` as a child process, store width 49;
3. *compact*     ``doctor compact`` (a deployment compacts before it serves);
4. *membership*  a second ``load-vcf`` of the first eighth of the records —
                 all duplicates — so the membership probe meets a segment and
                 a query batch large enough for the device path;
5. *verify*      the committed store against the generator and the scalar
                 oracle (``annotatedvdb_tpu/oracle``), in this process;
6. *serve*       ``serve --workers 1 --hbmBudget ...`` as a child: point
                 reads, bulk lookups, a batched region panel, one
                 ``/stats/region`` call, ``/metrics``; SIGTERM; exit code 0;
7. *reference*   the same requests against a second server pinned to the
                 CPU with the device routes off (numpy probe,
                 ``interval_spans_host``, ``stats_panel_host`` — the host
                 twins of ``ops.TWINS``): every body must be byte-identical.

``--chips 4`` runs ONLY the mesh path and what it is compared with: the same
load on the 4-device mesh against a single-device load (store contents
equal), and the bulk/region requests against a mesh server and a mesh-off
server (responses byte-identical, mesh counters read from ``/metrics``).

This process never imports JAX: a chip belongs to one process at a time, so
each child gets it alone, and the device named on the last line is what the
children reported.  Every child's stdout/stderr goes to a log file under
``chiprun_out/chip_smoke/`` — never to this script's stdout, which carries
one JSON object per phase and, last, exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failed phase, or a platform other than ``tpu``, ends the run with
``"ok": false`` in the same shape and a non-zero exit code.  Wall times on
the phase lines are set-up information, not a benchmark.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

try:
    import numpy as np

    from annotatedvdb_tpu.io.synth import (
        SYNTH_FREQ_POPULATION,
        first_wins,
        write_synth_vcf,
    )
    from annotatedvdb_tpu.oracle import infer_end_location
    from annotatedvdb_tpu.oracle.binindex import (
        closed_form_bin,
        closed_form_path,
    )
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.types import DEFAULT_ALLELE_WIDTH, chromosome_code
    from annotatedvdb_tpu.utils.runtime import compile_cache_dir
except ImportError as err:  # chip_smoke.py alone proves nothing
    sys.exit(f"chip_smoke.py needs the repository around it: {err}")

#: children started by this run; all are stopped before the last line
CHILDREN: list = []

#: region/stats route thresholds that keep the reference server on the
#: host twins for every group (existing knobs; no request is that large)
HOST_TWIN_ENV = {
    "JAX_PLATFORMS": "cpu",
    "AVDB_JAX_PLATFORM": "cpu",
    "AVDB_SERVE_MESH": "0",
    "AVDB_SERVE_REGIONS_DEVICE_MIN": str(1 << 30),
    "AVDB_SERVE_STATS_DEVICE_MIN": str(1 << 30),
}


class PhaseFailed(Exception):
    """A phase did not do what it must; the run ends ``"ok": false``."""


def emit(phase: str, **fields) -> None:
    """One phase line on stdout."""
    sys.stdout.write(json.dumps({"phase": phase, **fields}) + "\n")
    sys.stdout.flush()


def note(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def tail(path: str, n: int = 2000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(max(os.path.getsize(path) - n, 0))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


# -- children -----------------------------------------------------------------


def start_child(name: str, argv: list, env: dict | None = None):
    """Start ``python -m annotatedvdb_tpu <argv>`` with stdout and stderr in
    ``<LOG_DIR>/<name>.out|.err``; returns (process, out path, err path)."""
    out_path = os.path.join(LOG_DIR, f"{name}.out")
    err_path = os.path.join(LOG_DIR, f"{name}.err")
    with open(out_path, "wb") as out, open(err_path, "wb") as err:
        proc = subprocess.Popen(
            [sys.executable, "-m", "annotatedvdb_tpu", *argv],
            stdout=out, stderr=err, stdin=subprocess.DEVNULL, cwd=ROOT,
            env=dict(os.environ, **(env or {})),
        )
    CHILDREN.append(proc)
    return proc, out_path, err_path


def run_child(name: str, argv: list, env: dict | None = None,
              timeout: float = 900.0):
    """Run a child to its end; a non-zero exit fails the phase.  Returns
    (wall seconds, stdout path)."""
    t0 = time.monotonic()
    proc, out_path, err_path = start_child(name, argv, env)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise PhaseFailed(f"{name}: no exit within {timeout:.0f}s\n"
                          f"{tail(err_path)}") from None
    if rc != 0:
        raise PhaseFailed(f"{name}: exit code {rc}\n{tail(err_path)}")
    return time.monotonic() - t0, out_path


def stop_children() -> None:
    for proc in CHILDREN:
        if proc.poll() is None:
            proc.terminate()
    for proc in CHILDREN:
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def last_run_record(store_dir: str) -> dict:
    """The newest ``type: "run"`` record of the store's ledger — the load
    CLI's machine-readable summary (``obs.session.run_record``)."""
    record = None
    with open(os.path.join(store_dir, "ledger.jsonl")) as f:
        for line in f:
            entry = json.loads(line)
            if entry.get("type") == "run":
                record = entry
    if record is None or record.get("status") != "completed" \
            or "execution" not in record:
        raise PhaseFailed(f"{store_dir}: no completed run record with an "
                          "execution block in the ledger")
    return record


def load_vcf(name: str, vcf: str, store_dir: str, extra=()) -> dict:
    """One ``load-vcf --commit`` child; returns its summary."""
    seconds, _out = run_child(name, [
        "load-vcf", "--fileName", vcf, "--storeDir", store_dir, "--commit",
        "--logFilePath", os.path.join(LOG_DIR, f"{name}.log"), *extra,
    ])
    record = last_run_record(store_dir)
    record["child_seconds"] = round(seconds, 2)
    return record


# -- the generator's expectations ---------------------------------------------


class Expected:
    """What a first-wins load of the generated rows must hold."""

    def __init__(self, rows: dict, chromosomes: tuple):
        self.rows = rows
        self.chromosomes = chromosomes
        keep = first_wins(rows)
        self.n_rows = int(keep.sum())
        self.kept = {k: v[keep] for k, v in rows.items()}
        #: per chromosome: kept positions (sorted — the file is) and
        #: whether each kept row carries a FREQ value
        self.pos, self.has_freq = {}, {}
        for ci, label in enumerate(chromosomes):
            m = self.kept["chrom"] == ci
            self.pos[label] = self.kept["pos"][m]
            self.has_freq[label] = ~np.isnan(self.kept["freq"][m])

    def ident(self, i: int) -> str:
        """``chr:pos:ref:alt`` of kept row ``i``."""
        k = self.kept
        return (f"{self.chromosomes[k['chrom'][i]]}:{k['pos'][i]}:"
                f"{k['ref'][i].decode()}:{k['alt'][i].decode()}")

    def count(self, label: str, start: int, end: int,
              freq_only: bool = False) -> int:
        pos = self.pos[label]
        lo = np.searchsorted(pos, start, side="left")
        hi = np.searchsorted(pos, end, side="right")
        if freq_only:
            return int(self.has_freq[label][lo:hi].sum())
        return int(hi - lo)


def fnv1a(ref_len, alt_len, ref, alt) -> np.ndarray:
    """The identity hash as ``ops/hashing`` documents it, written out
    again here: 32-bit FNV-1a over (ref_len, alt_len, the zero-padded ref
    bytes, the zero-padded alt bytes)."""
    h = np.full(ref.shape[0], 2166136261, np.uint32)
    columns = [ref_len & 0xFF, alt_len & 0xFF, *ref.T, *alt.T]
    for byte in columns:
        h = (h ^ byte.astype(np.uint32)) * np.uint32(16777619)
    return h


def padded(alleles: np.ndarray, width: int) -> np.ndarray:
    """``S8`` alleles -> [n, width] uint8, zero-padded."""
    out = np.zeros((alleles.shape[0], width), np.uint8)
    raw = np.frombuffer(alleles.tobytes(), np.uint8).reshape(-1, 8)
    out[:, :8] = raw
    return out


def verify_store(store_dir: str, exp: Expected, sample: int,
                 seed: int) -> dict:
    """The committed store against the generator (every row's identity,
    rs number and multi-allelic flag) and the scalar oracle (a seeded
    sample, field by field).  Numpy and the oracle only."""
    store = VariantStore.load(store_dir, readonly=True)
    width = store.width
    if width != DEFAULT_ALLELE_WIDTH:
        raise PhaseFailed(f"store width {width}, every deployment uses "
                          f"{DEFAULT_ALLELE_WIDTH}")
    mismatches: list = []
    located = {}
    for ci, label in enumerate(exp.chromosomes):
        m = exp.kept["chrom"] == ci
        shard = store.shards.get(chromosome_code(label))
        n_want = int(m.sum())
        if shard is None or shard.n != n_want:
            raise PhaseFailed(
                f"chr{label}: {0 if shard is None else shard.n} rows "
                f"stored, {n_want} expected"
            )
        ref = padded(exp.kept["ref"][m], width)
        alt = padded(exp.kept["alt"][m], width)
        ref_len = np.char.str_len(exp.kept["ref"][m]).astype(np.int32)
        alt_len = np.char.str_len(exp.kept["alt"][m]).astype(np.int32)
        h = fnv1a(ref_len, alt_len, ref, alt)
        # the store orders a chromosome by (pos, hash); so does this
        order = np.lexsort((h, exp.kept["pos"][m]))
        shard.compact()  # in memory: one (pos, hash)-sorted segment
        seg = shard.segments[0]
        want = {
            "pos": exp.kept["pos"][m][order], "h": h[order],
            "ref_len": ref_len[order], "alt_len": alt_len[order],
            "ref_snp": exp.kept["rs"][m][order],
            "is_multi_allelic": exp.kept["multi"][m][order],
        }
        for name, col in want.items():
            bad = np.flatnonzero(seg.cols[name] != col)
            if bad.size:
                mismatches.append(
                    f"chr{label} {name}: {bad.size} rows differ, first at "
                    f"sorted row {int(bad[0])}: stored "
                    f"{seg.cols[name][bad[0]]!r}, expected {col[bad[0]]!r}"
                )
        for name, got, col in (("ref", seg.ref, ref[order]),
                               ("alt", seg.alt, alt[order])):
            bad = np.flatnonzero((got != col).any(axis=1))
            if bad.size:
                mismatches.append(f"chr{label} {name}: {bad.size} rows "
                                  f"differ, first at {int(bad[0])}")
        located[label] = (seg, np.flatnonzero(m)[order], shard)
    if mismatches:
        raise PhaseFailed("store differs from the generator:\n"
                          + "\n".join(mismatches[:10]))

    # the oracle sample: computed fields, row by row
    rng = np.random.default_rng(seed + 1)
    n_sampled = 0
    for label, (seg, kept_index, shard) in located.items():
        take = rng.choice(
            seg.n, size=min(seg.n, -(-sample // len(located))),
            replace=False,
        )
        for j in take.tolist():
            i = int(kept_index[j])
            pos = int(exp.kept["pos"][i])
            ref = exp.kept["ref"][i].decode()
            alt = exp.kept["alt"][i].decode()
            level, leaf = closed_form_bin(
                pos, infer_end_location(ref, alt, pos)
            )
            freq = exp.kept["freq"][i]
            want = {
                "bin_level": level, "leaf_bin": leaf,
                "needs_digest": len(ref) + len(alt) > 50,
                "allele_frequencies": None if np.isnan(freq) else
                {SYNTH_FREQ_POPULATION: {"gmaf": float(freq)}},
            }
            got = {
                "bin_level": int(seg.cols["bin_level"][j]),
                "leaf_bin": int(seg.cols["leaf_bin"][j]),
                "needs_digest": bool(seg.cols["needs_digest"][j]),
                "allele_frequencies": shard.get_ann("allele_frequencies", j),
            }
            if got["allele_frequencies"] is not None:
                got["allele_frequencies"] = dict(got["allele_frequencies"])
            for name in want:
                if got[name] != want[name]:
                    mismatches.append(
                        f"{label}:{pos}:{ref}:{alt} {name}: stored "
                        f"{got[name]!r}, oracle {want[name]!r}"
                    )
        n_sampled += int(take.size)
    if mismatches:
        raise PhaseFailed("store differs from the oracle:\n"
                          + "\n".join(mismatches[:10]))
    return {"rows_stored": store.n, "rows_expected": exp.n_rows,
            "rows_compared": store.n, "oracle_sampled": n_sampled,
            "mismatches": 0, "store_width": width}


def stores_equal(dir_a: str, dir_b: str) -> dict:
    """Two committed stores hold the same rows: every numeric column, both
    allele matrices and every annotation column, chromosome by chromosome
    (compacted in memory, so segment layout does not matter)."""
    a = VariantStore.load(dir_a, readonly=True)
    b = VariantStore.load(dir_b, readonly=True)
    if sorted(a.shards) != sorted(b.shards):
        raise PhaseFailed(f"shards differ: {sorted(a.shards)} vs "
                          f"{sorted(b.shards)}")
    columns = 0
    for code in sorted(a.shards):
        sa, sb = a.shards[code], b.shards[code]
        sa.compact()
        sb.compact()
        if sa.n != sb.n:
            raise PhaseFailed(f"chromosome {code}: {sa.n} vs {sb.n} rows")
        ga, gb = sa.segments[0], sb.segments[0]
        for name in ga.cols:
            if name == "row_algorithm_id":
                continue  # the two loads are two invocations
            if not np.array_equal(ga.cols[name], gb.cols[name]):
                raise PhaseFailed(f"chromosome {code}: column {name} "
                                  "differs between the two loads")
            columns += 1
        if not (np.array_equal(ga.ref, gb.ref)
                and np.array_equal(ga.alt, gb.alt)):
            raise PhaseFailed(f"chromosome {code}: alleles differ")
        for name in ga.obj:
            columns += 1
            if ga.obj[name] is None and gb.obj[name] is None:
                continue  # the column holds no value in either store
            ca = ga.obj_dense(name).tolist()
            cb = gb.obj_dense(name).tolist()
            same = all(
                (x is None and y is None)
                or (x is not None and y is not None
                    and json.dumps(_plain(x), sort_keys=True)
                    == json.dumps(_plain(y), sort_keys=True))
                for x, y in zip(ca, cb)
            )
            if not same:
                raise PhaseFailed(f"chromosome {code}: annotation column "
                                  f"{name} differs between the two loads")
    return {"rows": a.n, "columns_compared": columns, "mismatches": 0}


def _plain(value):
    """A stored annotation value as plain JSON data."""
    fresh = getattr(value, "fresh", None)
    return fresh() if fresh is not None else value


# -- requests -----------------------------------------------------------------


def build_requests(exp: Expected, seed: int, chips: int) -> list:
    """The request list, deterministic in ``seed``: (key, method, path,
    body, rows).  ``rows`` is what the generator says about the ids a
    request names, in order: a kept-row index, or None for an id that was
    never generated."""
    rng = np.random.default_rng(seed + 2)
    n_kept = exp.n_rows

    def present(n):
        picks = rng.choice(n_kept, size=min(n, n_kept), replace=False)
        return [(exp.ident(i), i) for i in picks.tolist()]

    def absent(n):
        # a kept row's alleles one base to the right of the last position
        # of its chromosome block: never generated
        out = []
        for i in rng.choice(n_kept, size=n, replace=False).tolist():
            label = exp.chromosomes[exp.kept["chrom"][i]]
            pos = int(exp.pos[label][-1]) + 1 + len(out)
            out.append((f"{label}:{pos}:{exp.kept['ref'][i].decode()}:"
                        f"{exp.kept['alt'][i].decode()}", None))
        return out

    def intervals(n, lo_width, hi_width):
        specs = []
        for k in range(n):
            label = exp.chromosomes[k % len(exp.chromosomes)]
            pos = exp.pos[label]
            start = int(rng.integers(int(pos[0]), int(pos[-1])))
            specs.append(
                f"{label}:{start}-"
                f"{start + int(rng.integers(lo_width, hi_width))}"
            )
        return specs

    # a bulk lookup first: it is what makes the residency manager upload
    # the segments, so the point reads after it meet resident ones too
    requests = []
    for k in range(3):
        ids = present(4352) + absent(256)
        rng.shuffle(ids)
        requests.append((f"bulk {k}", "POST", "/variants",
                         {"ids": [ident for ident, _ in ids]},
                         [i for _, i in ids]))
        if chips == 1 and k == 0:
            for ident, i in present(300) + absent(20):
                requests.append((f"point {ident}", "GET",
                                 f"/variant/{ident}", None, [i]))
    panel = intervals(384, 30, 150)
    requests.append(("regions rows", "POST", "/regions",
                     {"regions": panel, "limit": 25}, None))
    requests.append(("regions count", "POST", "/regions",
                     {"regions": panel, "limit": 0, "tokenize": True}, None))
    if chips == 1:
        requests.append(("stats", "POST", "/stats/region",
                         {"regions": intervals(96, 2_000, 20_000)}, None))
    return requests


class Server:
    """One ``serve`` child and a keep-alive connection to it."""

    def __init__(self, name: str, store_dir: str, extra=(),
                 env: dict | None = None):
        self.name = name
        t0 = time.monotonic()
        self.proc, out_path, self.err_path = start_child(
            name, ["serve", "--storeDir", store_dir, "--port", "0",
                   "--workers", "1", *extra], env,
        )
        address = None
        while address is None:
            if self.proc.poll() is not None:
                raise PhaseFailed(f"{name}: exited with code "
                                  f"{self.proc.returncode} before serving\n"
                                  f"{tail(self.err_path)}")
            if time.monotonic() - t0 > 600:
                raise PhaseFailed(f"{name}: no address line in 600s\n"
                                  f"{tail(self.err_path)}")
            with open(out_path) as f:
                address = re.search(r"on http://([\d.]+):(\d+)", f.read())
            if address is None:
                time.sleep(0.2)
        self.startup_seconds = round(time.monotonic() - t0, 2)
        self.conn = http.client.HTTPConnection(
            address.group(1), int(address.group(2)), timeout=600
        )

    def call(self, method: str, path: str, body=None):
        """(status, body bytes)."""
        payload = None if body is None else json.dumps(body)
        headers = {} if body is None else {
            "Content-Type": "application/json"
        }
        try:
            self.conn.request(method, path, body=payload, headers=headers)
            response = self.conn.getresponse()
            return response.status, response.read()
        except (OSError, http.client.HTTPException) as err:
            raise PhaseFailed(f"{self.name}: {method} {path} failed: "
                              f"{err!r}\n{tail(self.err_path)}") from None

    def json(self, path: str) -> dict:
        status, body = self.call("GET", path)
        if status != 200:
            raise PhaseFailed(f"{self.name}: GET {path} -> {status}")
        return json.loads(body)

    def metrics(self) -> dict:
        """``/metrics`` as {series (name + label text): value}."""
        status, body = self.call("GET", "/metrics")
        if status != 200:
            raise PhaseFailed(f"{self.name}: GET /metrics -> {status}")
        out = {}
        for line in body.decode().splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                out[series] = float(value)
        return out

    def stop(self) -> None:
        """SIGTERM, wait; the exit code must be 0."""
        self.conn.close()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise PhaseFailed(f"{self.name}: did not stop within 60s of "
                              "SIGTERM") from None
        if rc != 0:
            raise PhaseFailed(f"{self.name}: exit code {rc} after SIGTERM\n"
                              f"{tail(self.err_path)}")


def drive(server: Server, requests: list, wait_resident: bool) -> dict:
    """Send every request in order; {key: (status, body)}.  With
    ``wait_resident`` the first bulk lookup is followed by a wait for the
    residency manager's (asynchronous) upload, so the later lookups meet
    device-resident segments."""
    answers = {}
    waited = False
    for key, method, path, body, _rows in requests:
        answers[key] = server.call(method, path, body)
        if wait_resident and not waited and key.startswith("bulk"):
            waited = True
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and not server.metrics().get(
                    "avdb_serve_residency_uploads_total"):
                time.sleep(0.25)
    return answers


def check_answers(exp: Expected, requests: list, answers: dict) -> dict:
    """Every answer against the generator: which ids exist (and their rs
    numbers and bin paths, from the oracle), how many rows each interval
    holds, how many of them carry a frequency."""
    kept = exp.kept
    counts = {"point": 0, "bulk_ids": 0, "intervals": 0, "stats": 0}

    def check_record(ident: str, i, rec, where: str):
        if (rec is None) != (i is None):
            raise PhaseFailed(f"{where}: {ident} "
                              f"{'missing' if rec is None else 'invented'}")
        if rec is None:
            return
        label, pos, ref, alt = ident.split(":")
        level, leaf = closed_form_bin(
            int(pos), infer_end_location(ref, alt, int(pos))
        )
        want = {
            "metaseq_id": ident, "position": int(pos), "ref": ref,
            "alt": alt, "chromosome": label,
            "ref_snp": f"rs{int(kept['rs'][i])}",
            "is_multi_allelic": bool(kept["multi"][i]),
            "bin_index": closed_form_path(label, level, leaf),
        }
        for name, value in want.items():
            if rec.get(name) != value:
                raise PhaseFailed(f"{where}: {ident} {name} is "
                                  f"{rec.get(name)!r}, expected {value!r}")

    for key, _method, _path, body, rows in requests:
        status, raw = answers[key]
        if key.startswith("point"):
            if status not in (200, 404):
                raise PhaseFailed(f"{key}: status {status}")
            check_record(key.split(" ", 1)[1], rows[0],
                         json.loads(raw) if status == 200 else None, key)
            counts["point"] += 1
            continue
        if status != 200:
            raise PhaseFailed(f"{key}: status {status}: {raw[:300]!r}")
        doc = json.loads(raw)
        if key.startswith("bulk"):
            if doc["n"] != len(body["ids"]):
                raise PhaseFailed(f"{key}: n={doc['n']}")
            for ident, i, rec in zip(body["ids"], rows, doc["results"]):
                check_record(ident, i, rec, key)
            counts["bulk_ids"] += doc["n"]
        else:
            for spec, res in zip(body["regions"], doc["results"]):
                label, span = spec.split(":")
                start, end = (int(x) for x in span.split("-"))
                want = exp.count(label, start, end)
                if res["region"] != spec or res["count"] != want:
                    raise PhaseFailed(
                        f"{key}: {spec} count {res.get('count')}, the "
                        f"generator has {want}"
                    )
                if key == "stats":
                    present = exp.count(label, start, end, freq_only=True)
                    if res["af"]["present"] != present:
                        raise PhaseFailed(
                            f"{key}: {spec} af.present "
                            f"{res['af']['present']}, expected {present}"
                        )
                elif "returned" in res and res["returned"] != min(
                        want, body["limit"]):
                    raise PhaseFailed(f"{key}: {spec} returned "
                                      f"{res['returned']}")
            which = "stats" if key == "stats" else "intervals"
            counts[which] += len(body["regions"])
    return counts


def compare_answers(a: dict, b: dict, what: str) -> int:
    """Byte-for-byte equality of two answer sets; returns how many."""
    for key in a:
        if a[key] != b[key]:
            sa, ba = a[key]
            sb, bb = b[key]
            at = next((i for i, (x, y) in enumerate(zip(ba, bb)) if x != y),
                      min(len(ba), len(bb)))
            raise PhaseFailed(
                f"{key}: {what} differ (status {sa} vs {sb}, lengths "
                f"{len(ba)} vs {len(bb)}, first difference at byte {at}: "
                f"{ba[max(at - 60, 0):at + 60]!r} vs "
                f"{bb[max(at - 60, 0):at + 60]!r})"
            )
    return len(a)


# -- the two runs -------------------------------------------------------------


def load_line(record: dict) -> dict:
    """The fields of a load child's summary that a phase line carries."""
    ex = record["execution"]
    return {
        "device": ex["device"],
        "kernel": ex["kernel"],
        "native": bool(ex["native_ingest"]["loaded"]),
        "pack_transport": ex["pack_transport"],
        "device_lookup": ex["device_lookup"],
        "compile_seconds": ex["compile"]["seconds"],
        "compiled_programs": ex["compile"]["programs"],
        "compile_cache_hits": ex["compile"]["cache_hits"],
        "counters": {k: v for k, v in record["counters"].items()
                     if k != "alg_id"},
        "load_seconds": record["wall_seconds"],
        "child_seconds": record["child_seconds"],
    }


def require_native(line: dict, record: dict) -> None:
    if not line["native"]:
        raise PhaseFailed(
            "the load ran the Python tokenizer: native library not loaded "
            f"({record['execution']['native_ingest']['error']})"
        )


def generate(args, work: str, chromosomes: tuple):
    """The *generate* phase: (VCF path, what a load of it must hold)."""
    t0 = time.monotonic()
    vcf = os.path.join(work, "synth.vcf")
    exp = Expected(write_synth_vcf(vcf, args.rows, args.seed, chromosomes),
                   chromosomes)
    emit("generate", records=args.rows, rows=int(exp.rows["pos"].size),
         rows_expected=exp.n_rows, chromosomes=list(chromosomes),
         vcf_bytes=os.path.getsize(vcf), seed=args.seed,
         compile_cache_dir=compile_cache_dir(),
         seconds=round(time.monotonic() - t0, 2))
    return vcf, exp


def compact(store: str) -> None:
    """The *compact* phase: ``doctor compact`` as a child."""
    _seconds, out_path = run_child(
        "compact", ["doctor", "compact", "--storeDir", store, "--json"]
    )
    with open(out_path) as f:
        report = json.load(f)
    # "noop" is what a store small enough for one segment per chromosome
    # gets (a reduced --rows); at the real size every group compacts
    if report["status"] not in ("compacted", "noop") \
            or report["rows_dropped"] != 0:
        raise PhaseFailed(f"doctor compact: {report}")
    report.pop("plan", None)
    emit("compact", **report)


def one_chip(args, work: str, gates: dict, devices: list) -> None:
    vcf, exp = generate(args, work, ("1", "2", "22"))

    store = os.path.join(work, "vdb")
    record = load_vcf("load", vcf, store)
    line = load_line(record)
    devices.append(line["device"])
    stored = record["counters"]["variant"]
    if stored != exp.n_rows:
        raise PhaseFailed(f"load stored {stored} rows, the generator's "
                          f"first-wins dedup expects {exp.n_rows}")
    require_native(line, record)
    gates["kernel_pallas"] = line["kernel"] == "pallas"
    gates["packed_transport"] = bool(
        line["pack_transport"]["outputs_verified"]
        and line["pack_transport"]["nibble_verified"]
    )
    emit("load", records=args.rows, rows_stored=stored,
         rows_expected=exp.n_rows, **line)

    compact(store)

    # the membership probe: the first eighth of the records again (262,144
    # at the real size), every row a duplicate of a stored one
    n_head = args.rows // 8
    head = os.path.join(work, "head.vcf")
    with open(vcf) as src, open(head, "w") as dst:
        for k, text in enumerate(src):
            if k >= n_head + 2:  # two header lines
                break
            dst.write(text)
    head_rows = int((exp.rows["line"] < n_head).sum())
    record = load_vcf("membership", head, store)
    line = load_line(record)
    devices.append(line["device"])
    counters = record["counters"]
    if counters["variant"] != 0 or counters["duplicates"] != head_rows:
        raise PhaseFailed(
            f"membership: {counters['variant']} rows inserted and "
            f"{counters['duplicates']} duplicates found; all {head_rows} "
            "rows are already stored"
        )
    lookup = line["device_lookup"]
    gates["device_probe"] = lookup["device_probes"] > 0
    if gates["device_probe"]:
        why = None
    elif lookup["enabled"] is False:
        why = "cpu backend: the store never probes on the device there"
    elif lookup["transfer_fast"] is False:
        why = ("the measured upload rate is below DEVICE_MIN_BANDWIDTH "
               "(store/variant_store.py), so the rule kept probes on the host")
    else:
        why = ("no probe met a segment >= DEVICE_SEGMENT_MIN with >= "
               "DEVICE_QUERY_MIN queries and enough accumulated volume")
    emit("membership", records=n_head, duplicates=counters["duplicates"],
         duplicates_expected=head_rows, kept_on_host_because=why, **line)

    t0 = time.monotonic()
    emit("verify", **verify_store(store, exp, 8192, args.seed),
         seconds=round(time.monotonic() - t0, 2))

    requests = build_requests(exp, args.seed, chips=1)
    server = Server("serve", store, ["--hbmBudget", "2g"])
    stats = server.json("/stats")
    devices.append(stats["device"])
    t0 = time.monotonic()
    answers = drive(server, requests,
                    wait_resident=stats["device"]["platform"] != "cpu")
    seconds = round(time.monotonic() - t0, 2)
    metrics = server.metrics()
    stats = server.json("/stats")
    server.stop()
    checked = check_answers(exp, requests, answers)
    uploads = int(metrics.get("avdb_serve_residency_uploads_total", 0))
    resident = int(metrics.get("avdb_serve_resident_bytes", 0))
    trips = int(metrics.get("avdb_serve_breaker_trips_total", 0))
    gates["residency_uploads"] = uploads > 0
    gates["resident_bytes"] = resident > 0
    gates["no_breaker_trips"] = trips == 0
    emit("serve", requests=len(requests), **checked, mismatches=0,
         device=stats["device"], residency_uploads=uploads,
         resident_bytes=resident, breaker_trips=trips,
         residency=stats.get("residency"),
         device_lookup=stats["device_lookup"],
         compile_seconds=stats["compile"]["seconds"],
         compiled_programs=stats["compile"]["programs"],
         compile_cache_hits=stats["compile"]["cache_hits"],
         startup_seconds=server.startup_seconds, request_seconds=seconds,
         exit_code=0)

    reference = Server("reference", store, env=HOST_TWIN_ENV)
    ref_device = reference.json("/stats")["device"]
    twin = drive(reference, requests, wait_resident=False)
    reference.stop()
    emit("reference", device=ref_device, routes="host twins",
         compared=compare_answers(answers, twin, "chip and host-twin bodies"),
         mismatches=0)


def four_chips(args, work: str, gates: dict, devices: list) -> None:
    # one chromosome on each device of chromosome_placement(4)
    vcf, exp = generate(args, work, ("1", "2", "3", "22"))

    mesh_store = os.path.join(work, "vdb_mesh")
    record = load_vcf("load_mesh", vcf, mesh_store)
    line = load_line(record)
    devices.append(line["device"])
    require_native(line, record)
    with open(os.path.join(LOG_DIR, "load_mesh.err")) as f:
        fanout = re.search(r"annotating across (\d+) devices", f.read())
    gates["load_on_4_devices"] = bool(fanout and fanout.group(1) == "4")
    emit("load_mesh", records=args.rows,
         rows_stored=record["counters"]["variant"],
         rows_expected=exp.n_rows,
         annotate_devices=int(fanout.group(1)) if fanout else 1, **line)

    single_store = os.path.join(work, "vdb_single")
    record = load_vcf("load_single", vcf, single_store,
                      ["--maxWorkers", "off"])
    line = load_line(record)
    devices.append(line["device"])
    emit("load_single", records=args.rows,
         rows_stored=record["counters"]["variant"],
         rows_expected=exp.n_rows, annotate_devices=1, **line)
    if record["counters"]["variant"] != exp.n_rows:
        raise PhaseFailed("single-device load stored "
                          f"{record['counters']['variant']} rows, expected "
                          f"{exp.n_rows}")
    emit("stores_equal", **stores_equal(mesh_store, single_store))

    compact(mesh_store)
    requests = build_requests(exp, args.seed, chips=4)
    server = Server("serve_mesh", mesh_store, ["--hbmBudget", "8g"])
    stats = server.json("/stats")
    devices.append(stats["device"])
    answers = drive(server, requests, wait_resident=False)
    metrics = server.metrics()
    stats = server.json("/stats")
    server.stop()
    checked = check_answers(exp, requests, answers)
    mesh = stats.get("mesh") or {}
    per_device = mesh.get("per_device_bytes") or {}
    dispatch = {
        kind: int(metrics.get(
            f'avdb_mesh_dispatch_total{{kind="{kind}"}}', 0))
        for kind in ("bulk", "spans")
    }
    fallback = sum(int(v) for k, v in metrics.items()
                   if k.startswith("avdb_mesh_fallback_total"))
    gates["mesh_devices_4"] = int(metrics.get("avdb_mesh_devices", 0)) == 4
    gates["mesh_dispatched"] = all(v > 0 for v in dispatch.values())
    gates["no_mesh_fallback"] = fallback == 0
    gates["resident_on_every_device"] = (
        len(per_device) == 4 and all(v > 0 for v in per_device.values())
    )
    gates["no_breaker_trips"] = int(
        metrics.get("avdb_serve_breaker_trips_total", 0)) == 0
    emit("serve_mesh", requests=len(requests), **checked, mismatches=0,
         device=stats["device"],
         mesh_devices=int(metrics.get("avdb_mesh_devices", 0)),
         mesh_dispatch=dispatch, mesh_fallback=fallback,
         per_device_bytes=per_device,
         mesh_resident_bytes=mesh.get("resident_bytes"),
         compile_seconds=stats["compile"]["seconds"],
         startup_seconds=server.startup_seconds, exit_code=0)

    single = Server("serve_single", mesh_store, ["--hbmBudget", "8g"],
                    env={"AVDB_SERVE_MESH": "0"})
    single_stats = single.json("/stats")
    devices.append(single_stats["device"])
    plain = drive(single, requests, wait_resident=False)
    single_metrics = single.metrics()
    single.stop()
    emit("serve_single", device=single_stats["device"],
         mesh_devices=int(single_metrics.get("avdb_mesh_devices", 0)),
         compared=compare_answers(answers, plain,
                                  "mesh and single-device bodies"),
         mismatches=0)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the generated data (default 0)")
    parser.add_argument("--rows", type=int, default=1 << 21,
                        help="VCF records to generate and load (default "
                             "2,097,152 — the real size)")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the mesh path and its "
                             "single-device comparison (default 1)")
    args = parser.parse_args(argv)
    if args.rows < 4096:
        parser.error("--rows must be at least 4096")

    shutil.rmtree(LOG_DIR, ignore_errors=True)
    os.makedirs(LOG_DIR)
    work = tempfile.mkdtemp(prefix="avdb_chip_smoke_")
    gates: dict = {}
    devices: list = []
    failure = None
    try:
        (one_chip if args.chips == 1 else four_chips)(
            args, work, gates, devices
        )
    except PhaseFailed as err:
        failure = str(err)
    finally:
        stop_children()
        shutil.rmtree(work, ignore_errors=True)

    device = devices[0] if devices else {
        "platform": None, "kind": None, "count": 0,
    }
    if failure is None and any(d != device for d in devices):
        failure = f"the children ran on different devices: {devices}"
    if failure is None and device["platform"] != "tpu":
        failure = f"the children ran on {device['platform']!r}, not a tpu"
    if failure is None and device["count"] != args.chips:
        failure = f"{device['count']} devices, --chips {args.chips}"
    missed = sorted(name for name, met in gates.items() if not met)
    if failure is None and missed:
        failure = f"device evidence missing: {', '.join(missed)}"
    if failure is not None:
        note(f"FAILED: {failure}")
    emit("summary", gates=gates, failed=failure is not None)
    ok = failure is None
    sys.stdout.write(json.dumps({
        "ok": ok,
        "device": {
            "platform": device["platform"],
            "kind": device["kind"],
            "count": device["count"],
        },
    }) + "\n")
    sys.stdout.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
