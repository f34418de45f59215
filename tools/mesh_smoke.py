#!/usr/bin/env python
"""Mesh smoke-check (~15s): forced 4-device host mesh → sharded load →
fleet serve with the mesh execution path forced on → byte-verify every
query shape against the single-device answers.

The end-to-end path under test is the PR's whole tentpole in one breath:

1. a VCF loads through ``TpuVcfLoader`` with the global mesh resolved
   from ``AVDB_MESH_SHAPE=4`` (sharded annotate/hash/dedup; the manifest
   records the placement block) — load-vs-single-device byte parity
   itself is pinned by ``tests/test_mesh.py`` and
   ``tests/test_distributed_load.py``, so the smoke spends its budget on
   the serving half;
2. a REAL 2-worker serve fleet (subprocess CLI, aio front end) starts
   over that store with ``AVDB_SERVE_MESH=1`` — bulk lookups and region
   panels run as ONE sharded call each over the workers' 4-device host
   mesh;
3. point / bulk / region / regions responses from the fleet are compared
   byte-for-byte against a mesh-off in-process reference server over the
   same store (the single-device path).

Part of ``tools/run_checks.sh`` (tier-1 shells that script).  Exit codes:
0 clean, 1 smoke failure, 2 internal error.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import urllib.error
import urllib.request

# pin a 4-virtual-device CPU platform before anything imports jax (the
# mesh needs its devices before backend init)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("AVDB_JAX_PLATFORM", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()
os.environ["AVDB_MESH_SHAPE"] = "4"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# persistent XLA compilation cache, shared by this process AND the fleet
# workers (they inherit the environment): the sharded serve programs cost
# ~10s of compile each, and without the cache BOTH workers pay it on
# their first request — with it, the warmup request below compiles once
# and every later first-touch (second worker, smoke re-runs) loads from
# disk.  Content-keyed, so a stale entry can never serve wrong code.
from annotatedvdb_tpu.utils.runtime import ensure_compile_cache  # noqa: E402

ensure_compile_cache()


def log(msg: str) -> None:
    print(f"mesh_smoke: {msg}", file=sys.stderr)


def write_vcf(path: str) -> int:
    import numpy as np

    rng = np.random.default_rng(17)
    bases = "ACGT"
    lines = ["##fileformat=VCFv4.2",
             "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO"]
    n = 0
    for chrom in ("1", "8", "X"):
        pos = 500
        for i in range(120):
            pos += int(rng.integers(1, 800))
            ref = bases[int(rng.integers(0, 4))]
            alt = bases[(bases.index(ref) + 1 + int(rng.integers(0, 3))) % 4]
            if alt == ref:
                alt = bases[(bases.index(ref) + 1) % 4]
            lines.append(f"{chrom}\t{pos}\trs{n}\t{ref}\t{alt}\t.\t.\tRS={n}")
            n += 1
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return n


def load_store(vcf: str, store_dir: str, mesh) -> None:
    from annotatedvdb_tpu.loaders.vcf_loader import TpuVcfLoader
    from annotatedvdb_tpu.store import AlgorithmLedger, VariantStore

    store = VariantStore(width=16)
    ledger = AlgorithmLedger(os.path.join(
        os.path.dirname(store_dir), f"ledger_{os.path.basename(store_dir)}.jsonl"
    ))
    loader = TpuVcfLoader(store, ledger, mesh=mesh, batch_size=256,
                          log=lambda *a: None)
    loader.load_file(vcf, commit=True)
    store.save(store_dir)


def spawn_fleet(store_dir: str, env_extra: dict):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("AVDB_FAULT", None)
    env.update(env_extra)
    argv = [sys.executable, "-m", "annotatedvdb_tpu", "serve",
            "--storeDir", store_dir, "--port", "0"]
    if env_extra.get("AVDB_SERVE_WORKERS", "1") != "1":
        argv += ["--workers", env_extra["AVDB_SERVE_WORKERS"]]
    proc = subprocess.Popen(
        argv, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, cwd=ROOT,
    )
    for _ in range(200):
        line = proc.stdout.readline()
        if not line:
            break
        m = re.search(r"http://([\d.]+):(\d+)", line)
        if m:
            return proc, m.group(1), int(m.group(2))
    raise RuntimeError("serve fleet never printed its address")


def request(host, port, method, path, body=None, timeout=20):
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None,
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as err:
        return err.code, err.read()


def wait_ready(host, port, tries=120):
    import time

    for _ in range(tries):
        try:
            st, _ = request(host, port, "GET", "/healthz", timeout=5)
            if st == 200:
                return
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.25)
    raise RuntimeError("fleet never became healthy")


def main() -> int:
    from annotatedvdb_tpu.parallel.mesh import global_mesh

    work = tempfile.mkdtemp(prefix="avdb_mesh_smoke_")
    procs = []
    servers = []
    try:
        mesh = global_mesh()
        if mesh is None or mesh.devices.size != 4:
            log(f"FAIL: expected a 4-device host mesh, got {mesh}")
            return 1
        vcf = os.path.join(work, "smoke.vcf")
        n = write_vcf(vcf)
        log(f"sharded load of {n} rows over the 4-device mesh")
        mesh_dir = os.path.join(work, "store_mesh")
        load_store(vcf, mesh_dir, mesh)

        from annotatedvdb_tpu.store import VariantStore

        s_one = VariantStore.load(mesh_dir, readonly=True)
        if s_one.n != n:
            log(f"FAIL: sharded load landed {s_one.n} rows of {n}")
            return 1
        if (s_one.mesh_placement or {}).get("devices") != 4:
            log("FAIL: mesh store manifest carries no placement block")
            return 1
        log(f"sharded load committed {n} rows + placement block")

        # fleet with the mesh path forced vs a mesh-off IN-PROCESS
        # reference server (the single-device path) over the SAME store
        log("starting 2-worker fleet (mesh on) + reference (mesh off)")
        fleet, fhost, fport = spawn_fleet(mesh_dir, {
            "AVDB_SERVE_WORKERS": "2", "AVDB_SERVE_MESH": "1",
            "AVDB_MESH_BULK_MIN": "0",
        })
        procs.append(fleet)
        from annotatedvdb_tpu.serve.aio import build_aio_server

        os.environ["AVDB_SERVE_MESH"] = "0"
        httpd = build_aio_server(store_dir=mesh_dir, port=0)
        httpd.start_background()
        servers.append(httpd)
        rhost, rport = httpd.server_address[:2]
        wait_ready(fhost, fport)

        shard1 = s_one.shards[1]
        ids = []
        for code, shard in s_one.shards.items():
            from annotatedvdb_tpu.types import chromosome_label

            label = chromosome_label(code)
            for j in (0, 7, shard.n - 1):
                pos = int(shard.cols["pos"][j])
                r, a = shard.alleles(j)
                ids.append(f"{label}:{pos}:{r}:{a}")
        ids.append("2:1234:A:T")  # a miss on an unloaded chromosome
        regions = ["1:1-100000", "8:1-64000000", "X:500-90000",
                   "11:1-5000", "1:1-1"]
        del shard1

        # warmup: compile the sharded bulk + spans programs ONCE (the
        # answering worker writes the persistent cache; the OTHER
        # worker's first touch then loads from disk instead of paying a
        # fresh ~10s compile)
        request(fhost, fport, "POST", "/variants", {"ids": ids},
                timeout=60)
        request(fhost, fport, "POST", "/regions", {"regions": regions},
                timeout=60)

        checked = 0
        for path in (
            [f"/variant/{i}" for i in ids[:4]]
            + [f"/region/{r}" for r in regions]
        ):
            st_f, body_f = request(fhost, fport, "GET", path)
            st_r, body_r = request(rhost, rport, "GET", path)
            if (st_f, body_f) != (st_r, body_r):
                log(f"FAIL: {path} diverges (mesh {st_f} vs ref {st_r})")
                return 1
            checked += 1
        for payload in (
            {"ids": ids},
            {"regions": regions},
            {"regions": regions, "limit": 0},
            {"regions": regions, "minCadd": 5.0, "limit": 3},
        ):
            route = "/variants" if "ids" in payload else "/regions"
            st_f, body_f = request(fhost, fport, "POST", route, payload)
            st_r, body_r = request(rhost, rport, "POST", route, payload)
            if st_f != 200 or (st_f, body_f) != (st_r, body_r):
                log(f"FAIL: POST {route} {payload.keys()} diverges")
                return 1
            checked += 1
        # the fleet really ran the mesh path (not a silent fallback):
        # the /stats block proves construction, the dispatch counter
        # proves EXECUTION — a regression where every sharded call fails
        # (breaker absorbs it, fallback stays byte-identical) must not
        # pass this smoke
        st, stats = request(fhost, fport, "GET", "/stats")
        mesh_stats = json.loads(stats).get("mesh") if st == 200 else None
        if not mesh_stats or mesh_stats.get("devices") != 4:
            log(f"FAIL: fleet /stats carries no mesh block ({mesh_stats})")
            return 1
        dispatches = 0
        for _ in range(8):  # accept balancing: scrape until we hit a
            st, metrics = request(fhost, fport, "GET", "/metrics")
            for line in (metrics.decode() if st == 200 else "").splitlines():
                if line.startswith("avdb_mesh_dispatch_total"):
                    dispatches += int(float(line.rsplit(" ", 1)[1]))
            if dispatches:
                break
        if not dispatches:
            log("FAIL: no worker counted a mesh dispatch — the sharded "
                "path never executed (silent fallback)")
            return 1
        log(f"fleet mesh path byte-identical to single-device over "
            f"{checked} request shapes (devices={mesh_stats['devices']})")
        print("mesh_smoke: OK")
        return 0
    except Exception as exc:  # noqa: BLE001 - smoke boundary
        log(f"INTERNAL: {type(exc).__name__}: {exc}")
        import traceback

        traceback.print_exc()
        return 2
    finally:
        for proc in procs:
            try:
                proc.terminate()
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
        for httpd in servers:
            try:
                httpd.shutdown()
                httpd.ctx.batcher.close()
            except Exception as exc:
                log(f"reference-server teardown: {exc}")
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
