#!/usr/bin/env python
"""Serve smoke-check: build a tiny store, stand up the serving front
end (the asyncio event-loop server) on an ephemeral loopback port, and
drive one request of every kind through it — plus chunked region
streaming, cursor paging, and byte-parity of the region and stats bodies
with the engine called directly.

Part of ``tools/run_checks.sh`` (tier-1 shells that script), so a PR that
breaks the serving wiring — routes, batcher, snapshot pinning, metrics —
fails the suite in seconds without the full pytest battery.

Exit codes mirror the other tools: 0 clean, 1 smoke failure, 2 internal
error.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.error
import urllib.request

# pin CPU before anything imports jax: the smoke must never hang on an
# accelerator probe (same discipline as tests/conftest.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("AVDB_JAX_PLATFORM", "cpu")

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _build_store(store_dir: str) -> int:
    import numpy as np

    from annotatedvdb_tpu.loaders.lookup import identity_hashes
    from annotatedvdb_tpu.store import VariantStore
    from annotatedvdb_tpu.types import encode_allele_array

    width = 8
    store = VariantStore(width=width)
    n = 64
    refs = ["A", "C", "G", "T"] * (n // 4)
    alts = ["G", "T", "A", "C"] * (n // 4)
    ref, ref_len = encode_allele_array(refs, width)
    alt, alt_len = encode_allele_array(alts, width)
    h = identity_hashes(width, ref, alt, ref_len, alt_len, refs, alts)
    store.shard(8).append(
        {"pos": np.arange(1000, 1000 + 97 * n, 97, dtype=np.int32)[:n],
         "h": h, "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
        annotations={"cadd_scores": [
            {"CADD_phred": float(i)} if i % 2 else None for i in range(n)
        ]},
    )
    store.save(store_dir)
    return n


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=20
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


def _post(port: int, path: str, payload) -> tuple[int, str]:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=20) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode()


#: the interval panel of the batch-region and stats legs
SPECS = ["8:1-100000", "8:1000-1400", "8:999000-999999"]


def _drive_routes(port: int, n: int, check) -> str:
    """The route battery; returns the region + stats bodies for parity."""
    status, body = _get(port, "/healthz")
    check("healthz", status == 200
          and json.loads(body)["rows"] == n, body)
    status, body = _get(port, "/variant/8:1000:A:G")
    check("point hit", status == 200
          and json.loads(body)["position"] == 1000, body)
    status, body = _get(port, "/variant/8:999:A:G")
    check("point miss", status == 404, body)
    status, body = _get(port, "/variant/junk")
    check("point 400", status == 400, body)
    status, region_body = _get(port, "/region/8:1-100000?minCadd=1&limit=5")
    rec = json.loads(region_body) if status == 200 else {}
    check("region", status == 200
          and rec.get("returned") == 5
          and rec.get("count", 0) > 5, region_body[:200])
    status, body = _get(port, "/metrics")
    check("metrics", status == 200
          and "avdb_query_requests_total" in body, body[:200])
    # batch region join: per-interval envelopes must be byte-identical to
    # the single /region bodies (the BITS batch-API contract), plus the
    # count-only and tokenize modes
    specs = SPECS
    status, batch = _post(port, "/regions",
                          {"regions": specs, "minCadd": 1, "limit": 5})
    rec = json.loads(batch) if status == 200 else {}
    check("regions batch", status == 200 and rec.get("n") == 3, batch[:200])
    for spec in specs:
        _st, single = _get(port, f"/region/{spec}?minCadd=1&limit=5")
        check(f"regions parity {spec}", single in batch, batch[:200])
    status, body = _post(port, "/regions",
                         {"regions": specs, "limit": 0, "tokenize": True})
    rec = json.loads(body) if status == 200 else {}
    check("regions count-only+tokens", status == 200
          and rec.get("results", [{}])[0].get("returned") == 0
          and rec.get("tokens", {}).get("count", [0])[0] > 0, body[:200])
    status, body = _post(port, "/regions", {"regions": ["8:9-3"]})
    check("regions 400", status == 400, body[:200])
    # analytics: the fused stats panel answers summaries (counts, CADD
    # histogram, windowed scan); the returned blob joins the parity
    # compare with the engine's own rendering
    status, stats_body = _post(port, "/stats/region",
                               {"regions": specs, "windows": 4})
    rec = json.loads(stats_body) if status == 200 else {}
    first = (rec.get("results") or [{}])[0]
    check("stats batch", status == 200 and rec.get("n") == 3
          and first.get("count", 0) > 0
          and first.get("cadd", {}).get("present", 0) > 0
          and len(first.get("windows", {}).get("counts", [])) == 4,
          stats_body[:200])
    status, body = _post(port, "/stats/region", {"regions": "junk"})
    check("stats 400", status == 400, body[:200])
    return region_body + stats_body


def main() -> int:
    from annotatedvdb_tpu.serve.aio import build_aio_server

    work = tempfile.mkdtemp(prefix="avdb_serve_smoke_")
    store_dir = os.path.join(work, "store")
    aio = None
    failures: list[str] = []

    def check(label: str, ok: bool, detail: str = "") -> None:
        if not ok:
            failures.append(f"{label}: {detail}"[:300])

    # everything that can fail to start lives inside the try: a startup
    # timeout must still remove the temp store and report through the
    # FAIL path — not a traceback
    try:
        n = _build_store(store_dir)
        aio = build_aio_server(
            store_dir=store_dir, port=0, stream_threshold=4
        )
        aio.start_background()
        aport = aio.server_address[1]
        served = _drive_routes(aport, n, check)
        engine = aio.ctx.engine
        check("engine parity", served == (
            engine.region("8:1-100000", min_cadd=1.0, limit=5)
            + engine.stats_serve(SPECS, windows=4).assemble()
        ), "region/stats bodies differ from the engine's own rendering")
        # chunked streaming (threshold 4 forces it) and cursor paging
        status, body = _get(aport, "/region/8:1-100000?limit=20")
        rec = json.loads(body) if status == 200 else {}
        check("stream", status == 200 and rec.get("returned") == 20,
              body[:200])
        status, body = _get(aport, "/region/8:1-100000?limit=5&cursor=")
        rec = json.loads(body) if status == 200 else {}
        check("page", status == 200 and rec.get("next"), body[:200])
    except Exception as exc:
        check("startup", False, repr(exc))
    finally:
        if aio is not None:
            aio.shutdown()
            aio.ctx.batcher.close()
        import shutil

        shutil.rmtree(work, ignore_errors=True)
    if os.environ.get("AVDB_LOCK_TRACE", "") == "1":
        # lock-order smoke: the whole battery just ran with every serve-
        # stack lock traced — any cycle in the acquisition-order graph is
        # a potential deadlock and fails the check (tools/run_checks.sh
        # arms this; see analysis/lockorder).  Cycles join the ordinary
        # failures list so the functional failures that may explain them
        # still print alongside.
        from annotatedvdb_tpu.analysis.lockorder import RECORDER

        rep = RECORDER.report()
        for cyc in rep["cycles"]:
            check("lock-order cycle (potential deadlock)", False,
                  " -> ".join(cyc + cyc[:1]))
        if not rep["cycles"]:
            print(
                f"serve_smoke: lock order clean ({len(rep['locks'])} "
                f"traced locks, {len(rep['edges'])} ordering edges, "
                f"0 cycles)",
                file=sys.stderr,
            )
    if failures:
        for f in failures:
            print(f"serve_smoke FAIL {f}", file=sys.stderr)
        return 1
    print(f"serve_smoke: ok ({n} rows; the asyncio front end, "
          "streaming, paging and stats answered)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
