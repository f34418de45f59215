"""The interval kind of cell: target panels for ``POST /regions`` against the
store ``drivers/serve.py`` builds and serves (its set-up, its server child
and its client, imported as they stand).

Set-up (all of it ``setup_s``): first of all the program is asked whether
it can say when its interval indexes are ready (:func:`probe`: a
program that cannot fails the run in seconds, before any store is built);
then generation, load, compaction and ``serve`` as in the bulk cell; then
warm-up panels until ``/stats`` ``residency.resident`` covers the segments,
``region_index.device`` equals ``candidates``, every chromosome group of
a whole panel was answered by the device and the overload ladder is at
rest (a 503 it sheds before that is waited out: :func:`warm_up`); the
next panel is timed (the first after the server said ready: what the
configuration's last guarantee is about), and ``warmup_requests_per_client``
are sent after ready in all.  A server whose ``/stats`` has no
``region_index`` block fails the run at once.

The window: one client, closed loop, keep-alive; a reply is read to the
end of its last chunk.  ``serve_keys_per_s`` is the query intervals of the
200-replies that completed inside the window over the window (an interval
is this read's key); ``serve_p95_ms`` the 95th percentile of send -> last
byte of the last chunk over all requests sent; ``setup_s`` as everywhere.
A reply other than 200 counts no interval and goes to ``failed``.  After
the window a sample of the kept replies, drawn from the seed, is compared
envelope by envelope and record by record with the reference
(``reference/regions_check.py``); the server must have taken no chromosome
group to its host twin, built or uploaded no index and compiled nothing.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import threading
import time

import numpy as np

from common import (Children, RunFailed, check, child_env, load_json,
                    note_json, percentile, wait_for_file, work_dir,
                    write_json_atomic)
from drivers.serve import (Client, build_store, client_loop, finish_build,
                           start_server)
from readers import prom, xplane_idle
from reference import regions_check
from traffic import regions as traffic_regions
from traffic.vcf import Expected, synth_vcf_rows, write_synth_vcf

#: name -> limit of every number the comparison returns (all exact)
LIMITS = dict(regions_check.LIMITS, responses_uncompared=0, unanswered=0,
              breaker_trips=0, segments_not_resident=0, indexes_not_ready=0,
              host_groups_in_window=0, server_exit_code=0,
              compiled_in_window=0)

#: the faults of ``tests/faults`` that a cell of this kind can have:
#: ``serve.answer_altered`` wraps ``engine._render_row``, which a region
#: page renders every row through; ``serve.half_left_out`` breaks
#: ``lookup_many``, which an interval read never calls
FAULTS = ("serve.answer_altered",)

#: exit code of :data:`PROBE` for a program without the readiness report
NOT_SUPPORTED_RC = 4

#: asks the program, without a device and without a store, whether its
#: server can report its interval indexes ready
PROBE = (
    "import sys\n"
    "from annotatedvdb_tpu.serve.engine import QueryEngine\n"
    f"sys.exit(0 if hasattr(QueryEngine, 'region_index_stats') "
    f"else {NOT_SUPPORTED_RC})\n"
)


def probe(children, rehearse: bool) -> None:
    """Fail the run, in seconds, on a program that cannot run this cell."""
    proc, _out, err_path = children.start(
        "probe_child", ["-c", PROBE],
        dict(child_env(rehearse), JAX_PLATFORMS="cpu"))
    rc = children.wait(proc, "probe_child", err_path, 120)
    if rc != 0:
        raise RunFailed(
            "this program's server cannot report its interval indexes "
            "ready (no QueryEngine.region_index_stats, no /stats "
            f"region_index): it cannot run this cell (probe exit code {rc})")


# ---------------------------------------------------------------------------
# the controls: the reference in the program's place, one guarantee broken


def _body(envelopes: list) -> bytes:
    return json.dumps({"n": len(envelopes), "results": envelopes}).encode()


def controls(config: dict, params: dict, seed: int, seconds: float) -> dict:
    """{broken guarantee: the numbers compared}: the reference answers as
    many panels as a run compares, one guarantee broken (``control.py``).

    ``limit_ignored`` — every row of an interval returned, ``returned`` =
    ``count``; ``end_exclusive`` — the rows at ``pos == end`` dropped;
    ``sorted_order`` — envelopes in BED order (chromosome, start, end)
    where the configuration says request order; ``stale_generation`` —
    answers from the store before its last chromosome block was
    committed."""
    chromosomes = tuple(params["chromosomes"])
    exp = Expected(synth_vcf_rows(int(params["store_records"]), seed,
                                  chromosomes), chromosomes)
    stored = regions_check.StoredRows(exp, int(config["shapes"]["store_width"]))
    sampled = traffic_regions.build(
        exp, dict(params, clients=1,
                  requests_per_client=int(params["check_responses"])),
        seed)[0]
    limit, generation = int(params["limit"]), 1
    last = len(chromosomes) - 1

    def answer(panel, limit=limit, **kw):
        return [regions_check.expected_envelope(stored, spec, limit,
                                                generation, **kw)
                for spec in panel.specs]

    def stale(panel):
        envelopes = answer(panel)
        for spec, env in zip(panel.specs, envelopes):
            env["generation"] = generation - 1
            if spec[0] == last:
                env.update(count=0, returned=0, variants=[])
        return envelopes

    broken = {
        "limit_ignored": lambda p: answer(p, limit=None),
        "end_exclusive": lambda p: answer(p, end_exclusive=True),
        "sorted_order": lambda p: [env for _s, env in sorted(
            zip(p.specs, answer(p)), key=lambda pair: pair[0])],
        "stale_generation": stale,
    }
    out = {}
    for name, responder in broken.items():
        numbers = regions_check.compare(
            stored, [(p, 200, _body(responder(p))) for p in sampled],
            limit, generation)
        numbers.pop("first_wrong")
        out[name] = numbers
    return out


# ---------------------------------------------------------------------------
# the run


#: seconds between two warm-up panels while the server is not ready
WARM_PACE_S = 0.5


def warm_up(admin, client, warm_pool, params, n_segments: int,
            rehearse: bool) -> dict:
    """Panels until the server says it is ready for them and shows it:
    every candidate segment resident, every candidate interval index on
    the device and warmed, a whole panel's groups answered there, the
    overload ladder at rest.  Then the first panel after that is timed,
    and the rest of the ``warmup_requests_per_client`` follow.

    Until then the server is still setting up: its first panel builds the
    indexes inside the request, and beside a cold compile cache a few more
    run over the ladder's 250 ms while the uploader compiles.  Three such
    close together and the default server sheds region reads for some
    seconds (503) — what it is built to do, and part of the set-up, not
    of the window.  So the panels before ready are paced
    (:data:`WARM_PACE_S`: the ladder's signal decays in the idle evaluations
    between them), a 503 before ready is waited out and counted
    (``shed_before_ready``), and ready includes ``brownout.level`` 0.  Any
    other status, and any status but 200 after ready, fails the run."""
    t0 = time.monotonic()
    stats = admin.get_json("/stats")
    if "region_index" not in stats or "region_panels" not in stats:
        raise RunFailed("the server's /stats has no region_index / "
                        "region_panels block: it cannot say when its "
                        "interval indexes are ready")
    sent, shed, residency, index, before_ready = 0, 0, {}, {}, []
    while time.monotonic() - t0 < 180:
        before = stats["region_panels"]
        panel = warm_pool[sent % len(warm_pool)]
        status, body, t_send, t_done = client.call(panel)
        sent += 1
        before_ready.append(round((t_done - t_send) * 1000.0, 1))
        if status == 503:
            shed += 1
        elif status != 200:
            raise RunFailed(
                f"warm-up panel {sent} -> {status} {body[:200]!r}; panels "
                f"so far took {before_ready} ms; brownout "
                f"{stats.get('brownout')}, residency "
                f"{stats.get('residency')}, region_index "
                f"{stats['region_index']}")
        stats = admin.get_json("/stats")
        after = stats["region_panels"]
        residency = stats.get("residency") or {}
        index = stats["region_index"]
        floor = 0 if rehearse else n_segments
        on_device = (status == 200
                     and after["host_groups"] == before["host_groups"]
                     and after["device_groups"] > before["device_groups"])
        if on_device \
                and residency.get("resident", 0) >= max(
                    residency.get("candidates", 0), floor) \
                and index["device"] >= max(index["candidates"], floor) \
                and (stats.get("brownout") or {}).get("level", 0) == 0:
            break
        time.sleep(WARM_PACE_S)
    else:
        raise RunFailed(f"warm-up: no panel was answered by a ready "
                        f"server in 180 s: residency {residency}, "
                        f"region_index {index}, brownout "
                        f"{stats.get('brownout')}, {shed} of {sent} "
                        f"panels shed")
    ready_s = round(time.monotonic() - t0, 2)
    after_ready = []
    for k in range(int(params["warmup_requests_per_client"])):
        status, body, t_send, t_done = client.call(
            warm_pool[(sent + k) % len(warm_pool)])
        if status != 200:
            raise RunFailed(f"warm-up panel after ready -> {status} "
                            f"{body[:200]!r}")
        after_ready.append(round((t_done - t_send) * 1000.0, 2))
    return {"requests": sent + len(after_ready), "ready_seconds": ready_s,
            "seconds": round(time.monotonic() - t0, 2),
            "first_panel_after_ready_ms": after_ready[0],
            "panels_after_ready_ms": after_ready,
            "panels_before_ready_ms": before_ready,
            "shed_before_ready": shed,
            "residency": residency, "region_index": index}


def run(ctx) -> dict:
    cell, params = ctx.cell, ctx.params
    chromosomes = tuple(params["chromosomes"])
    work = work_dir()
    children = Children(ctx.log_dir)
    trace_dir = os.path.join(work, "trace") if ctx.trace else None
    try:
        # before a chip is taken or a store built
        probe(children, ctx.rehearse)
        warm_vcf = os.path.join(work, "warmup.vcf")
        vcf = os.path.join(work, "store.vcf")
        store = os.path.join(work, "vdb")
        build = build_store(ctx, children, work, warm_vcf, vcf, store)
        t0 = time.monotonic()
        for path, records, seed in (
                (warm_vcf, int(params["warmup_records"]), ctx.seed + 1),
                (vcf, int(params["store_records"]), ctx.seed)):
            rows = write_synth_vcf(path + ".part", records, seed,
                                   chromosomes)
            os.replace(path + ".part", path)
        exp = Expected(rows, chromosomes)
        t1 = time.monotonic()
        n_clients = int(params["clients"])
        # one more list than clients: the warm-up's own panels
        pools = traffic_regions.build(
            exp, dict(params, clients=n_clients + 1), ctx.seed)
        warm_pool = pools.pop()[:64]
        stored = regions_check.StoredRows(
            exp, int(ctx.config["shapes"]["store_width"]))
        note_json("generate", records=int(params["store_records"]),
                  rows_expected=exp.n_rows, vcf_bytes=os.path.getsize(vcf),
                  generate_seconds=round(t1 - t0, 2),
                  panels_built=sum(len(p) for p in pools),
                  request_seconds=round(time.monotonic() - t1, 2))
        built = finish_build(children, build, exp.n_rows)

        t0 = time.monotonic()
        server, serve_err, control, host, port = start_server(
            ctx, children, work, store, params)
        startup_seconds = round(time.monotonic() - t0, 2)
        admin = Client(host, port)
        clients = [Client(host, port) for _ in range(n_clients)]
        stats0 = admin.get_json("/stats")
        note_json("serve_up", startup_seconds=startup_seconds,
                  rows=stats0.get("rows"), generation=stats0.get("generation"),
                  device=stats0.get("device"), compile=stats0.get("compile"))
        if stats0.get("rows") != exp.n_rows:
            raise RunFailed(f"the server holds {stats0.get('rows')} rows, "
                            f"the generator's first-wins load {exp.n_rows}")
        generation = int(stats0["generation"])
        warm = warm_up(admin, clients[0], warm_pool, params,
                       len(chromosomes), ctx.rehearse)
        note_json("warm_up", **warm)

        prom_before = prom.parse(admin.get_text("/metrics"))
        stats_before = admin.get_json("/stats")
        rng = np.random.default_rng([int(ctx.seed), 3])
        keep_every = int(params["keep_every"])
        keep_offset = int(rng.integers(keep_every))
        outs = [[] for _ in clients]
        t_open = time.monotonic()
        deadline = t_open + ctx.seconds
        threads = [
            threading.Thread(target=client_loop, args=(
                client, pool, deadline, keep_every, keep_offset, out))
            for client, pool, out in zip(clients, pools, outs)
        ]
        for thread in threads:
            thread.start()
        traced = None
        if trace_dir:
            trace_s = min(float(params["trace_seconds"]), ctx.seconds / 2)
            time.sleep(min(2.0, ctx.seconds / 4))
            write_json_atomic(os.path.join(control, "trace.request"),
                              {"dir": trace_dir, "seconds": trace_s})
        for thread in threads:
            thread.join()
        prom_after = prom.parse(admin.get_text("/metrics"))
        stats_after = admin.get_json("/stats")
        if trace_dir:
            wait_for_file(os.path.join(control, "trace.done"), server,
                          "serve_child", serve_err, 120)
            traced = load_json(os.path.join(control, "trace.done"))
        write_json_atomic(os.path.join(control, "device.request"), {})
        wait_for_file(os.path.join(control, "device.json"), server,
                      "serve_child", serve_err, 60)
        device = load_json(os.path.join(control, "device.json"))
        device["memory_peak_bytes"] = max(
            device["memory_peak_bytes"], built["device"]["memory_peak_bytes"])
        for client in clients + [admin]:
            client.conn.close()
        server.send_signal(signal.SIGTERM)
        try:
            exit_code = server.wait(timeout=60)
        except subprocess.TimeoutExpired:
            server.kill()
            server.wait()
            exit_code = -9

        # (panel, status, kept body or None, t_send, t_done)
        done = [r for out in outs for r in out]
        if not done:
            raise RunFailed("no request completed in the window")
        latencies = [(t_done - t_send) * 1000.0
                     for _p, _s, _b, t_send, t_done in done]
        ok = [r for r in done if r[1] == 200]
        keys_in_window = sum(len(r[0].specs) for r in ok if r[4] <= deadline)
        unanswered = sum(1 for r in done if r[1] is None)
        statuses: dict = {}
        for r in done:
            statuses[str(r[1])] = statuses.get(str(r[1]), 0) + 1
        p50 = percentile(latencies, 50)
        note_json("window", requests=len(done), statuses=statuses,
                  p50_ms=round(p50, 2),
                  p95_ms=round(percentile(latencies, 95), 2),
                  max_ms=round(max(latencies), 2),
                  first_panel_after_ready_ms=warm[
                      "first_panel_after_ready_ms"],
                  first_over_median=round(
                      warm["first_panel_after_ready_ms"] / p50, 3),
                  intervals_in_window=keys_in_window, exit_code=exit_code,
                  logs=ctx.log_dir)

        def metric(name):
            return int(prom_after.get(name, 0))

        def moved(block, name):
            return stats_after[block][name] - stats_before[block][name]

        residency = stats_after.get("residency") or {}
        index = stats_after["region_index"]
        note_json("server", compile=stats_after.get("compile"),
                  residency=residency, region_index=index,
                  region_panels=stats_after["region_panels"],
                  residency_uploads=metric(
                      "avdb_serve_residency_uploads_total"),
                  resident_bytes=metric("avdb_serve_resident_bytes"),
                  breaker_trips=metric("avdb_serve_breaker_trips_total"),
                  brownout_shed=metric("avdb_serve_brownout_shed_total"),
                  memory_peak_bytes=device["memory_peak_bytes"])

        t0 = time.monotonic()
        kept = [r for r in ok if r[2] is not None]
        n_check = min(int(params["check_responses"]), len(kept))
        picks = rng.choice(len(kept), size=n_check, replace=False) \
            if kept else []
        numbers = regions_check.compare(
            stored, [(kept[i][0], kept[i][1], kept[i][2]) for i in picks],
            int(params["limit"]), generation)
        note_json("check", responses_kept=len(kept),
                  responses_compared=n_check,
                  seconds=round(time.monotonic() - t0, 2), **numbers)
        n_chrom = len(chromosomes)
        numbers.update(
            responses_uncompared=0 if n_check else 1, unanswered=unanswered,
            breaker_trips=metric("avdb_serve_breaker_trips_total"),
            segments_not_resident=0 if ctx.rehearse else max(
                n_chrom - int(residency.get("resident", 0)), 0),
            # ready when the window closed, and nothing built or uploaded
            # inside it
            indexes_not_ready=(0 if ctx.rehearse else max(
                n_chrom - int(index["device"]), 0))
            + moved("region_index", "builds")
            + moved("region_index", "uploads"),
            host_groups_in_window=moved("region_panels", "host_groups"),
            server_exit_code=abs(exit_code),
            compiled_in_window=(stats_after["compile"]["programs"]
                                - stats_before["compile"]["programs"]))
        checks = {name: check(numbers[name], limit)
                  for name, limit in LIMITS.items()}

        artefacts = {
            "prom_before": prom_before, "prom_after": prom_after,
            "stats_before": stats_before, "stats_after": stats_after,
            "requests_sent": len(done),
            "intervals_sent": sum(len(r[0].specs) for r in done),
            "groups_sent": sum(len({s[0] for s in r[0].specs})
                               for r in done),
        }
        breakdown = None
        if trace_dir:
            reduced = xplane_idle.reduce_trace(
                trace_dir, None if traced is None
                else traced["t1"] - traced["t0"])
            xplane_idle.keep_capture(trace_dir, ctx.log_dir)
            note_json("trace", **xplane_idle.summary(reduced))
            artefacts["xplane"] = reduced
            breakdown = xplane_idle.breakdown(reduced)
        return {
            "end_to_end": {
                "serve_keys_per_s": keys_in_window / ctx.seconds,
                "serve_p95_ms": percentile(latencies, 95),
                "setup_s": t_open - ctx.t_start,
            },
            "attempted": len(done),
            "failed": len(done) - len(ok),
            "checks": checks,
            "device": device,
            "artefacts": artefacts,
            "breakdown": breakdown,
        }
    finally:
        children.stop_all()
        shutil.rmtree(work, ignore_errors=True)
