"""The serving kind of cell: identity lookups against a committed, compacted
store served by the program's own ``serve`` entry point, from closed-loop
clients in this process.

Set-up (all of it ``setup_s``): generate the VCF -> ``children/load_child.py``
loads and compacts it in one process (a small throw-away load while the file
is written, ``load-vcf --commit``, then ``doctor compact``, as a deployment
does before it serves) -> ``children/
serve_child.py`` serves it (``serve --workers 1 --hbmBudget ..``) -> warm-up
lookups until the residency manager has every candidate segment on the
device, then a few more on each client's own connection.  Request bodies
are built and encoded while the store loads.

The window: each client sends its next request when its reply has arrived,
for ``--seconds``.  ``serve_keys_per_s`` is the ids of the replies that
completed inside the window over the window; ``serve_p95_ms`` is the 95th
percentile of send -> last byte over all requests sent in the window.  A
reply other than 200 counts no keys and goes to ``failed``.  After the
window: ``/metrics`` and ``/stats`` again, the device's memory peak, SIGTERM
(exit code 0 required), and a sample of the kept replies, drawn from the
seed, is compared record by record with the reference
(``reference/answers_check.py``).
"""

from __future__ import annotations

import http.client
import json
import os
import re
import shutil
import signal
import subprocess
import threading
import time

import numpy as np

from common import (BENCH, Children, RunFailed, check, child_env,
                    load_json, note_json, percentile, tail, wait_for_file,
                    work_dir, write_json_atomic)
from readers import prom, xplane_idle
from reference import answers_check
from traffic import requests as traffic_requests
from traffic.vcf import Expected, synth_vcf_rows, write_synth_vcf

#: name -> limit of every number the comparison returns (all exact)
LIMITS = dict(answers_check.LIMITS, responses_uncompared=0, unanswered=0,
              breaker_trips=0, segments_not_resident=0, server_exit_code=0,
              compiled_in_window=0)

#: the faults of ``tests/faults`` that a cell of this kind can have
FAULTS = ("serve.half_left_out", "serve.answer_altered")


def _envelope(records: list) -> bytes:
    found = sum(r is not None for r in records)
    return json.dumps({"n": len(records), "found": found,
                       "results": records}).encode()


def controls(config: dict, params: dict, seed: int, seconds: float) -> dict:
    """{broken guarantee: the numbers compared}: the reference answers as
    many requests as a run compares, one guarantee broken (``control.py``).

    ``sorted_order`` — answers in key order where the configuration says
    request order; ``stale_generation`` — answers from the store before its
    last chromosome block was committed; ``nearest_row`` — an id that was
    never loaded answered with a row that was (approximate)."""
    chromosomes = tuple(params["chromosomes"])
    exp = Expected(synth_vcf_rows(int(params["store_records"]), seed,
                                  chromosomes), chromosomes)
    sampled = traffic_requests.build(
        exp, dict(params, clients=1,
                  requests_per_client=int(params["check_responses"])),
        seed)[0]
    n_chrom = len(chromosomes)
    last_row = [int(np.flatnonzero(exp.kept["chrom"] == ci)[-1])
                for ci in range(n_chrom)]

    def answer(request, stale=False, nearest=False):
        records = []
        for ident, i in zip(request.ids, request.rows):
            if i < 0:
                if nearest:  # the row that sorts next to the absent key
                    j = last_row[chromosomes.index(ident.split(":")[0])]
                    records.append(answers_check.expected_record(
                        exp, exp.ident(j), j))
                else:
                    records.append(None)
            elif stale and int(exp.kept["chrom"][i]) == n_chrom - 1:
                records.append(None)
            else:
                records.append(answers_check.expected_record(exp, ident, i))
        return records

    broken = {
        "sorted_order": lambda rq: [
            rec for _id, rec in sorted(
                zip(rq.ids, answer(rq)), key=lambda p: p[0])],
        "stale_generation": lambda rq: answer(rq, stale=True),
        "nearest_row": lambda rq: answer(rq, nearest=True),
    }
    out = {}
    for name, responder in broken.items():
        numbers = answers_check.compare(
            exp, [(rq, 200, _envelope(responder(rq))) for rq in sampled])
        numbers.pop("first_wrong")
        out[name] = numbers
    return out


class Client:
    """One keep-alive connection; every call's clock readings are kept."""

    def __init__(self, host: str, port: int):
        self.address = (host, port)
        self.conn = http.client.HTTPConnection(host, port, timeout=120)

    def call(self, request):
        """(status or None, body bytes, t_send, t_done)."""
        headers = {} if request.body is None else {
            "Content-Type": "application/json"}
        t_send = time.monotonic()
        try:
            self.conn.request(request.method, request.path,
                              body=request.body, headers=headers)
            response = self.conn.getresponse()
            body = response.read()
            return response.status, body, t_send, time.monotonic()
        except (OSError, http.client.HTTPException):
            self.conn.close()
            self.conn = http.client.HTTPConnection(*self.address, timeout=120)
            return None, b"", t_send, time.monotonic()

    def get_json(self, path: str) -> dict:
        return json.loads(self.get_text(path))

    def get_text(self, path: str) -> str:
        self.conn.request("GET", path)
        response = self.conn.getresponse()
        body = response.read()
        if response.status != 200:
            raise RunFailed(f"GET {path} -> {response.status}")
        return body.decode()


def client_loop(client, requests, deadline, keep_every, keep_offset, out):
    """Closed loop until the deadline; ``out`` gets one tuple per request:
    (request, status, kept body or None, t_send, t_done)."""
    k = 0
    while time.monotonic() < deadline:
        request = requests[k % len(requests)]
        status, body, t_send, t_done = client.call(request)
        keep = k % keep_every == keep_offset
        out.append((request, status, body if keep else None, t_send, t_done))
        k += 1


def build_store(ctx, children, work, warm_vcf, vcf, store) -> dict:
    """Start the load + compact child; returns what to wait for.  A small
    throw-away load comes first: the program starts the device, builds its
    tokenizer and loads its programs while the store's file is written."""
    result_path = os.path.join(work, "build.json")
    job = {
        "chips": ctx.cell["chips"], "rehearse": ctx.rehearse, "trace": None,
        "result": result_path,
        "steps": [
            {"kind": "load", "name": "warmup", "vcf": warm_vcf,
             "wait_for": warm_vcf, "store": os.path.join(work, "warm"),
             "log": os.path.join(ctx.log_dir, "warmup.log")},
            {"kind": "load", "name": "load", "vcf": vcf, "wait_for": vcf,
             "store": store, "log": os.path.join(ctx.log_dir, "load.log")},
            {"kind": "compact", "name": "compact", "store": store},
        ],
    }
    job_path = os.path.join(work, "build_job.json")
    write_json_atomic(job_path, job)
    proc, _out, err_path = children.start(
        "build_child",
        [os.path.join(BENCH, "children", "load_child.py"), job_path],
        child_env(ctx.rehearse),
    )
    return {"proc": proc, "err": err_path, "result": result_path}


def finish_build(children, build, rows_expected: int) -> dict:
    child = children.result(build["proc"], "build_child", build["err"],
                            build["result"])
    warm, load, compact = child["steps"]
    report = compact["report"]
    if report.get("status") not in ("compacted", "noop") \
            or report.get("rows_dropped") != 0:
        raise RunFailed(f"doctor compact: {report}")
    note_json("build", import_seconds=round(child["import_seconds"], 2),
              startup_seconds=round(warm["t1"] - warm["t0"], 2),
              load_seconds=round(load["t1"] - load["t0"], 2),
              compact_seconds=round(compact["t1"] - compact["t0"], 2),
              compact=report.get("status"), rows_expected=rows_expected,
              rows_after_compact=report.get("rows"),
              memory_peak_bytes=child["device"]["memory_peak_bytes"])
    return child


def start_server(ctx, children, work, store, params):
    control = os.path.join(work, "control")
    os.makedirs(control)
    proc, out_path, err_path = children.start(
        "serve_child",
        [os.path.join(BENCH, "children", "serve_child.py"), control, "--",
         "--storeDir", store, "--port", "0", "--workers", "1",
         "--hbmBudget", str(params["hbm_budget"])],
        child_env(ctx.rehearse),
    )
    t0 = time.monotonic()
    address = None
    while address is None:
        if proc.poll() is not None:
            raise RunFailed(f"serve_child: exit code {proc.returncode} "
                            f"before serving\n{tail(err_path)}")
        if time.monotonic() - t0 > 600:
            raise RunFailed(f"serve_child: no address line in 600s\n"
                            f"{tail(err_path)}")
        with open(out_path) as f:
            address = re.search(r"on http://([\d.]+):(\d+)", f.read())
        if address is None:
            time.sleep(0.1)
    return proc, err_path, control, address.group(1), int(address.group(2))


def warm_up(admin, clients, warm_pool, params, n_segments,
            rehearse) -> dict:
    """Lookups until every candidate segment is device-resident AND a whole
    request's ids went through the device probe (the manager reports a
    segment resident before a large upload has landed; until then lookups
    take the host path and the probe program is not yet loaded), then a few
    on each client's own connection."""
    t0 = time.monotonic()
    sent, residency = 0, {}

    def device_queries():
        stats = admin.get_json("/stats")
        return stats, int((stats.get("device_lookup") or {})
                          .get("device_queries", 0))

    _stats, before = device_queries()
    while time.monotonic() - t0 < 180:
        request = warm_pool[sent % len(warm_pool)]
        status, _body, _s, _d = clients[0].call(request)
        sent += 1
        if status != 200:
            raise RunFailed(f"warm-up lookup {sent} -> {status}")
        stats, after = device_queries()
        residency = stats.get("residency") or {}
        resident = residency.get("resident", 0)
        on_device = after - before >= len(request.ids)
        before = after
        if rehearse and resident >= residency.get("candidates", 0):
            break
        if resident >= max(residency.get("candidates", 0), n_segments) \
                and on_device:
            break
        time.sleep(0.1)
    else:
        raise RunFailed(f"warm-up: the device path never took a whole "
                        f"request in 180 s: {residency}")
    for client in clients:
        for k in range(int(params["warmup_requests_per_client"])):
            status, _body, _s, _d = client.call(
                warm_pool[(sent + k) % len(warm_pool)])
            if status != 200:
                raise RunFailed(f"warm-up lookup -> {status}")
        sent += int(params["warmup_requests_per_client"])
    return {"requests": sent, "seconds": round(time.monotonic() - t0, 2),
            "residency": residency}


def run(ctx) -> dict:
    cell, params = ctx.cell, ctx.params
    chromosomes = tuple(params["chromosomes"])
    work = work_dir()
    children = Children(ctx.log_dir)
    trace_dir = os.path.join(work, "trace") if ctx.trace else None
    try:
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
        # one more list than clients: the warm-up's own requests
        pools = traffic_requests.build(
            exp, dict(params, clients=n_clients + 1), ctx.seed)
        warm_pool = pools.pop()[:64]
        note_json("generate", records=int(params["store_records"]),
                  rows_expected=exp.n_rows, vcf_bytes=os.path.getsize(vcf),
                  generate_seconds=round(t1 - t0, 2),
                  requests_built=sum(len(p) for p in pools),
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
                  rows=stats0.get("rows"),
                  device=stats0.get("device"), compile=stats0.get("compile"))
        if stats0.get("rows") != exp.n_rows:
            raise RunFailed(f"the server holds {stats0.get('rows')} rows, "
                            f"the generator's first-wins load {exp.n_rows}")
        warm = warm_up(admin, clients, warm_pool, params,
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

        done = [r for out in outs for r in out]
        latencies = [(t_done - t_send) * 1000.0
                     for _r, _s, _b, t_send, t_done in done]
        ok = [r for r in done if r[1] == 200]
        keys_in_window = sum(len(r[0].ids) for r in ok if r[4] <= deadline)
        ids_sent = sum(len(r[0].ids) for r in done)
        unanswered = sum(1 for r in done if r[1] is None)
        statuses: dict = {}
        for r in done:
            statuses[str(r[1])] = statuses.get(str(r[1]), 0) + 1
        if not latencies:
            raise RunFailed("no request completed in the window")
        note_json("window", requests=len(done), statuses=statuses,
                  per_client=[len(out) for out in outs],
                  p50_ms=round(percentile(latencies, 50), 2),
                  p95_ms=round(percentile(latencies, 95), 2),
                  max_ms=round(max(latencies), 2),
                  keys_in_window=keys_in_window, exit_code=exit_code,
                  logs=ctx.log_dir)

        def metric(name):
            return int(prom_after.get(name, 0))

        residency = stats_after.get("residency") or {}
        note_json("server", compile=stats_after.get("compile"),
                  residency=residency,
                  residency_uploads=metric(
                      "avdb_serve_residency_uploads_total"),
                  resident_bytes=metric("avdb_serve_resident_bytes"),
                  breaker_trips=metric("avdb_serve_breaker_trips_total"),
                  brownout_shed=metric("avdb_serve_brownout_shed_total"),
                  device_lookup=stats_after.get("device_lookup"),
                  memory_peak_bytes=device["memory_peak_bytes"])
        compiled_in_window = (stats_after["compile"]["programs"]
                              - stats_before["compile"]["programs"])

        t0 = time.monotonic()
        kept = [r for r in done if r[2] is not None and r[1] == 200]
        n_check = min(int(params["check_responses"]), len(kept))
        picks = rng.choice(len(kept), size=n_check, replace=False) \
            if kept else []
        numbers = answers_check.compare(
            exp, [(kept[i][0], kept[i][1], kept[i][2]) for i in picks])
        note_json("check", responses_kept=len(kept),
                  responses_compared=n_check,
                  seconds=round(time.monotonic() - t0, 2), **numbers)
        numbers.update(
            responses_uncompared=0 if n_check else 1, unanswered=unanswered,
            breaker_trips=metric("avdb_serve_breaker_trips_total"),
            segments_not_resident=0 if ctx.rehearse else max(
                len(chromosomes) - int(residency.get("resident", 0)), 0),
            server_exit_code=abs(exit_code),
            compiled_in_window=compiled_in_window)
        checks = {name: check(numbers[name], limit)
                  for name, limit in LIMITS.items()}

        artefacts = {"prom_before": prom_before, "prom_after": prom_after,
                     "stats_before": stats_before, "stats_after": stats_after,
                     "ids_sent": ids_sent, "requests_sent": len(done)}
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
