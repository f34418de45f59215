"""The YCSB workload E kind of cell: closed-loop clients that mostly scan a
short range forward from a Zipf-drawn variant while a share of their
operations insert new ones, against the store ``drivers/serve.py`` builds,
served with upserts on (``AVDB_SERVE_UPSERTS=1``, the environment spelling
of ``serve --upserts``), every other knob at its default.

Its set-up, its server and its first warm-up are ``drivers/serve.py``'s;
its clients are ``drivers/serve_point.py``'s raw-socket reader processes,
one a client, each on its own keep-alive connection, whose ceiling is
measured and checked as there (``client_ceiling_short``); its read-back,
its SIGKILL and ``children/durable_child.py``'s durability check, and its
fsync count are ``drivers/serve_ycsb_d.py``'s.

Each operation is drawn (``traffic/scans.py``): with
``insert_proportion`` a ``POST /variants/upsert`` of a new record
(``traffic/inserts.py``'s, each client its own run of insert numbers), else
a scan — ``GET /region/<chr>:<pos>-<pos+W-1>?limit=N``, the start row of
Zipf rank ``rank`` among the stored rows, ``N`` uniform over 1 ..
``max_scan_length``.  No scan depends on what another client inserted, so
every request is encoded in set-up.  After the first warm-up the server
must say its interval indexes are ready and its overload ladder at rest;
a second, short warm-up runs the mix itself, so the overlay is live and
the memtable's flush clock runs before the window opens — with the
default 30 s flush age the one flush of each 40 s window lands inside it.

The end-to-end numbers are the point cell's: operations of 200 replies
completed inside the window over the window (a scan is one key, as an
insert is), the 95th percentile over all operations sent (YCSB's
``[OVERALL]``), the set-up.  After the window, outside every clock: every
acknowledged insert is read back, the server is SIGKILLed and what it left
on disk is read offline; ``reference/ycsb_e_check.py`` compares every kept
scan whole with the model, given which inserts were acknowledged before it
was sent (it must show them) and which were sent before its reply arrived
(it may).  The limits are all 0, and so are ``request_index_builds`` (a
whole-chromosome interval index sorted on a request's thread in the
window), ``uploads_in_window`` and ``compiled_in_window``.  First of all
the program is asked whether it counts those sorts (:func:`probe`): a
program that cannot fails the run in seconds, before any store is built.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import time

import numpy as np

from common import (Children, RunFailed, check, child_env, load_json,
                    note_json, percentile, wait_for_file, work_dir,
                    write_json_atomic)
from drivers.serve import (Client, build_store, finish_build, start_server,
                           warm_up)
from drivers.serve_point import Reader, ReaderProcesses, ceiling, head_of
from drivers.serve_ycsb_d import (_INSERT_HEAD, _durable, _read_back,
                                  _wal_fsyncs, fsync_short)
from readers import prom, xplane_idle, xplane_named_all
from reference import ycsb_d_check, ycsb_e_check
from traffic import inserts as traffic_inserts
from traffic import requests as traffic_requests
from traffic import scans as traffic_scans
from traffic.vcf import Expected, synth_vcf_rows, write_synth_vcf

#: name -> limit of every number compared (all exact)
LIMITS = dict(ycsb_e_check.LIMITS, records_wrong=0, acked_not_read_back=0,
              acked_not_durable=0, fsync_short=0, request_index_builds=0,
              uploads_in_window=0, compiled_in_window=0,
              client_ceiling_short=0, responses_uncompared=0, unanswered=0,
              breaker_trips=0, segments_not_resident=0, server_exit_code=0)

#: the faults of ``tests/faults`` that bite here: ``serve.half_left_out``
#: the read-back's bulk lookups (``serve.answer_altered`` wraps the scalar
#: renderer, which only a scan of one record goes through)
FAULTS = ("serve.half_left_out",)

#: seconds the server has, after the first warm-up, to say its interval
#: indexes are ready and its overload ladder at rest
READY_SECONDS = 180

#: exit code of :data:`PROBE` for a program that cannot count the interval
#: index sorts its requests' threads make
NOT_SUPPORTED_RC = 4

#: asks the program, without a device and without a store, whether its
#: server counts the whole-chromosome index sorts a request's thread made
#: (``/stats`` ``region_index.request_builds``): the one guarantee of the
#: configuration no reply shows
PROBE = (
    "import sys\n"
    "from annotatedvdb_tpu.serve.engine import QueryEngine\n"
    "from annotatedvdb_tpu.serve.snapshot import StaticSnapshots\n"
    "from annotatedvdb_tpu.store import VariantStore\n"
    "stats = QueryEngine(StaticSnapshots(VariantStore(width=8)))"
    ".region_index_stats()\n"
    f"sys.exit(0 if 'request_builds' in stats else {NOT_SUPPORTED_RC})\n"
)


# ---------------------------------------------------------------------------
# the traffic


class Ops:
    """Every client's operations of one phase (the warm-up mix or the
    window), drawn from the seed: per client, the kind, scan spec and
    limit or insert number of each, and its encoded request."""

    def __init__(self, exp, params: dict, order, new: dict, seed: int,
                 phase: int, first_insert: int, n_ops: int):
        labels = exp.chromosomes
        kept = exp.kept
        window = int(params["scan_window_bp"])
        cdf = traffic_inserts.zipf_cdf(exp.n_rows, params["zipf_theta"])
        self.clients = []
        j = first_insert
        for client in range(int(params["clients"])):
            kinds, ranks, lengths = traffic_scans.client_draws(
                cdf, n_ops, params["insert_proportion"],
                params["max_scan_length"], seed, phase * 1000 + client)
            ops, heads = [], []
            for kind, rank, length in zip(kinds.tolist(), ranks.tolist(),
                                          lengths.tolist()):
                if kind and j < len(new["pos"]):
                    body = traffic_inserts.upsert_body(labels, new, j)
                    heads.append((_INSERT_HEAD % len(body)).encode() + body)
                    ops.append((1, j, 0))
                    j += 1
                    continue
                row = int(order[rank])
                ci, pos = int(kept["chrom"][row]), int(kept["pos"][row])
                heads.append(traffic_scans.scan_head(labels[ci], pos, window,
                                                     length))
                ops.append((0, (ci, *traffic_scans.scan_window(pos, window)),
                            length))
            self.clients.append((ops, heads))
        self.next_insert = j


# ---------------------------------------------------------------------------
# the controls: the reference in the program's place, one guarantee broken


def controls(config: dict, params: dict, seed: int, seconds: float) -> dict:
    """{broken guarantee: the numbers compared}: the reference answers as
    many scans as a run compares, every insert acknowledged before them,
    one guarantee broken (``control.py``).

    ``stale_generation`` — scans answered from the store before the second
    half of the inserts; ``one_row_short`` — a page one record short;
    ``rows_out_of_order`` — a page's first two records swapped;
    ``index_sorted_per_insert`` — the interval index sorted anew on a
    request's thread after each insert (what a store without a base index
    does); ``ack_before_fsync`` — acknowledgements with no fsync of their
    WAL frame before them."""
    chromosomes = tuple(params["chromosomes"])
    exp = Expected(synth_vcf_rows(int(params["store_records"]), seed,
                                  chromosomes), chromosomes)
    width = int(config["shapes"]["store_width"])
    n_ins = min(int(params["check_responses"]), int(params["max_inserts"]))
    new = traffic_inserts.new_records(exp, n_ins, seed)
    model = ycsb_e_check.ScanModel(exp, width, new)
    order = traffic_scans.scatter(exp.n_rows, seed)
    ops = Ops(exp, dict(params, clients=1, insert_proportion=0.0), order,
              new, seed, 0, 0, int(params["check_responses"])).clients[0][0]
    acked = np.ones(n_ins, bool)
    stale = acked.copy()
    stale[n_ins // 2:] = False

    def replies(shown, alter=None):
        out = []
        for _kind, spec, limit in ops:
            env = model.envelope(spec, limit, shown, 1)
            if alter is not None:
                alter(env)
            out.append((spec, limit, acked, acked, 200,
                        json.dumps(env).encode()))
        return out

    def short(env):
        if env["variants"]:
            env["variants"].pop()
            env["returned"] -= 1

    def swapped(env):
        rows = env["variants"]
        if len(rows) > 1:
            rows[0], rows[1] = rows[1], rows[0]

    def numbers(scans, builds=0, fsyncs=n_ins):
        out = ycsb_e_check.compare_scans(model, scans)
        out.pop("first_wrong")
        out.update(request_index_builds=builds,
                   fsync_short=fsync_short(n_ins, fsyncs))
        return out

    return {
        "stale_generation": numbers(replies(stale)),
        "one_row_short": numbers(replies(acked, short)),
        "rows_out_of_order": numbers(replies(acked, swapped)),
        "index_sorted_per_insert": numbers(replies(acked), builds=n_ins),
        "ack_before_fsync": numbers(replies(acked), fsyncs=0),
    }


# ---------------------------------------------------------------------------
# the run


def probe(children, rehearse: bool) -> None:
    """Fail the run, in seconds, on a program that cannot run this cell."""
    proc, _out, err_path = children.start(
        "probe_child", ["-c", PROBE],
        dict(child_env(rehearse), JAX_PLATFORMS="cpu"))
    rc = children.wait(proc, "probe_child", err_path, 120)
    if rc != 0:
        raise RunFailed(
            "this program's server does not count the interval index "
            "sorts its requests make (no /stats region_index."
            f"request_builds): it cannot run this cell (probe exit code {rc})")


def _ready(admin, rehearse: bool) -> dict:
    """Until ``/stats`` says every candidate chromosome's interval index is
    on the device and warmed, and the overload ladder is at rest."""
    t0 = time.monotonic()
    while True:
        stats = admin.get_json("/stats")
        index = stats.get("region_index") or {}
        level = (stats.get("brownout") or {}).get("level", 0)
        candidates = int(index.get("candidates", 0))
        if level == 0 and (rehearse or candidates > 0) \
                and index.get("device") == candidates:
            return {"seconds": round(time.monotonic() - t0, 2),
                    "region_index": index}
        if time.monotonic() - t0 > READY_SECONDS:
            raise RunFailed(
                f"warm-up: in {READY_SECONDS} s the interval indexes were "
                f"not ready or the overload ladder not at rest: "
                f"region_index {index}, brownout level {level}")
        time.sleep(0.2)


def _phase(processes, readers, ops: Ops, seconds, keep_every, keep_offset,
           started=None):
    """One closed-loop phase; (t_open, deadline, [(op, status, kept body,
    t_send, t_done)] per client).  A client that used up its operations
    ends the run: its requests would repeat."""
    t_open, deadline, outs = processes.window(
        readers, [heads for _ops, heads in ops.clients], seconds,
        keep_every, keep_offset, started=started)
    done = []
    for (client_ops, _heads), out in zip(ops.clients, outs):
        if len(out) > len(client_ops):
            raise RunFailed(f"a client sent more than its "
                            f"{len(client_ops)} operations; raise "
                            "requests_per_client")
        done.append([(client_ops[r[0]], r[1], r[2], r[3], r[4])
                     for r in out])
    return t_open, deadline, done


def run(ctx) -> dict:
    params = ctx.params
    chromosomes = tuple(params["chromosomes"])
    n_clients = int(params["clients"])
    processes = ReaderProcesses(n_clients)  # first: while this is small
    work = work_dir()
    children = Children(ctx.log_dir)
    trace_dir = os.path.join(work, "trace") if ctx.trace else None
    try:
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
        order = traffic_scans.scatter(exp.n_rows, ctx.seed)
        new = traffic_inserts.new_records(exp, int(params["max_inserts"]),
                                          ctx.seed)
        n_ops = int(params["requests_per_client"])
        mix = Ops(exp, params, order, new, ctx.seed, 1, 0, n_ops // 4)
        window_ops = Ops(exp, params, order, new, ctx.seed, 2,
                         mix.next_insert, n_ops)
        width = int(ctx.config["shapes"]["store_width"])
        model = ycsb_e_check.ScanModel(exp, width, new)
        # the first warm-up's lone point reads, as the point cell's
        warm_pool = traffic_requests.build(
            exp, dict(params, clients=1, requests_per_client=64,
                      ids_per_request=1, absent_per_request=0,
                      key_distribution={"zipf": params["zipf_theta"]}),
            ctx.seed)[0]
        note_json("generate", records=int(params["store_records"]),
                  rows_expected=exp.n_rows, vcf_bytes=os.path.getsize(vcf),
                  generate_seconds=round(t1 - t0, 2),
                  inserts_drawn=window_ops.next_insert,
                  traffic_seconds=round(time.monotonic() - t1, 2))
        built = finish_build(children, build, exp.n_rows)

        # ``serve --upserts``; the default flush triggers unless the
        # workload's rehearsal sizes name an age (a 2 s window)
        os.environ["AVDB_SERVE_UPSERTS"] = "1"
        if "memtable_flush_s" in params:
            os.environ["AVDB_MEMTABLE_FLUSH_S"] = str(
                params["memtable_flush_s"])
        t0 = time.monotonic()
        server, serve_err, control, host, port = start_server(
            ctx, children, work, store, params)
        startup_seconds = round(time.monotonic() - t0, 2)
        admin = Client(host, port)
        readers = [Reader(host, port) for _ in range(n_clients)]
        stats0 = admin.get_json("/stats")
        note_json("serve_up", startup_seconds=startup_seconds,
                  rows=stats0.get("rows"),
                  device=stats0.get("device"), compile=stats0.get("compile"))
        if stats0.get("rows") != exp.n_rows:
            raise RunFailed(f"the server holds {stats0.get('rows')} rows, "
                            f"the generator's first-wins load {exp.n_rows}")
        warm = warm_up(admin, readers, warm_pool, params,
                       len(chromosomes), ctx.rehearse)
        note_json("warm_up", **warm)
        note_json("ready", **_ready(admin, ctx.rehearse))
        client = ceiling(processes, 2.0, [head_of(rq) for rq in warm_pool])
        note_json("client_ceiling", **client)
        # the mix itself, inserts included: the overlay is live and the
        # flush clock runs from here
        _t, _d, warm_done = _phase(processes, readers, mix,
                                   float(params["warmup_mix_seconds"]),
                                   1 << 30, 0)
        warm_flat = [r for out in warm_done for r in out]
        note_json("warm_up_mix", operations=len(warm_flat),
                  inserts=sum(1 for r in warm_flat if r[0][0] == 1),
                  failed=sum(1 for r in warm_flat if r[1] != 200))

        prom_before = prom.parse(admin.get_text("/metrics"))
        stats_before = admin.get_json("/stats")
        rng = np.random.default_rng([int(ctx.seed), 3])
        keep_every = int(params["keep_every"])
        keep_offset = int(rng.integers(keep_every))

        def ask_for_trace():
            trace_s = min(float(params["trace_seconds"]), ctx.seconds / 2)
            time.sleep(min(2.0, ctx.seconds / 4))
            write_json_atomic(os.path.join(control, "trace.request"),
                              {"dir": trace_dir, "seconds": trace_s})

        t_open, deadline, outs = _phase(
            processes, readers, window_ops, ctx.seconds, keep_every,
            keep_offset, started=ask_for_trace if trace_dir else None)
        prom_after = prom.parse(admin.get_text("/metrics"))
        stats_after = admin.get_json("/stats")
        traced = None
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

        # ((kind, key, limit), status, kept body, t_send, t_done)
        done = [r for out in outs for r in out]
        if not done:
            raise RunFailed("no operation completed in the window")
        inserts = [r for r in warm_flat + done if r[0][0] == 1]
        n_new = len(new["pos"])
        acked_at = np.full(n_new, np.inf)
        sent_at = np.full(n_new, np.inf)
        for (_kind, j, _l), status, _body, t_send, t_done in inserts:
            sent_at[j] = t_send
            if status == 200:
                acked_at[j] = t_done
        dmodel = ycsb_d_check.Model(exp, order, new)
        for j in np.flatnonzero(np.isfinite(acked_at)).tolist():
            dmodel.insert(j)
        t0 = time.monotonic()
        read_back = _read_back(admin, dmodel)
        read_back_seconds = round(time.monotonic() - t0, 2)
        for reader in readers:
            reader.close()
        admin.conn.close()
        # durability without a restart: kill, then read what is on disk
        alive = server.poll() is None
        server.send_signal(signal.SIGKILL)
        try:
            server.wait(timeout=60)
        except subprocess.TimeoutExpired:
            pass
        exit_code = 0 if alive else server.returncode
        t0 = time.monotonic()
        durable = _durable(children, ctx, work, store, dmodel)
        durable_seconds = round(time.monotonic() - t0, 2)

        latencies = [(r[4] - r[3]) * 1000.0 for r in done]
        scans = [r for r in done if r[0][0] == 0]
        window_inserts = [r for r in done if r[0][0] == 1]
        n_ok = sum(1 for r in done if r[1] == 200)
        ops_in_window = sum(1 for r in done
                            if r[1] == 200 and r[4] <= deadline)
        statuses: dict = {}
        for r in done:
            name = f"{'scan' if r[0][0] == 0 else 'insert'}:{r[1]}"
            statuses[name] = statuses.get(name, 0) + 1

        def metric(name):
            return int(prom_after.get(name, 0) - prom_before.get(name, 0))

        note_json("window", operations=len(done), scans=len(scans),
                  inserts=len(window_inserts), statuses=statuses,
                  per_client_min=min(len(out) for out in outs),
                  per_client_max=max(len(out) for out in outs),
                  p50_ms=round(percentile(latencies, 50), 2),
                  p95_ms=round(percentile(latencies, 95), 2),
                  max_ms=round(max(latencies), 2),
                  scan_p95_ms=round(percentile(
                      [(r[4] - r[3]) * 1000.0 for r in scans], 95), 2)
                  if scans else None,
                  insert_p50_ms=round(percentile(
                      [(r[4] - r[3]) * 1000.0 for r in window_inserts], 50),
                      2) if window_inserts else None,
                  ops_in_window=ops_in_window,
                  flushes_in_window=metric("avdb_upsert_flushes_total"),
                  loop_max_turn_ms=(stats_after.get("loop") or {}).get(
                      "max_turn_ms"),
                  inserts_acked=len(dmodel.acked), exit_code=exit_code,
                  logs=ctx.log_dir)
        residency = stats_after.get("residency") or {}
        note_json("server", compile=stats_after.get("compile"),
                  residency=residency, snapshot=stats_after.get("snapshot"),
                  memtable=stats_after.get("memtable"),
                  overlay=stats_after.get("overlay"),
                  region_index=stats_after.get("region_index"),
                  region_scans=stats_after.get("region_scans"),
                  breaker_trips=metric("avdb_serve_breaker_trips_total"),
                  brownout_shed=metric("avdb_serve_brownout_shed_total"),
                  memory_peak_bytes=device["memory_peak_bytes"])

        t0 = time.monotonic()
        kept = [(spec, limit, acked_at < t_send, sent_at < t_done, status,
                 body)
                for (_k, spec, limit), status, body, t_send, t_done in scans
                if body is not None][:int(params["check_responses"])]
        numbers = ycsb_e_check.compare_scans(model, kept)
        first_wrong = numbers.pop("first_wrong")
        numbers["responses_malformed"] += read_back["responses_malformed"]
        numbers.update(
            records_wrong=read_back["records_wrong"],
            acked_not_read_back=read_back["acked_not_read_back"],
            acked_not_durable=ycsb_d_check.compare_durable(
                dmodel, durable["found"])["acked_not_durable"],
            request_index_builds=(
                stats_after["region_index"]["request_builds"]
                - stats_before["region_index"]["request_builds"]))
        note_json("check", scans_kept=numbers["scans_compared"],
                  records_compared=numbers["records_compared"],
                  read_back=read_back["read_back"],
                  read_back_seconds=read_back_seconds,
                  durable_rows=durable["rows"],
                  durable_seconds=durable_seconds,
                  seconds=round(time.monotonic() - t0, 2),
                  first_wrong=first_wrong)
        numbers.update(
            responses_uncompared=0 if numbers["scans_compared"] else 1,
            unanswered=sum(1 for r in done if r[1] is None),
            breaker_trips=int(prom_after.get(
                "avdb_serve_breaker_trips_total", 0)),
            segments_not_resident=0 if ctx.rehearse else max(
                len(chromosomes) - int(residency.get("resident", 0)), 0),
            server_exit_code=abs(exit_code),
            compiled_in_window=(stats_after["compile"]["programs"]
                                - stats_before["compile"]["programs"]),
            uploads_in_window=metric("avdb_serve_residency_uploads_total"),
            fsync_short=fsync_short(
                sum(1 for r in window_inserts if r[1] == 200),
                _wal_fsyncs(stats_before, stats_after)),
            client_ceiling_short=max(0, int(
                2 * ops_in_window / ctx.seconds - client["replies_per_s"])))
        checks = {name: check(numbers[name], limit)
                  for name, limit in LIMITS.items()}

        artefacts = {"prom_before": prom_before, "prom_after": prom_after,
                     "stats_before": stats_before, "stats_after": stats_after,
                     "requests_sent": len(done), "windows": 1}
        breakdown = None
        if trace_dir:
            reduced = xplane_idle.reduce_trace(
                trace_dir, None if traced is None
                else traced["t1"] - traced["t0"])
            gaps = xplane_named_all.reduce_trace(
                trace_dir, reduced and reduced["window_s"])
            xplane_idle.keep_capture(trace_dir, ctx.log_dir)
            note_json("trace", **xplane_idle.summary(reduced),
                      **{k: v for k, v in (gaps or {}).items()
                         if k != "idle_gaps"})
            artefacts["xplane"] = reduced
            artefacts["xplane_all_gaps"] = gaps
            breakdown = dict(xplane_idle.breakdown(reduced),
                             **({"idle_gaps": gaps["idle_gaps"]}
                                if gaps else {}))
        return {
            "end_to_end": {
                "serve_keys_per_s": ops_in_window / ctx.seconds,
                "serve_p95_ms": percentile(latencies, 95),
                "setup_s": t_open - ctx.t_start,
            },
            "attempted": len(done),
            "failed": len(done) - n_ok,
            "checks": checks,
            "device": device,
            "artefacts": artefacts,
            "breakdown": breakdown,
        }
    finally:
        children.stop_all()
        processes.close()
        shutil.rmtree(work, ignore_errors=True)
