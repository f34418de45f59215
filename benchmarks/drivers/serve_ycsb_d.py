"""The YCSB workload D kind of cell: closed-loop clients that mostly read
the newest records while a share of their operations insert new ones,
against the store ``drivers/serve.py`` builds, served with upserts on
(``AVDB_SERVE_UPSERTS=1``, the environment spelling of ``serve
--upserts``), every other knob at its default.

Its set-up, its server and its first warm-up are ``drivers/serve.py``'s;
its clients are ``drivers/serve_point.py``'s raw-socket reader processes,
one a client, each on its own keep-alive connection, and the client's
ceiling is measured and checked as there (``client_ceiling_short``).

Each operation is drawn (``traffic/inserts.py``): with
``insert_proportion`` a ``POST /variants/upsert`` of the next new record,
else a ``GET /variant/<id>`` of the record ``rank`` places back from the
newest one acknowledged, ``rank`` Zipf-distributed.  The acknowledged
sequence is YCSB's: the stored rows in a seeded recency order, then the
inserts in the order they were handed out, a record eligible once every
earlier insert is acknowledged too (the suite's
``AcknowledgedCounterGenerator``) — one counter, in memory every client
process shares.  A second, short warm-up runs the same mix, so the
overlay is live and the memtable's flush clock runs before the window
opens; with the default 30 s flush age the one flush of each 40 s window
lands inside it.

The end-to-end numbers are the point cell's: operations of 200 replies
completed inside the window over the window, the 95th percentile over all
operations sent, the set-up.  After the window, outside every clock: every
acknowledged insert is read back (``POST /variants``), the server is
SIGKILLed, and ``children/durable_child.py`` reads what it left on disk —
the committed store and the WAL.  ``reference/ycsb_d_check.py`` compares
every kept read, every read-back record and every acknowledged insert's
durable copy with its model; the limits are all 0, and so are
``uploads_in_window`` (a segment the server holds uploaded again) and
``compiled_in_window``.

The SIGKILL proves that an acknowledged insert was written, not that it
was fsync'd: the killed process's writes stay in the page cache, which a
kill does not drop.  The fsync is held by ``fsync_short`` — the window's
acknowledged inserts less the WAL frames whose fsync returned (``/stats``
``memtable.wal_fsyncs``, counted after the fsync in the append the ack
waits for) — whose limit is 0 too.
"""

from __future__ import annotations

import json
import mmap
import multiprocessing
import os
import pickle
import shutil
import signal
import socket
import struct
import subprocess
import time

import numpy as np

from common import (Children, RunFailed, check, child_env, load_json,
                    note_json, percentile, wait_for_file, work_dir,
                    write_json_atomic)
from drivers import serve_point
from drivers.serve import (BENCH, Client, build_store, finish_build,
                           start_server, warm_up)
from drivers.serve_point import (Reader, ReaderProcesses, ceiling, head_of,
                                 read_exactly, reader_loop)
from readers import prom, xplane_idle, xplane_named_all
from reference import ycsb_d_check
from traffic import inserts as traffic_inserts
from traffic import requests as traffic_requests
from traffic.vcf import Expected, synth_vcf_rows, write_synth_vcf

#: the point cell's limits (the serving limits and the client's ceiling),
#: and the model's
LIMITS = dict(serve_point.LIMITS, reads_wrong=0, acked_not_read_back=0,
              acked_not_durable=0, uploads_in_window=0, fsync_short=0)

#: the faults of ``tests/faults`` that bite here: ``serve.half_left_out``
#: the read-back's bulk lookups, ``serve.answer_altered`` a drain's lone
#: miss of a chromosome (the scalar renderer)
FAULTS = ("serve.half_left_out", "serve.answer_altered")

#: ids a read-back request names
READ_BACK_IDS = 1024

_INSERT_HEAD = ("POST /variants/upsert HTTP/1.1\r\nHost: bench\r\n"
                "Content-Type: application/json\r\nContent-Length: %d\r\n\r\n")


# ---------------------------------------------------------------------------
# the acknowledged sequence, shared by every client process


class Sequence:
    """YCSB's insert counter and its acknowledged counter in memory every
    forked process shares: ``[next insert, acknowledged limit, one flag an
    insert...]``.  An insert takes the next number; its acknowledgement
    sets its flag and moves the limit over every flag set in a row, so the
    limit is the count of inserts that all earlier ones preceded in
    acknowledgement.  A read takes the limit without the lock (one aligned
    word)."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._map = mmap.mmap(-1, 8 * (2 + self.capacity))  # MAP_SHARED
        self.words = np.frombuffer(self._map, np.int64)
        self.lock = multiprocessing.Lock()

    def take(self) -> int:
        with self.lock:
            seq = int(self.words[0])
            self.words[0] = seq + 1
        return seq

    def acknowledge(self, seq: int) -> None:
        words = self.words
        with self.lock:
            words[2 + seq] = 1
            limit = int(words[1])
            while limit < self.capacity and words[2 + limit]:
                limit += 1
            words[1] = limit

    def limit(self) -> int:
        return int(self.words[1])

    def acknowledged(self) -> list:
        """The acknowledged inserts, in insert order."""
        return np.flatnonzero(self.words[2:] == 1).tolist()

    def taken(self) -> int:
        return int(self.words[0])


def ycsb_loop(reader, spec, seq: Sequence, deadline, keep_every,
              keep_offset, out):
    """One client's closed loop until the deadline; ``out`` gets one tuple
    an operation: (kind 0 read / 1 insert, key or insert number, status,
    acknowledged, kept body or None, t_send, t_done)."""
    base = {name: np.load(os.path.join(spec["dir"], f"base_{name}.npy"),
                          mmap_mode="r")
            for name in ("chrom", "pos", "ref", "alt")}
    with np.load(os.path.join(spec["dir"], "new.npz")) as z:
        new = {name: z[name] for name in z.files}
    labels, n_base = spec["chromosomes"], spec["n_base"]
    kinds, ranks = spec["kinds"], spec["ranks"]
    clock, exchange, n = time.monotonic, reader.exchange, len(kinds)
    k = 0
    t_send = clock()
    while t_send < deadline:
        j = seq.take() if kinds[k % n] else -1
        if 0 <= j < seq.capacity:
            body = traffic_inserts.upsert_body(labels, new, j)
            status, reply = exchange(
                (_INSERT_HEAD % len(body)).encode() + body)
            acked = status == 200 and b'"accepted":1' in reply
            if acked:
                seq.acknowledge(j)
            t_done = clock()
            out.append((1, j, status, acked, None, t_send, t_done))
        else:  # a read (or an insert past the drawn records: read instead)
            key = n_base + seq.limit() - 1 - int(ranks[k % n])
            cols, at = (base, key) if key < n_base else (new, key - n_base)
            status, reply = exchange(
                f"GET /variant/{traffic_inserts.ident(labels, cols, at)} "
                f"HTTP/1.1\r\nHost: bench\r\n\r\n".encode())
            t_done = clock()
            out.append((0, key, status, status == 200,
                        reply if k % keep_every == keep_offset else None,
                        t_send, t_done))
        k += 1
        t_send = t_done


def take_jobs(channel, seq: Sequence) -> None:
    """``serve_point.take_jobs`` for both kinds of job: a list of point
    heads (the ceiling) runs the point loop, a spec the YCSB D loop."""
    while True:
        size, fds, _flags, _address = socket.recv_fds(channel, 8, 1)
        if not size:
            return  # the driver is done
        address, job, keep_every, keep_offset = pickle.loads(
            read_exactly(channel, struct.unpack("q", size)[0]))
        reader = Reader(*address, sock=socket.socket(fileno=fds[0]))
        channel.sendall(b"r")  # ready: the clock may start
        (deadline,) = struct.unpack("d", read_exactly(channel, 8))
        out = []
        if isinstance(job, dict):
            ycsb_loop(reader, job, seq, deadline, keep_every, keep_offset,
                      out)
        else:
            reader_loop(reader, job, deadline, keep_every, keep_offset, out)
        reader.close()
        data = pickle.dumps(out)
        channel.sendall(struct.pack("q", len(data)) + data)


class ClientProcesses(ReaderProcesses):
    """``serve_point.ReaderProcesses`` whose processes also run the D
    loop, over one :class:`Sequence` created before they fork."""

    def __init__(self, n: int, seq: Sequence):
        self.channels, self.pids = [], []
        for _ in range(n):
            ours, theirs = socket.socketpair()
            pid = os.fork()
            if pid == 0:  # the child: its jobs, nothing else
                code = 1
                try:
                    for channel in (ours, *self.channels):
                        channel.close()
                    take_jobs(theirs, seq)
                    code = 0
                finally:
                    os._exit(code)
            theirs.close()
            self.channels.append(ours)
            self.pids.append(pid)


# ---------------------------------------------------------------------------
# the traffic


def _stage(exp, params: dict, seed: int, where: str) -> dict:
    """Write what the client processes read (the stored rows in recency
    order, the new records) under ``where``; the model and the draws."""
    chromosomes = exp.chromosomes
    order = traffic_inserts.recency(exp.n_rows, seed)
    for name in ("chrom", "pos", "ref", "alt"):
        np.save(os.path.join(where, f"base_{name}.npy"),
                exp.kept[name][order])
    new = traffic_inserts.new_records(exp, int(params["max_inserts"]), seed)
    np.savez(os.path.join(where, "new.npz"), **new)
    cdf = traffic_inserts.zipf_cdf(exp.n_rows, params["zipf_theta"])
    specs = []
    for client in range(int(params["clients"])):
        kinds, ranks = traffic_inserts.client_draws(
            cdf, int(params["requests_per_client"]),
            params["insert_proportion"], seed, client)
        specs.append({"dir": where, "chromosomes": list(chromosomes),
                      "n_base": exp.n_rows, "kinds": kinds, "ranks": ranks})
    return {"model": ycsb_d_check.Model(exp, order, new), "specs": specs}


def controls(config: dict, params: dict, seed: int, seconds: float) -> dict:
    """{broken guarantee: the numbers compared}: the reference answers a
    run's worth of D operations with one guarantee broken
    (``control.py``).

    ``stale_overlay`` — reads that skip the memtable overlay (an insert
    not yet flushed answers 404); ``ack_before_fsync`` — acknowledgements
    sent with no fsync of their WAL frame before them: the offline check
    after the kill still finds every frame (a SIGKILL leaves the page
    cache), so only ``fsync_short`` sees it; ``reload_on_flush`` — the
    flush reloads the store and uploads every resident segment again."""
    chromosomes = tuple(params["chromosomes"])
    exp = Expected(synth_vcf_rows(int(params["store_records"]), seed,
                                  chromosomes), chromosomes)
    n_ins = min(int(params["check_responses"]), int(params["max_inserts"]))
    order = traffic_inserts.recency(exp.n_rows, seed)
    new = traffic_inserts.new_records(exp, n_ins, seed)
    model = ycsb_d_check.Model(exp, order, new)
    for j in range(n_ins):
        model.insert(j)
    inserted = [exp.n_rows + j for j in range(n_ins)]
    keys = inserted + [int(k) for k in np.random.default_rng(
        [int(seed), 7]).integers(0, exp.n_rows, n_ins)]
    flushed = n_ins // 2  # the inserts before the window's flush

    def reply(key, stale=False):
        want = model.record(key)
        if stale and key - exp.n_rows >= flushed:
            want = None
        return (key, 200 if want else 404,
                json.dumps(want or {"error": "not in store"}).encode())

    def durable():
        found = {}
        for j in model.acked:
            ident = traffic_inserts.ident(chromosomes, new, j)
            fields = ycsb_d_check.durable_fields(new, j)
            in_store = j < flushed
            found[ident] = {"store": fields if in_store else None,
                            "wal": None if in_store else fields}
        return found

    def numbers(reads, found, uploads=0, fsyncs=None):
        out = ycsb_d_check.compare_reads(model, reads)
        out.pop("first_wrong")
        out.update(ycsb_d_check.compare_durable(model, found),
                   uploads_in_window=uploads,
                   fsync_short=fsync_short(
                       len(model.acked),
                       len(model.acked) if fsyncs is None else fsyncs))
        return out

    straight = [reply(k) for k in keys]
    return {
        "stale_overlay": numbers([reply(k, stale=True) for k in keys],
                                 durable()),
        "ack_before_fsync": numbers(straight, durable(), fsyncs=0),
        "reload_on_flush": numbers(straight, durable(),
                                   uploads=len(chromosomes)),
    }


# ---------------------------------------------------------------------------
# the run


def fsync_short(acked: int, fsyncs) -> int:
    """Acknowledged inserts no fsync'd WAL frame accounts for; every one
    of them when the program counts no fsyncs (``fsyncs`` None)."""
    return max(0, acked - (0 if fsyncs is None else int(fsyncs)))


def _wal_fsyncs(before: dict, after: dict):
    """The WAL frames fsync'd between two ``/stats`` reads, or None."""
    try:
        return (after["memtable"]["wal_fsyncs"]
                - before["memtable"]["wal_fsyncs"])
    except (KeyError, TypeError):
        return None


def _read_back(admin, model) -> dict:
    """Every acknowledged insert, read back by ``POST /variants``."""
    replies = []
    acked = list(model.acked)
    for at in range(0, len(acked), READ_BACK_IDS):
        js = acked[at:at + READ_BACK_IDS]
        ids = [traffic_inserts.ident(model.exp.chromosomes, model.new, j)
               for j in js]
        admin.conn.request("POST", "/variants",
                           body=json.dumps({"ids": ids}).encode(),
                           headers={"Content-Type": "application/json"})
        response = admin.conn.getresponse()
        replies.append((js, response.status, response.read()))
    return ycsb_d_check.compare_read_back(model, replies)


def _durable(children, ctx, work, store, model) -> dict:
    """What the killed server left on disk, read by the program offline."""
    ids_path = os.path.join(work, "acked.json")
    out_path = os.path.join(work, "durable.json")
    write_json_atomic(ids_path, [
        traffic_inserts.ident(model.exp.chromosomes, model.new, j)
        for j in model.acked])
    env = child_env(ctx.rehearse)
    env["JAX_PLATFORMS"] = "cpu"  # reads files; the chip is not needed
    proc, _out, err_path = children.start(
        "durable_child",
        [os.path.join(BENCH, "children", "durable_child.py"), store,
         ids_path, out_path], env)
    rc = children.wait(proc, "durable_child", err_path, 900)
    if rc != 0 or not os.path.exists(out_path):
        raise RunFailed(f"durable_child: exit code {rc}")
    return load_json(out_path)


def run(ctx) -> dict:
    params = ctx.params
    chromosomes = tuple(params["chromosomes"])
    n_clients = int(params["clients"])
    seq = Sequence(int(params["max_inserts"]))
    processes = ClientProcesses(n_clients, seq)  # first: while this is small
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
        staged = _stage(exp, params, ctx.seed, work)
        model, specs = staged["model"], staged["specs"]
        # the first warm-up's lone point reads, as the point cell's
        warm_pool = traffic_requests.build(
            exp, dict(params, clients=1, requests_per_client=64),
            ctx.seed)[0]
        note_json("generate", records=int(params["store_records"]),
                  rows_expected=exp.n_rows, vcf_bytes=os.path.getsize(vcf),
                  generate_seconds=round(t1 - t0, 2),
                  new_records=int(params["max_inserts"]),
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
        client = ceiling(processes, 2.0, [head_of(rq) for rq in warm_pool])
        note_json("client_ceiling", **client)
        # the mix itself, inserts included: the overlay is live and the
        # flush clock runs from here
        _t, _d, outs = processes.window(
            readers, specs, float(params["warmup_mix_seconds"]), 1 << 30, 0)
        warm_ops = [r for out in outs for r in out]
        note_json("warm_up_mix", operations=len(warm_ops),
                  inserts_acked=seq.limit(),
                  failed=sum(1 for r in warm_ops if not r[3]))

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

        t_open, deadline, outs = processes.window(
            readers, specs, ctx.seconds, keep_every, keep_offset,
            started=ask_for_trace if trace_dir else None)
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
        for j in seq.acknowledged():
            model.insert(j)
        t0 = time.monotonic()
        read_back = _read_back(admin, model)
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
        durable = _durable(children, ctx, work, store, model)
        durable_seconds = round(time.monotonic() - t0, 2)

        # (kind, key, status, acknowledged, kept body, t_send, t_done)
        done = [r for out in outs for r in out]
        if not done:
            raise RunFailed("no operation completed in the window")
        latencies = [(r[6] - r[5]) * 1000.0 for r in done]
        slowest = max(range(len(done)), key=latencies.__getitem__)
        n_ok = sum(1 for r in done if r[3])
        ops_in_window = sum(1 for r in done if r[3] and r[6] <= deadline)
        reads = [r for r in done if r[0] == 0]
        inserts = [r for r in done if r[0] == 1]
        unanswered = sum(1 for r in done if r[2] is None)
        statuses: dict = {}
        for r in done:
            name = f"{'read' if r[0] == 0 else 'insert'}:{r[2]}"
            statuses[name] = statuses.get(name, 0) + 1

        def metric(name):
            return int(prom_after.get(name, 0) - prom_before.get(name, 0))

        note_json("window", operations=len(done), reads=len(reads),
                  inserts=len(inserts), statuses=statuses,
                  per_client_min=min(len(out) for out in outs),
                  per_client_max=max(len(out) for out in outs),
                  p50_ms=round(percentile(latencies, 50), 2),
                  p95_ms=round(percentile(latencies, 95), 2),
                  max_ms=round(max(latencies), 2),
                  max_kind="read" if done[slowest][0] == 0 else "insert",
                  max_sent_at_s=round(done[slowest][5] - t_open, 3),
                  loop_max_turn_ms=(stats_after.get("loop") or {}).get(
                      "max_turn_ms"),
                  read_p95_ms=round(percentile(
                      [(r[6] - r[5]) * 1000.0 for r in reads], 95), 2)
                  if reads else None,
                  insert_p50_ms=round(percentile(
                      [(r[6] - r[5]) * 1000.0 for r in inserts], 50), 2)
                  if inserts else None,
                  ops_in_window=ops_in_window,
                  flushes_in_window=metric("avdb_upsert_flushes_total"),
                  inserts_acked=len(model.acked),
                  inserts_taken=seq.taken(), exit_code=exit_code,
                  logs=ctx.log_dir)
        residency = stats_after.get("residency") or {}
        note_json("server", compile=stats_after.get("compile"),
                  residency=residency, snapshot=stats_after.get("snapshot"),
                  memtable=stats_after.get("memtable"),
                  overlay=stats_after.get("overlay"),
                  breaker_trips=metric("avdb_serve_breaker_trips_total"),
                  brownout_shed=metric("avdb_serve_brownout_shed_total"),
                  device_lookup=stats_after.get("device_lookup"),
                  render_cache=stats_after.get("render_cache"),
                  memory_peak_bytes=device["memory_peak_bytes"])

        t0 = time.monotonic()
        numbers = ycsb_d_check.compare_reads(
            model, [(r[1], r[2], r[4]) for r in reads if r[4] is not None])
        first_wrong = numbers.pop("first_wrong")
        numbers["responses_malformed"] += read_back["responses_malformed"]
        numbers.update(
            records_wrong=read_back["records_wrong"],
            acked_not_read_back=read_back["acked_not_read_back"],
            acked_not_durable=ycsb_d_check.compare_durable(
                model, durable["found"])["acked_not_durable"])
        note_json("check", reads_kept=numbers["reads_compared"],
                  read_back=read_back["read_back"],
                  read_back_seconds=read_back_seconds,
                  durable_rows=durable["rows"],
                  durable_seconds=durable_seconds,
                  seconds=round(time.monotonic() - t0, 2),
                  first_wrong=first_wrong)
        numbers.update(
            responses_uncompared=0 if numbers["reads_compared"] else 1,
            unanswered=unanswered,
            breaker_trips=int(prom_after.get(
                "avdb_serve_breaker_trips_total", 0)),
            segments_not_resident=0 if ctx.rehearse else max(
                len(chromosomes) - int(residency.get("resident", 0)), 0),
            server_exit_code=abs(exit_code),
            compiled_in_window=(stats_after["compile"]["programs"]
                                - stats_before["compile"]["programs"]),
            uploads_in_window=metric("avdb_serve_residency_uploads_total"),
            fsync_short=fsync_short(sum(1 for r in inserts if r[3]),
                                    _wal_fsyncs(stats_before, stats_after)),
            client_ceiling_short=max(0, int(
                2 * ops_in_window / ctx.seconds - client["replies_per_s"])))
        checks = {name: check(numbers[name], limit)
                  for name, limit in LIMITS.items()}

        artefacts = {"prom_before": prom_before, "prom_after": prom_after,
                     "stats_before": stats_before, "stats_after": stats_after,
                     "ids_sent": len(reads), "reads_sent": len(reads),
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
