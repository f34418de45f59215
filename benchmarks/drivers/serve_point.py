"""The point-read kind of cell: many concurrent readers, one id a request,
against the store ``drivers/serve.py`` builds and serves (its set-up, its
warm-up and its admin client, imported as they stand).

The window: one process per reader, each on its own keep-alive connection,
closed loop (YCSB's client threads): a reader sends its next
``GET /variant/<id>`` when its reply has arrived, for ``--seconds``.  The
three end-to-end numbers are ``drivers/serve.py``'s by the same
definitions: ``serve_keys_per_s`` is the ids of the 200-replies that
completed inside the window over the window, ``serve_p95_ms`` the 95th
percentile of send -> last byte over all requests sent, ``setup_s`` the
start of ``run.py`` -> the window opens.  Every stored id exists, so a
reply other than 200 is a failure: it counts no key, goes to ``failed``,
and — a 404 being an answer — is compared like any other.

The client must not be what is measured.  A reader is a raw socket in a
process of its own (:class:`ReaderProcesses`): the request heads are
encoded in set-up, a reply is read by its ``Content-Length``, a request
costs the reader one ``send`` and one ``recv`` and no reader waits for
another's interpreter lock.  :func:`ceiling` drives this window against a
canned responder (one
process a connection, each answering every GET at once with one fixed 200
body of a record's size: a peer that cannot be what holds the readers
back) and gives the rate the client alone sustains.  Every run measures
it, after the warm-up, and checks it: ``client_ceiling_short`` (limit 0)
is how far the ceiling lies under twice the rate the run then read —
where the client could be what is measured the run says not correct
(PERF.md section 4).  ``python3 benchmarks/drivers/serve_point.py
--ceiling`` prints it alone.

The server coalesces concurrent point reads into microbatches, so what can
go wrong here and not in the bulk cell is a reply that is another
request's: ``controls`` breaks that, and two more guarantees, in the
shape of single-record replies.
"""

from __future__ import annotations

import json
import os
import pickle
import shutil
import signal
import socket
import subprocess
import struct
import sys
import time

import numpy as np

if __name__ == "__main__":  # the ceiling mode is run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from common import (Children, RunFailed, check, load_json, note_json,
                    percentile, wait_for_file, work_dir, write_json_atomic)
from drivers import serve
from drivers.serve import (Client, build_store, finish_build, start_server,
                           warm_up)
from readers import prom, xplane_idle, xplane_named_all
from reference import answers_check
from traffic import requests as traffic_requests
from traffic.vcf import Expected, synth_vcf_rows, write_synth_vcf

#: the faults of ``tests/faults`` that a cell of this kind can have:
#: ``serve.half_left_out`` bites any microbatch of four ids or more;
#: ``serve.answer_altered`` wraps ``engine._render_row``, which a
#: co-batched read does not go through (PERF.md section 7, question 13)
FAULTS = ("serve.half_left_out",)

#: ``drivers/serve.py``'s limits, and the client's own: replies a second
#: by which its ceiling lies under twice the rate the run read
LIMITS = dict(serve.LIMITS, client_ceiling_short=0)


def head_of(request) -> bytes:
    """The bytes of one point read on a keep-alive connection (no
    ``Connection`` header: HTTP/1.1 keeps alive, and the server's fast
    path takes the request)."""
    return f"GET {request.path} HTTP/1.1\r\nHost: bench\r\n\r\n".encode()


class Reader:
    """One keep-alive connection as a raw socket, one request in flight."""

    def __init__(self, host: str, port: int, sock=None):
        self.address = (host, port)
        self.sock = sock
        if sock is None:
            self.connect()
        else:  # adopted as a descriptor: the time limit is the object's
            sock.settimeout(120)

    def connect(self) -> None:
        self.sock = socket.create_connection(self.address, timeout=120)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def exchange(self, head: bytes):
        """(status or None, body bytes): send one request head, read one
        reply by its ``Content-Length``."""
        try:
            self.sock.sendall(head)
            buf = self.sock.recv(65536)
            while True:
                end = buf.find(b"\r\n\r\n")
                if end >= 0:
                    break
                more = self.sock.recv(65536)
                if not more:
                    raise OSError("connection closed inside a reply head")
                buf += more
            lower = buf[:end].lower()
            at = lower.find(b"content-length:")
            eol = lower.find(b"\r\n", at)
            length = int(lower[at + 15:eol if eol >= 0 else end])
            body = buf[end + 4:]
            while len(body) < length:
                more = self.sock.recv(65536)
                if not more:
                    raise OSError("connection closed inside a reply body")
                body += more
            return int(buf[9:12]), body
        except (OSError, ValueError):
            self.sock.close()
            self.connect()
            return None, b""

    def call(self, request):
        """``drivers.serve.Client.call``'s tuple, for its ``warm_up``."""
        t_send = time.monotonic()
        status, body = self.exchange(head_of(request))
        return status, body, t_send, time.monotonic()

    def close(self) -> None:
        self.sock.close()


def reader_loop(reader, heads, deadline, keep_every, keep_offset, out):
    """Closed loop until the deadline; ``out`` gets one tuple per request:
    (index into the reader's requests, status, kept body or None, t_send,
    t_done)."""
    clock, exchange, n = time.monotonic, reader.exchange, len(heads)
    k = 0
    t_send = clock()
    while t_send < deadline:
        status, body = exchange(heads[k % n])
        t_done = clock()
        out.append((k % n, status,
                    body if k % keep_every == keep_offset else None,
                    t_send, t_done))
        k += 1
        t_send = t_done


def read_exactly(channel, size: int) -> bytes:
    parts = []
    while size:
        part = channel.recv(min(size, 1 << 20))
        if not part:
            raise EOFError("the other end of a reader's channel is gone")
        parts.append(part)
        size -= len(part)
    return b"".join(parts)


def take_jobs(channel) -> None:
    """A reader process's life: for each job on its channel — a connected
    socket (passed as a descriptor), its request heads, then the deadline —
    run the reader's closed loop and send back what it saw."""
    while True:
        size, fds, _flags, _address = socket.recv_fds(channel, 8, 1)
        if not size:
            return  # the driver is done
        address, heads, keep_every, keep_offset = pickle.loads(
            read_exactly(channel, struct.unpack("q", size)[0]))
        reader = Reader(*address, sock=socket.socket(fileno=fds[0]))
        channel.sendall(b"r")  # ready: the clock may start
        (deadline,) = struct.unpack("d", read_exactly(channel, 8))
        out = []
        reader_loop(reader, heads, deadline, keep_every, keep_offset, out)
        reader.close()  # this process's copy of the descriptor
        data = pickle.dumps(out)
        channel.sendall(struct.pack("q", len(data)) + data)


class ReaderProcesses:
    """One process a reader (a thread each would queue for one interpreter
    lock), forked while this process is still small, so that a fork's
    cost does not grow with the store's rows it holds later.  A process
    never touched JAX and has no thread but its main one; it is handed its
    connection when a window opens."""

    def __init__(self, n: int):
        self.channels, self.pids = [], []
        for _ in range(n):
            ours, theirs = socket.socketpair()
            pid = os.fork()
            if pid == 0:  # the child: its jobs, nothing else
                code = 1
                try:
                    for channel in (ours, *self.channels):
                        channel.close()
                    take_jobs(theirs)
                    code = 0
                finally:
                    os._exit(code)
            theirs.close()
            self.channels.append(ours)
            self.pids.append(pid)

    def window(self, readers, heads, seconds, keep_every, keep_offset,
               started=None):
        """Run every reader's closed loop for ``seconds``; (t_open,
        deadline, one list of tuples per reader).  The readers' connections
        are open already and stay this process's too: each goes to its
        process as a descriptor, with its heads; when all are ready the
        deadline follows, so all start together.  ``time.monotonic`` is one
        clock for every process of the machine.  ``started`` is called once
        the readers run (the traced run asks for its capture there)."""
        channels = self.channels[:len(readers)]
        for channel, reader, pool in zip(channels, readers, heads):
            job = pickle.dumps((reader.address, pool, keep_every,
                                keep_offset))
            socket.send_fds(channel, [struct.pack("q", len(job))],
                            [reader.sock.fileno()])
            channel.sendall(job)
        for channel in channels:
            read_exactly(channel, 1)
        t_open = time.monotonic()
        deadline = t_open + seconds
        for channel in channels:
            channel.sendall(struct.pack("d", deadline))
        if started is not None:
            started()
        outs = []
        for channel in channels:
            (size,) = struct.unpack("q", read_exactly(channel, 8))
            outs.append(pickle.loads(read_exactly(channel, size)))
        return t_open, deadline, outs

    def close(self) -> None:
        for channel in self.channels:
            channel.close()
        for pid in self.pids:
            os.waitpid(pid, 0)


# ---------------------------------------------------------------------------
# the controls: the reference in the program's place, one guarantee broken


def controls(config: dict, params: dict, seed: int, seconds: float) -> dict:
    """{broken guarantee: the numbers compared}: the reference answers as
    many point reads as a run compares, one guarantee broken
    (``control.py``).

    ``stale_generation`` — answers from the store before its last
    chromosome block was committed (those ids answer 404);
    ``neighbour_row`` — the row that sorts next to the key (approximate);
    ``crossed_replies`` — two co-batched requests get each other's
    record."""
    chromosomes = tuple(params["chromosomes"])
    exp = Expected(synth_vcf_rows(int(params["store_records"]), seed,
                                  chromosomes), chromosomes)
    sampled = traffic_requests.build(
        exp, dict(params, clients=1,
                  requests_per_client=int(params["check_responses"])),
        seed)[0]
    chrom = exp.kept["chrom"]
    last_chrom = len(chromosomes) - 1

    def reply(i: int):
        return 200, json.dumps(answers_check.expected_record(
            exp, exp.ident(i), i)).encode()

    def own(request):
        return reply(request.rows[0])

    def stale(request):
        i = request.rows[0]
        if int(chrom[i]) == last_chrom:
            return 404, json.dumps({"error": f"variant {request.ids[0]!r} "
                                             "not in store"}).encode()
        return reply(i)

    def neighbour(request):
        i = request.rows[0]
        j = i + 1 if i + 1 < exp.n_rows and chrom[i + 1] == chrom[i] \
            else i - 1
        return reply(j)

    straight = [own(rq) for rq in sampled]
    crossed = list(straight)
    for k in range(0, len(crossed) - 1, 2):
        crossed[k], crossed[k + 1] = crossed[k + 1], crossed[k]
    broken = {
        "stale_generation": [stale(rq) for rq in sampled],
        "neighbour_row": [neighbour(rq) for rq in sampled],
        "crossed_replies": crossed,
    }
    out = {}
    for name, replies in broken.items():
        numbers = answers_check.compare(
            exp, [(rq, status, body)
                  for rq, (status, body) in zip(sampled, replies)])
        numbers.pop("first_wrong")
        out[name] = numbers
    return out


# ---------------------------------------------------------------------------
# the run


def run(ctx) -> dict:
    params = ctx.params
    chromosomes = tuple(params["chromosomes"])
    n_readers = int(params["clients"])
    processes = ReaderProcesses(n_readers)  # first: while this one is small
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
        # one more list than readers: the warm-up's own requests
        pools = traffic_requests.build(
            exp, dict(params, clients=n_readers + 1), ctx.seed)
        warm_pool = pools.pop()[:64]
        heads = [[head_of(rq) for rq in pool] for pool in pools]
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
        readers = [Reader(host, port) for _ in range(n_readers)]
        stats0 = admin.get_json("/stats")
        note_json("serve_up", startup_seconds=startup_seconds,
                  rows=stats0.get("rows"),
                  device=stats0.get("device"), compile=stats0.get("compile"))
        if stats0.get("rows") != exp.n_rows:
            raise RunFailed(f"the server holds {stats0.get('rows')} rows, "
                            f"the generator's first-wins load {exp.n_rows}")
        # lone requests only: the warm-up is not what compiles the shapes
        # a microbatch takes
        warm = warm_up(admin, readers, warm_pool, params,
                       len(chromosomes), ctx.rehearse)
        note_json("warm_up", **warm)
        # what the client alone sustains on this machine, now: the server
        # is idle and the window has not opened
        client = ceiling(processes, 2.0, heads[0])
        note_json("client_ceiling", **client)

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
            readers, heads, ctx.seconds, keep_every, keep_offset,
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
        for reader in readers:
            reader.close()
        admin.conn.close()
        server.send_signal(signal.SIGTERM)
        try:
            exit_code = server.wait(timeout=60)
        except subprocess.TimeoutExpired:
            server.kill()
            server.wait()
            exit_code = -9

        # (request, status, kept body or None, t_send, t_done)
        done = [(pool[k], status, body, t_send, t_done)
                for pool, out in zip(pools, outs)
                for k, status, body, t_send, t_done in out]
        if not done:
            raise RunFailed("no request completed in the window")
        latencies = [(t_done - t_send) * 1000.0
                     for _r, _s, _b, t_send, t_done in done]
        n_ok = sum(1 for r in done if r[1] == 200)
        keys_in_window = sum(1 for r in done
                             if r[1] == 200 and r[4] <= deadline)
        unanswered = sum(1 for r in done if r[1] is None)
        statuses: dict = {}
        for r in done:
            statuses[str(r[1])] = statuses.get(str(r[1]), 0) + 1
        note_json("window", requests=len(done), statuses=statuses,
                  per_reader_min=min(len(out) for out in outs),
                  per_reader_max=max(len(out) for out in outs),
                  p50_ms=round(percentile(latencies, 50), 2),
                  p95_ms=round(percentile(latencies, 95), 2),
                  max_ms=round(max(latencies), 2),
                  keys_in_window=keys_in_window, exit_code=exit_code,
                  logs=ctx.log_dir)

        def metric(name):
            return int(prom_after.get(name, 0))

        artefacts = {"prom_before": prom_before, "prom_after": prom_after,
                     "stats_before": stats_before, "stats_after": stats_after,
                     "ids_sent": len(done), "requests_sent": len(done)}
        residency = stats_after.get("residency") or {}
        drains = stats_after["batcher"]["batches"] \
            - stats_before["batcher"]["batches"]
        note_json("server", compile=stats_after.get("compile"),
                  residency=residency, drains=drains,
                  ids_a_drain=round(len(done) / drains, 2) if drains else None,
                  residency_uploads=metric(
                      "avdb_serve_residency_uploads_total"),
                  resident_bytes=metric("avdb_serve_resident_bytes"),
                  breaker_trips=metric("avdb_serve_breaker_trips_total"),
                  brownout_shed=metric("avdb_serve_brownout_shed_total"),
                  device_lookup=stats_after.get("device_lookup"),
                  render_cache=stats_after.get("render_cache"),
                  memory_peak_bytes=device["memory_peak_bytes"])
        compiled_in_window = (stats_after["compile"]["programs"]
                              - stats_before["compile"]["programs"])

        t0 = time.monotonic()
        kept = [r for r in done if r[2] is not None and r[1] in (200, 404)]
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
            compiled_in_window=compiled_in_window,
            client_ceiling_short=max(0, int(
                2 * keys_in_window / ctx.seconds - client["replies_per_s"])))
        checks = {name: check(numbers[name], limit)
                  for name, limit in LIMITS.items()}

        breakdown = None
        if trace_dir:
            reduced = xplane_idle.reduce_trace(
                trace_dir, None if traced is None
                else traced["t1"] - traced["t0"])
            # this cell's gaps are many and short (three probes a drain):
            # every one is named, not the 200 longest
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
                "serve_keys_per_s": keys_in_window / ctx.seconds,
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


# ---------------------------------------------------------------------------
# the client's own ceiling

CANNED = r"""
import os, socket, sys

BODY = b"x" * int(sys.argv[1])
REPLY = (b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
         b"Content-Length: %d\r\n\r\n" % len(BODY)) + BODY

listener = socket.socket()
listener.bind(("127.0.0.1", 0))
listener.listen(256)
print(listener.getsockname()[1], flush=True)
for _ in range(int(sys.argv[2]) - 1):  # a process a connection
    if os.fork() == 0:
        break
while True:
    conn, _peer = listener.accept()
    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    try:
        while True:
            data = conn.recv(4096)
            if not data:
                break
            conn.sendall(REPLY * data.count(b"\r\n\r\n"))
    except OSError:
        pass
    conn.close()
"""


def ceiling(processes: ReaderProcesses, seconds: float, heads: list,
            body_bytes: int = 420) -> dict:
    """The rate this driver's window sustains against a responder that
    does no work and cannot be what holds the readers back (a process a
    connection, blocked in ``recv``, answering with one ``send``): what
    the client can measure at most."""
    n_readers = len(processes.channels)
    proc = subprocess.Popen(
        [sys.executable, "-c", CANNED, str(body_bytes), str(n_readers)],
        stdout=subprocess.PIPE, text=True, start_new_session=True)
    try:
        port = int(proc.stdout.readline())
        readers = [Reader("127.0.0.1", port) for _ in range(n_readers)]
        _t_open, deadline, outs = processes.window(
            readers, [heads] * n_readers, seconds, 8, 0)
        for reader in readers:
            reader.close()
    finally:
        os.killpg(proc.pid, signal.SIGKILL)  # the forked responders too
        proc.wait()
    done = [r for out in outs for r in out]
    latencies = [(r[4] - r[3]) * 1000.0 for r in done]
    return {
        "readers": n_readers, "seconds": seconds, "body_bytes": body_bytes,
        "requests": len(done),
        "failed": sum(1 for r in done if r[1] != 200),
        "replies_per_s": sum(1 for r in done if r[1] == 200
                             and r[4] <= deadline) / seconds,
        "p50_ms": percentile(latencies, 50),
        "p95_ms": percentile(latencies, 95),
    }


if __name__ == "__main__":
    if sys.argv[1:2] != ["--ceiling"]:
        raise SystemExit("usage: serve_point.py --ceiling [readers [seconds]]")
    pool = ReaderProcesses(int((sys.argv[2:3] or [32])[0]))
    print(json.dumps(ceiling(
        pool, float((sys.argv[3:4] or [10])[0]),
        [f"GET /variant/1:{10000 + k}:A:G HTTP/1.1\r\n"
         f"Host: bench\r\n\r\n".encode() for k in range(8192)])))
    pool.close()
