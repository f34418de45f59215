"""CLI: serve point/bulk/region queries over a loaded variant store.

The read-side entry point the reference never shipped as a program (its
query surface is raw SQL against ``AnnotatedVDB.Variant``): a stdlib JSON
API over the store directory, with request coalescing, bounded admission,
weighted per-client fairness, snapshot isolation against concurrent
loader commits, and (optionally) an HBM residency budget.

Usage::

    python -m annotatedvdb_tpu serve --storeDir ./vdb --port 8080
    python -m annotatedvdb_tpu serve --storeDir ./vdb --port 8080 \\
        --workers 4 --hbmBudget 2g          # multi-process fleet
    curl localhost:8080/variant/8:1000:A:G
    curl 'localhost:8080/region/8:1000-250000?minCadd=20'
    curl -d '{"regions":["8:1000-2000","8:9000-9500"],"limit":50}' \\
        localhost:8080/regions              # batch region join (BITS)

``--port 0`` binds an ephemeral port (printed on startup) — the smoke/test
mode.  ``--workers N`` (default ``AVDB_SERVE_WORKERS`` or 1) runs the
multi-process fleet: N worker processes share the port (SO_REUSEPORT
where available, parent accept handoff otherwise) and one readonly store
generation; the supervisor restarts dead workers and drains on SIGTERM.
The front end is the asyncio event loop (``serve/aio.py``).
Knobs default from ``AVDB_SERVE_*`` (see README "Configuration"); flags
override the environment.  ``--_workerIndex``/``--_listenFd`` are the
fleet's internal worker handshake, not a user surface.
"""

from __future__ import annotations

import argparse
import os
import sys


def _build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="HTTP query API over a TPU-native variant store"
    )
    parser.add_argument("--storeDir", required=True,
                        help="variant store directory (opened read-only)")
    parser.add_argument("--host", default="127.0.0.1",
                        help="bind address (default 127.0.0.1)")
    parser.add_argument("--port", type=int, default=8080,
                        help="bind port (0 = ephemeral, printed on startup)")
    parser.add_argument("--workers", type=int, default=None,
                        help="serve fleet size: N>1 runs N worker processes "
                             "sharing the port and one readonly store "
                             "generation (default: AVDB_SERVE_WORKERS or 1)")
    parser.add_argument("--upserts", action="store_true",
                        default=None,
                        help="enable the live write path: POST "
                             "/variants/upsert with a per-worker "
                             "write-ahead log, replayed on start "
                             "(default: AVDB_SERVE_UPSERTS or off)")
    parser.add_argument("--follow", default=None, metavar="LEADER-URL",
                        help="run as a replication follower: bootstrap a "
                             "consistent snapshot cut from the leader's "
                             "/repl surface into --storeDir, tail its "
                             "WAL/ledger stream, and serve bounded-"
                             "staleness reads (/readyz answers 503 past "
                             "AVDB_REPL_MAX_LAG_S; writes answer 403 with "
                             "the leader's location); fail over with "
                             "'doctor promote'")
    parser.add_argument("--maintain", action="store_true",
                        default=None,
                        help="arm the autonomous maintenance daemon in "
                             "the fleet supervisor: watermark-driven "
                             "background compaction, load-aware and "
                             "crash-safe (default: AVDB_MAINTAIN or off; "
                             "implies fleet mode even with --workers 1)")
    parser.add_argument("--maxBatch", type=int, default=None,
                        help="max point queries per coalesced microbatch "
                             "(default: AVDB_SERVE_BATCH_MAX or 256)")
    parser.add_argument("--batchWaitMs", type=float, default=None,
                        help="batcher drain deadline in ms "
                             "(default: AVDB_SERVE_BATCH_WAIT_MS or 2)")
    parser.add_argument("--maxQueue", type=int, default=None,
                        help="admission bound: pending queries beyond this "
                             "are rejected 429 "
                             "(default: AVDB_SERVE_MAX_QUEUE or 1024)")
    parser.add_argument("--regionCache", type=int, default=None,
                        help="rendered hot-region LRU capacity "
                             "(default: AVDB_SERVE_REGION_CACHE or 64)")
    parser.add_argument("--clientRate", type=float, default=None,
                        help="weighted per-client admission: requests/sec "
                             "per weight unit, 0 disables "
                             "(default: AVDB_SERVE_CLIENT_RATE or 0)")
    parser.add_argument("--streamThreshold", type=int, default=None,
                        help="region row count above which responses "
                             "stream chunked instead of buffering "
                             "(default: AVDB_SERVE_STREAM_THRESHOLD or 2048)")
    parser.add_argument("--hbmBudget", default=None, metavar="BYTES",
                        help="HBM residency budget for probe segment "
                             "caches, e.g. 512m / 2g; unset = unmanaged "
                             "(default: AVDB_SERVE_HBM_BUDGET). In fleet "
                             "mode this is the WHOLE-fleet budget, split "
                             "equally across workers — the device is "
                             "shared, the budget must be too")
    parser.add_argument("--snapshotTtlMs", type=float, default=None,
                        help="coalesced manifest freshness window in ms "
                             "(default: AVDB_SERVE_SNAPSHOT_TTL_MS or 250)")
    parser.add_argument("--metricsOut", default=None, metavar="FILE",
                        help="write serving metrics on shutdown: Prometheus "
                             "textfile at FILE plus JSON at FILE.json "
                             "(live scrape: GET /metrics)")
    parser.add_argument("--traceOut", default=None, metavar="FILE",
                        help="write a Chrome trace of batcher drain spans "
                             "on shutdown")
    parser.add_argument("--_workerIndex", type=int, default=None,
                        help=argparse.SUPPRESS)  # fleet-internal
    parser.add_argument("--_listenFd", type=int, default=None,
                        help=argparse.SUPPRESS)  # fleet-internal
    parser.add_argument("--_heartbeatFile", default=None,
                        help=argparse.SUPPRESS)  # fleet-internal (watchdog)
    parser.add_argument("--_telemetryDir", default=None,
                        help=argparse.SUPPRESS)  # fleet-internal (?fleet=1)
    parser.add_argument("--_forceHandoff", action="store_true",
                        help=argparse.SUPPRESS)  # tests: no-SO_REUSEPORT path
    return parser


def _upserts_enabled(args) -> bool:
    """Flag wins over environment; ``AVDB_SERVE_UPSERTS`` accepts the
    usual truthy spellings.  Resolved ONCE here, never in the server."""
    if args.upserts is not None:
        return bool(args.upserts)
    return os.environ.get("AVDB_SERVE_UPSERTS", "").lower() \
        not in ("", "0", "false")


def _maintain_enabled(args) -> bool:
    """Flag wins over environment (``AVDB_MAINTAIN``) — the env spelling
    lives once in ``store.maintenance``, per the knob-resolution
    contract."""
    if args.maintain is not None:
        return bool(args.maintain)
    from annotatedvdb_tpu.store.maintenance import maintain_enabled_from_env

    return maintain_enabled_from_env()


def _effective_workers(args) -> int:
    if args.workers is not None:
        return max(int(args.workers), 1)
    return max(int(os.environ.get("AVDB_SERVE_WORKERS", "") or 1), 1)


def _resolve_budget(args):
    """The effective HBM budget in bytes (flag wins over env), or None
    when unmanaged — the ONE resolution both the fleet supervisor and the
    single-process/worker path share."""
    from annotatedvdb_tpu.serve.residency import budget_from_env, parse_bytes

    return (
        parse_bytes(args.hbmBudget) if args.hbmBudget is not None
        else budget_from_env()
    )


def _knob_args(args, workers: int) -> list[str]:
    """Knob flags forwarded to every fleet worker (per-process exports
    like --metricsOut/--traceOut stay supervisor-only: N workers cannot
    share one output file).  The HBM budget is the exception to verbatim
    forwarding: it caps ONE shared device, so each worker gets an equal
    share — N workers each enforcing the full budget could pin N x budget
    of probe caches (an explicit flag also overrides the inherited
    AVDB_SERVE_HBM_BUDGET, which would have the same problem)."""
    out: list[str] = []
    if _upserts_enabled(args):
        # every worker runs its own memtable + WAL (serve-w<idx>.*.wal):
        # the flag must reach them all
        out.append("--upserts")
    if args.follow:
        # every follower worker tails the leader; only worker 0 persists
        # the mirror (the others apply shipped bytes in memory)
        out += ["--follow", args.follow]
    for flag, val in (
        ("--maxBatch", args.maxBatch),
        ("--batchWaitMs", args.batchWaitMs),
        ("--maxQueue", args.maxQueue),
        ("--regionCache", args.regionCache),
        ("--clientRate", args.clientRate),
        ("--streamThreshold", args.streamThreshold),
        ("--snapshotTtlMs", args.snapshotTtlMs),
    ):
        if val is not None:
            out += [flag, str(val)]
    budget = _resolve_budget(args)
    if budget is not None:
        out += ["--hbmBudget", str(budget // workers)]
    return out


def main(argv=None):
    args = _build_parser().parse_args(argv)

    def log(msg):
        print(f"serve: {msg}", file=sys.stderr)

    try:
        workers = _effective_workers(args)
    except ValueError as err:
        print(f"serve: cannot start: bad AVDB_SERVE_WORKERS ({err})",
              file=sys.stderr)
        return 1
    if args.follow:
        if _upserts_enabled(args):
            # a follower is read-only BY ROLE: its overlay exists to
            # apply the leader's stream, and a second writer would fork
            # the replica — the write path belongs to the leader
            print("serve: --follow and --upserts are mutually exclusive "
                  "(a follower forwards writes to its leader; promote it "
                  "with 'doctor promote' to accept writes)",
                  file=sys.stderr)
            return 2
        if _maintain_enabled(args):
            # compaction rewrites segments the ship stream mirrors —
            # the leader compacts, the follower re-syncs the cut
            print("serve: --follow and --maintain are mutually exclusive "
                  "(the leader owns compaction; the follower mirrors its "
                  "commits)", file=sys.stderr)
            return 2
        if args._workerIndex is None and not os.path.exists(
            os.path.join(args.storeDir, "manifest.json")
        ):
            # first start against an empty directory: bootstrap the
            # snapshot cut BEFORE any worker loads the store (fleet
            # workers need a loadable manifest mirror on their first
            # SnapshotManager load)
            from annotatedvdb_tpu.store.replication import (
                ReplError,
                ReplicaTailer,
            )

            try:
                ReplicaTailer(
                    args.storeDir, args.follow, log=log, persist=True
                ).bootstrap()
            except (ReplError, OSError, ValueError) as err:
                print(f"serve: cannot bootstrap from {args.follow}: {err}",
                      file=sys.stderr)
                return 1
    maintain = args._workerIndex is None and _maintain_enabled(args)
    if args._workerIndex is None and (workers > 1 or maintain):
        if args.metricsOut or args.traceOut:
            print("serve: --metricsOut/--traceOut are per-process exports "
                  "and are not collected in fleet mode; scrape GET "
                  "/metrics instead", file=sys.stderr)
        from annotatedvdb_tpu.serve.fleet import ServeFleet

        try:
            # --maintain hosts the maintenance daemon in the supervisor,
            # so it forces fleet mode even at --workers 1 (the daemon
            # must outlive any single worker's death/respawn)
            fleet = ServeFleet(
                args.storeDir, host=args.host, port=args.port,
                workers=workers, worker_args=_knob_args(args, workers),
                log=log, maintain=maintain,
                reuseport=False if args._forceHandoff else None,
            )
        except (OSError, ValueError) as err:
            print(f"serve: cannot start fleet: {err}", file=sys.stderr)
            return 1
        print(f"serving {args.storeDir} on http://{args.host}:{fleet.port} "
              f"with {workers} workers", flush=True)
        return fleet.run()
    return _run_single(args, log)


def _run_single(args, log) -> int:
    """One serving process: the default single-process mode AND the fleet
    worker mode (``--_workerIndex`` set)."""
    from annotatedvdb_tpu.obs.metrics import MetricsRegistry
    from annotatedvdb_tpu.obs.trace import Tracer
    from annotatedvdb_tpu.serve.residency import ResidencyManager
    from annotatedvdb_tpu.serve.snapshot import SnapshotManager
    from annotatedvdb_tpu.utils import faults
    from annotatedvdb_tpu.utils.runtime import device_summary, pin_platform

    # honor an explicit cpu pin, place the compile cache (the supervisor
    # of a fleet never gets here: it stays off JAX)
    pin_platform()
    try:
        # take the device NOW: a process that cannot have one (the chip is
        # held by another process) must say so in one line at start-up,
        # not in a traceback at its first request
        device_summary()
    except RuntimeError as err:
        from annotatedvdb_tpu.serve.fleet import NO_DEVICE_RC

        print("serve: cannot start: JAX found no usable device "
              f"({str(err).splitlines()[0][:300]})", file=sys.stderr)
        return NO_DEVICE_RC
    tracer = Tracer(process_name="avdb-serve") if args.traceOut else None
    registry = MetricsRegistry()
    try:
        budget = _resolve_budget(args)
        # None = unmanaged (the store's own ski-rental rule); an EXPLICIT
        # 0 is the managed degenerate case — nothing may be resident,
        # which is the opposite of unmanaged on a memory-pressured device.
        # When MESH SERVING is on (the serve_mesh_on resolution — never
        # the bare device count: a mesh-off server must keep the
        # historical single-bucket plan) the worker's budget splits PER
        # DEVICE and segments pin to their chromosome's placed device —
        # the mesh twin of the fleet's per-worker split in _knob_args
        residency = None
        if budget is not None:
            from annotatedvdb_tpu.serve.mesh_exec import serve_mesh_on

            mesh = serve_mesh_on()
            if mesh is not None:
                from annotatedvdb_tpu.parallel.mesh import (
                    chromosome_placement,
                )

                n_dev = int(mesh.devices.size)
                residency = ResidencyManager(
                    budget // n_dev, registry=registry, log=log,
                    placement=chromosome_placement(n_dev),
                    devices=list(mesh.devices.flat),
                    max_batch=args.maxBatch,
                )
            else:
                residency = ResidencyManager(budget, registry=registry,
                                             log=log,
                                             max_batch=args.maxBatch)
        manager = SnapshotManager(
            args.storeDir, log=log,
            ttl_s=(args.snapshotTtlMs / 1000.0
                   if args.snapshotTtlMs is not None else None),
        )
    except (OSError, ValueError) as err:
        print(f"serve: cannot start: {err}", file=sys.stderr)
        return 1

    # crash flight recorder: this worker's mmap'd black box under
    # <store>/flight/ — it survives SIGKILL, the supervisor harvests it
    # on any death.  A recorder that cannot start must never block
    # serving (observability is strictly best-effort).
    flight = None
    from annotatedvdb_tpu.obs import flight as flight_mod

    if flight_mod.flight_events_from_env() > 0:
        try:
            flight = flight_mod.FlightRecorder(
                flight_mod.ring_path(args.storeDir,
                                     args._workerIndex or 0),
                log=log,
            )
        except (OSError, ValueError) as err:
            log(f"flight: recorder unavailable ({err}); serving without "
                "a black box")

    # health plane: the metrics time-series ring + SLO burn-rate alerts
    # (obs/slo.py), persisted under <store>/history/ so the supervisor
    # can harvest a dead worker's history like its flight ring.  Knob
    # typos fail startup loudly (the *_from_env contract); a plane that
    # resolves to disabled (tick or retention 0) stays None — serving is
    # never gated on its observer.
    health = None
    from annotatedvdb_tpu.obs.slo import HealthPlane
    from annotatedvdb_tpu.obs.timeseries import (
        obs_history_from_env,
        obs_tick_from_env,
    )

    try:
        if obs_tick_from_env() > 0 and obs_history_from_env() > 0:
            health = HealthPlane(
                registry, store_dir=args.storeDir,
                worker=args._workerIndex or 0, log=log,
            )
    except ValueError as err:
        print(f"serve: cannot start: {err}", file=sys.stderr)
        return 1

    memtable = None
    if _upserts_enabled(args):
        from annotatedvdb_tpu.serve.snapshot import MemtableSnapshots
        from annotatedvdb_tpu.store.memtable import Memtable
        from annotatedvdb_tpu.store.wal import WriteAheadLog

        worker = args._workerIndex or 0
        # replication fencing: remember the manifest epoch this writer
        # opened under — if a follower is promoted while this leader is
        # alive (or wakes a deposed one), the on-disk epoch moves past
        # this value and every flush commit aborts instead of clobbering
        # the promoted lineage (store/replication.py)
        fence = 0
        try:
            import json as json_mod

            with open(os.path.join(args.storeDir, "manifest.json")) as f:
                fence = int((json_mod.load(f) or {}).get(
                    "repl_epoch", 0) or 0)
        except (OSError, ValueError):
            pass
        try:
            wal = WriteAheadLog(
                args.storeDir, name=f"serve-w{worker}", log=log
            )
            memtable = Memtable(
                width=manager.current().store.width,
                store_dir=args.storeDir, wal=wal,
                registry=registry, log=log, fence_epoch=fence,
            )
            # recovery: acknowledged-but-unflushed upserts from a previous
            # incarnation (crash, SIGKILL, wedge kill) come back before
            # the first request is accepted — idempotent, so a death
            # mid-replay just replays again on the next respawn
            replayed = memtable.replay(manager.current().store)
        except (OSError, ValueError) as err:
            print(f"serve: cannot start: {err}", file=sys.stderr)
            return 1
        if replayed:
            log(f"wal: replayed {replayed} acknowledged upsert row(s) "
                "into the memtable")
        # reads resolve through the overlay from here on: upserted rows
        # are visible immediately, first-wins against the base store
        manager = MemtableSnapshots(manager, memtable)

    tailer = None
    if args.follow:
        from annotatedvdb_tpu.serve.snapshot import MemtableSnapshots
        from annotatedvdb_tpu.store.memtable import Memtable
        from annotatedvdb_tpu.store.replication import ReplicaTailer

        follow_url = args.follow.rstrip("/")
        base_manager = manager
        worker = args._workerIndex or 0

        def _overlay_mem():
            # memory-only overlay: the mirrored WAL files on disk are
            # the durability (worker 0 fsyncs them before records count
            # as applied); flush triggers are disabled — a follower
            # never writes segments, it mirrors the leader's
            return Memtable(
                width=base_manager.current().store.width, store_dir=None,
                wal=None, flush_bytes=0, flush_age_s=0.0, log=log,
            )

        mem_ref = {"mem": _overlay_mem()}
        manager = MemtableSnapshots(base_manager, mem_ref["mem"])

        def _apply_rows(rows):
            mem_ref["mem"].upsert(
                base_manager.current().store, rows, durable=False
            )

        def _on_resync():
            # a leader commit landed: pick up the new base cut, then
            # swap in a fresh overlay (rows now covered by the cut
            # leave memory; first-wins keeps the overlap byte-stable)
            try:
                base_manager.refresh()
            except Exception as err:
                log(f"repl: base refresh after re-sync failed ({err})")
            fresh = _overlay_mem()
            mem_ref["mem"] = fresh
            manager.reset_memtable(fresh)

        try:
            # only worker 0 mirrors bytes into the shared store dir;
            # sibling workers tail the leader applying shipped frames
            # straight from memory
            tailer = ReplicaTailer(
                args.storeDir, follow_url, log=log, registry=registry,
                apply_rows=_apply_rows, on_resync=_on_resync,
                persist=(worker == 0),
            )
            recovered = tailer.resume()
        except (OSError, ValueError) as err:
            print(f"serve: cannot start follower: {err}", file=sys.stderr)
            return 1
        if recovered:
            # restart recovery: records already durable in the local
            # mirror re-enter the overlay before the first request
            for record in tailer.local_records():
                rows = record.get("rows")
                if isinstance(rows, list):
                    _apply_rows(rows)
            log(f"repl: re-applied {recovered} mirrored WAL record(s) "
                "into the overlay")

    max_wait_s = (
        args.batchWaitMs / 1000.0 if args.batchWaitMs is not None else None
    )
    sock = None
    if args._workerIndex is not None:
        try:
            sock = _worker_socket(args)
        except OSError as err:
            print(f"serve: worker cannot bind: {err}", file=sys.stderr)
            return 1

    from annotatedvdb_tpu.serve.aio import build_aio_server

    try:
        server = build_aio_server(
            manager=manager, host=args.host, port=args.port, sock=sock,
            max_batch=args.maxBatch, max_wait_s=max_wait_s,
            max_queue=args.maxQueue, region_cache_size=args.regionCache,
            registry=registry, residency=residency, memtable=memtable,
            client_rate=args.clientRate,
            stream_threshold=args.streamThreshold,
            heartbeat_file=args._heartbeatFile,
            heartbeat_index=args._workerIndex or 0,
            tracer=tracer, log=log, flight=flight,
            telemetry_dir=args._telemetryDir,
            health=health,
        )
    except (OSError, ValueError) as err:
        # unparseable AVDB_SERVE_* knob or unbindable address: same clean
        # exit as every other startup failure (a fleet worker dying with a
        # traceback here would respawn into a crash loop)
        print(f"serve: cannot start: {err}", file=sys.stderr)
        return 1
    ctx = server.ctx
    if tailer is not None:
        # the staleness contract flows through the context: lag gates
        # /readyz, writes 403 toward the leader; the tail thread starts
        # only once the context that consumes its gauge exists
        ctx.repl = tailer
        ctx.follow_url = tailer.leader_url
        tailer.start()
    snap = manager.current()

    # GC hygiene for a latency-sensitive process: the loaded store is
    # millions of long-lived objects — freeze them out of the collector
    # so a mid-request gen2 pass never walks the whole store (those walks
    # are tens of milliseconds, straight into p99), and widen gen0 so
    # request-rate allocation (futures, pendings, rendered strings)
    # doesn't trigger collections thousands of times per second
    import gc

    gc.collect()
    gc.freeze()
    gc.set_threshold(50_000, 25, 25)
    # a 5ms GIL slice (the interpreter default) stacks whole-slice stalls
    # onto request tails whenever the batcher drain or an executor thread
    # runs hot; 1ms trades a little switching overhead for p99
    sys.setswitchinterval(0.001)

    import signal
    import threading

    try:
        if args._workerIndex is not None:
            # fleet worker: the event loop owns the main thread (and its
            # SIGTERM graceful drain); a watcher fires the worker fault
            # point and prints readiness once the socket is accepting
            def ready():
                server._started.wait()
                try:
                    # crash point: this worker is accepting; a failure
                    # here is a worker death the SUPERVISOR must absorb
                    # and restart
                    faults.fire("serve.worker")
                except Exception as err:
                    print(f"serve: worker fault injected: {err}",
                          file=sys.stderr)
                    os._exit(1)
                host, port = server.server_address[:2]
                print(f"worker {args._workerIndex} serving {args.storeDir} "
                      f"(generation {snap.generation}, {snap.store.n} rows)"
                      f" on http://{host}:{port}", flush=True)

            threading.Thread(target=ready, daemon=True).start()
            server.serve_forever()
        else:
            # single process: bind on a helper thread first so the
            # concrete (possibly ephemeral) address prints before we block
            server.start_background()
            host, port = server.server_address[:2]
            print(f"serving {args.storeDir} (generation {snap.generation}, "
                  f"{snap.store.n} rows) on http://{host}:{port}",
                  flush=True)
            stop = threading.Event()
            for sig in (signal.SIGTERM, signal.SIGINT):
                signal.signal(sig, lambda *_a: stop.set())
            stop.wait()
            log("shutting down")
    except OSError as err:
        # bind failure: same clean exit as every other startup failure
        print(f"serve: cannot start: {err}", file=sys.stderr)
        return 1
    except KeyboardInterrupt:
        log("shutting down")
    finally:
        if tailer is not None:
            tailer.stop()
        server.shutdown()
        ctx.batcher.close()
        if memtable is not None and memtable.wal is not None:
            # record-free WAL files protect nothing: drop them so a clean
            # shutdown leaves no fsck warning (files WITH records stay —
            # they are the durability of unflushed acknowledged upserts)
            memtable.wal.close(remove_if_empty=True)
        # uninstall the process-global background sink BEFORE closing the
        # flight recorder it points at: a later store-layer operation in
        # this process must not record into a dead context's ring
        from annotatedvdb_tpu.obs import reqtrace as reqtrace_mod

        reqtrace_mod.set_background_sink(None, None)
        if flight is not None:
            flight.close()
        if health is not None:
            # forced final persist: a clean shutdown leaves the full
            # history tail on disk for doctor slo
            health.close()
        _export(args, ctx.registry, tracer, log)
    return 0


def _worker_socket(args):
    """The worker's listening socket: inherit the supervisor's fd (accept
    handoff) or bind our own SO_REUSEPORT socket on the fleet port."""
    import socket as socket_mod

    from annotatedvdb_tpu.serve.fleet import bind_reuseport

    if args._listenFd is not None:
        return socket_mod.socket(fileno=args._listenFd)
    return bind_reuseport(args.host, args.port)


def _export(args, registry, tracer, log) -> None:
    if args.metricsOut:
        try:
            registry.write_textfile(args.metricsOut)
            registry.write_json(args.metricsOut + ".json")
        except OSError as err:
            log(f"metrics export failed ({err})")
    if tracer is not None and args.traceOut:
        try:
            tracer.save(args.traceOut)
        except OSError as err:
            log(f"trace export failed ({err})")


if __name__ == "__main__":
    raise SystemExit(main())
