"""Test harness config: force JAX onto a virtual 8-device CPU mesh so
multi-chip sharding is exercised without TPU hardware (SURVEY.md §4d).

Must run before any ``import jax`` in test modules — pytest imports conftest
first, so setting the env here is sufficient."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take an accelerator
# CLI subprocess tests inherit both: the explicit pin every entry point
# honors (utils.runtime.pin_platform) and JAX's own variable
os.environ["AVDB_JAX_PLATFORM"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache: the suite's wall time is dominated by
# jit compiles (the mesh programs alone are ~10s each), and tier-1 runs
# under a hard timeout.  The cache is content-keyed — a stale entry can
# never serve wrong code — and subprocess tests (serve fleet workers,
# CLI loads) inherit it through the environment, so re-runs and
# sibling-process first-touches load from disk instead of recompiling.
# The program's own helper places it: JAX_COMPILATION_CACHE_DIR when the
# caller set it (an empty value disables), else <checkout>/.jax_cache.
from annotatedvdb_tpu.utils.runtime import ensure_compile_cache

ensure_compile_cache()

import jax

jax.config.update("jax_platforms", "cpu")

import asyncio
import random
import threading

import numpy as np
import pytest


@pytest.fixture
def rng():
    return random.Random(20260729)


def start_server(**kw):
    """The one way a test starts a server: ``build_aio_server(**kw)`` on
    an ephemeral port unless ``port`` says otherwise, bound and serving on
    a loop thread when this returns (``server.server_address[1]`` is the
    port, ``server.ctx`` the :class:`ServeContext`).  Pair with
    :func:`stop_server`."""
    from annotatedvdb_tpu.serve.aio import build_aio_server

    kw.setdefault("port", 0)
    server = build_aio_server(**kw)
    server.start_background()
    return server


def stop_server(server):
    """The builder's shutdown order: the server, then its batcher."""
    server.shutdown()
    server.ctx.batcher.close()


def ring_records(ctx, trace_id: str, n: int = 1, timeout: float = 5.0):
    """The span ring's records of ``trace_id`` — polled until ``n`` are
    there: a trace is sealed after the write that hands its reply to the
    transport, so a client can hold the reply a moment before the ring
    holds the record."""
    import time

    deadline = time.monotonic() + timeout
    while True:
        recs = [r for r in ctx.reqtrace.records() if r[0] == trace_id]
        if len(recs) >= n or time.monotonic() > deadline:
            return recs
        time.sleep(0.005)


def bulk_envelope(records: list) -> str:
    """The ``POST /variants`` body for ``engine.lookup_many``'s records
    (JSON texts, ``None`` for an absent id) — spelled here once, apart
    from the server, so a test can compare the server's bytes with the
    engine called directly."""
    return '{"n":%d,"found":%d,"results":[%s]}' % (
        len(records), sum(r is not None for r in records),
        ",".join("null" if r is None else r for r in records))


class BatcherOnLoop:
    """A :class:`LoopBatcher` with a loop thread of its own, as under
    ``AioServer``, for tests that drive the batcher without a socket.
    :meth:`submit` blocks the calling thread like one client;
    :meth:`run` runs ``fn(batcher)`` (a coroutine function) on the loop,
    where several submissions can share one turn."""

    def __init__(self, engine, **kw):
        from annotatedvdb_tpu.serve.aio import LoopBatcher

        self.batcher = LoopBatcher(engine, **kw)
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self.loop.run_forever, name="test-batcher-loop",
            daemon=True,
        )
        self._thread.start()

    def run(self, fn, timeout: float = 60.0):
        return asyncio.run_coroutine_threadsafe(
            fn(self.batcher), self.loop
        ).result(timeout)

    def submit(self, variant_id, **kw):
        async def one(batcher):
            return await batcher.submit_future(variant_id, **kw)

        return self.run(one)

    def close(self):
        self.batcher.close()
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=10)
        self.loop.close()


BASES = "ACGT"


def random_allele(rng, min_len=1, max_len=12):
    return "".join(rng.choice(BASES) for _ in range(rng.randint(min_len, max_len)))


def random_variants(rng, n, max_len=12):
    """Mix of shapes: SNVs, MNVs, inversions, pure ins/del, indels, dups,
    shared-prefix pairs — the cases that exercise every branch of the
    reference's annotator."""
    out = []
    for _ in range(n):
        kind = rng.randrange(8)
        chrom = rng.choice([str(c) for c in range(1, 23)] + ["X", "Y", "M"])
        pos = rng.randint(1, 248_000_000)
        if kind == 0:  # SNV
            ref = rng.choice(BASES)
            alt = rng.choice(BASES.replace(ref, ""))
        elif kind == 1:  # MNV (maybe accidental inversion)
            L = rng.randint(2, max_len)
            ref = random_allele(rng, L, L)
            alt = random_allele(rng, L, L)
        elif kind == 2:  # inversion
            ref = random_allele(rng, 2, max_len)
            alt = ref[::-1]
        elif kind == 3:  # pure insertion (anchored)
            ref = rng.choice(BASES)
            alt = ref + random_allele(rng, 1, max_len - 1)
        elif kind == 4:  # duplication: ref[1:] = k copies of inserted motif
            motif = random_allele(rng, 1, 4)
            k = rng.randint(1, 3)
            anchor = rng.choice(BASES)
            ref = anchor + motif * k
            alt = ref + motif
        elif kind == 5:  # deletion (anchored)
            alt = rng.choice(BASES)
            ref = alt + random_allele(rng, 1, max_len - 1)
        elif kind == 6:  # indel with shared prefix
            shared = random_allele(rng, 1, 4)
            ref = shared + random_allele(rng, 1, 5)
            alt = shared + random_allele(rng, 1, 5)
        else:  # arbitrary ragged pair
            ref = random_allele(rng, 1, max_len)
            alt = random_allele(rng, 1, max_len)
        out.append((chrom, pos, ref, alt))
    return out
