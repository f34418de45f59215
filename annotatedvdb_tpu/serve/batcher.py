"""Request coalescing: what the point-lookup batcher shares with the rest
of the serving stack.

Concurrent point requests each carry ONE query; probing the store one row
at a time would waste everything the vectorized membership path is good
at.  The batcher (:class:`annotatedvdb_tpu.serve.aio.LoopBatcher`, the
continuous-batching shape inference stacks use — annbatch makes the same
argument for sharded scientific stores) takes the first pending query,
waits up to a deadline for company, executes the whole microbatch through
``QueryEngine.lookup_many`` (one vectorized probe per chromosome group —
large batches ride the device probe path), and hands each caller its own
slice back.  This module holds its knob resolution, its admission error,
its fill histogram's edges and its drain's profiler span.

Knobs (env defaults, overridable per instance):

- ``AVDB_SERVE_BATCH_MAX``      — max queries per microbatch (default 256);
- ``AVDB_SERVE_BATCH_WAIT_MS``  — how long the first query of a batch waits
  for company (default 2ms: under load batches fill and the wait never
  triggers; idle, a lone query pays at most the deadline);
- ``AVDB_SERVE_MAX_QUEUE``      — admission bound; a submission beyond this
  depth raises :class:`QueueFull` (the HTTP layer's 429).
"""

from __future__ import annotations

import os

from annotatedvdb_tpu.utils.profiling import annotation

#: batch-fill histogram edges (fraction of max_batch actually used)
BATCH_FILL_EDGES = (0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0)


class QueueFull(RuntimeError):
    """Admission rejection: the pending-query queue is at capacity.  The
    HTTP front end maps this to 429 + Retry-After."""


def resolve_batch_knobs(max_batch, max_wait_s, max_queue):
    """Fill ``None`` knobs from ``AVDB_SERVE_BATCH_MAX`` /
    ``_BATCH_WAIT_MS`` / ``_MAX_QUEUE`` and clamp — the ONE place the env
    defaults live."""
    if max_batch is None:
        max_batch = int(os.environ.get("AVDB_SERVE_BATCH_MAX", "") or 256)
    if max_wait_s is None:
        max_wait_s = int(
            os.environ.get("AVDB_SERVE_BATCH_WAIT_MS", "") or 2
        ) / 1000.0
    if max_queue is None:
        max_queue = int(os.environ.get("AVDB_SERVE_MAX_QUEUE", "") or 1024)
    return (max(int(max_batch), 1), max(float(max_wait_s), 0.0),
            max(int(max_queue), 0))


def resolve_regions_knobs(regions_max, device_min):
    """The region-microbatching knobs, resolved in ONE place (the same
    contract as :func:`resolve_batch_knobs` — the front end and the
    engine must see identical env defaults):

    - ``AVDB_SERVE_REGIONS_MAX``        — max query intervals per
      ``POST /regions`` batch (default 4096; an over-cap batch is a 400,
      never an unbounded device call);
    - ``AVDB_SERVE_REGIONS_DEVICE_MIN`` — min intervals per chromosome
      group before the batched BITS kernel engages (default 32: smaller
      groups — including every single ``GET /region`` — take the
      byte-identical host searchsorted twin, which beats a device
      dispatch at that size; 0 sends every group to the device).
    """
    if regions_max is None:
        regions_max = int(
            os.environ.get("AVDB_SERVE_REGIONS_MAX", "") or 4096
        )
    if device_min is None:
        device_min = int(
            os.environ.get("AVDB_SERVE_REGIONS_DEVICE_MIN", "") or 32
        )
    return max(int(regions_max), 1), max(int(device_min), 0)


def resolve_stats_knobs(stats_max, device_min):
    """The analytics-panel knobs, resolved in ONE place (the
    :func:`resolve_batch_knobs` contract — the front end and the
    engine must see identical env defaults):

    - ``AVDB_SERVE_STATS_MAX``        — max query intervals per
      ``POST /stats/region`` batch (default 4096; an over-cap batch is
      a 400, never an unbounded device call);
    - ``AVDB_SERVE_STATS_DEVICE_MIN`` — min intervals per chromosome
      group before the fused stats kernel engages (default 16: smaller
      panels take the byte-identical host twin — a stats panel already
      amortizes its prefix sums over the whole group, so the dispatch
      pays off earlier than the span search's 32; 0 sends every group
      to the device).
    """
    if stats_max is None:
        stats_max = int(
            os.environ.get("AVDB_SERVE_STATS_MAX", "") or 4096
        )
    if device_min is None:
        device_min = int(
            os.environ.get("AVDB_SERVE_STATS_DEVICE_MIN", "") or 16
        )
    return max(int(stats_max), 1), max(int(device_min), 0)


def batch_annotation(parsed: list):
    """``avdb.serve.batch`` on the profiler's clock around one drain of
    the batcher: ``n`` ids in ``groups`` chromosome groups (one probe
    each).  A capture then shows the drains, what lies between them
    (waiting for company, the sockets) and what each spent outside the
    engine (shedding, handing results back)."""
    return annotation("avdb.serve.batch", n=len(parsed),
                      groups=len({p[0] for p in parsed}))
