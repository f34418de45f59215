"""Batched Binary Interval Search (BITS) kernel: bulk region joins on device.

The reference answers every range query with a Postgres ltree/bin-index
scan — one server round-trip per region — and the PR-5 serve path, while
TPU-resident for point lookups, still walked a host-side per-segment
``np.searchsorted`` loop answering ONE region per request.  Annotating a
BED file or gene panel that way costs thousands of HTTP round-trips and
thousands of tiny host slices.

BITS (Layer et al., arXiv 1208.3407) observes that interval intersection
against a pre-sorted database needs no tree and no per-row compare: two
binary searches over the sorted end-points answer each query.  Here the
database rows are variant positions — each row occupies a single base
coordinate for range-match purposes (the reference's region scan matches
on POS), so the database's sorted start-points and sorted end-points are
the SAME array and the two searches return a *contiguous* row span:

- ``lo = searchsorted(pos, q_start, side="left")``   (rows before the query)
- ``hi = searchsorted(pos, q_end,   side="right")``  (rows not after it)

``hi - lo`` is the intersection COUNT (never materializing rows — the
count-only mode), ``[lo, hi)`` is the materializable row span, and both
searches vectorize over thousands of query intervals in one device call.
The kernel additionally fuses the closed-form hierarchical bin index of
every query interval (same arithmetic as ``ops/binindex``), which is the
interval-tokenization output for ML consumers (genomic interval
tokenizers, arXiv 2511.01555): per interval, a discrete bin token
(level, leaf) plus its row-id span — fixed-width integer arrays.

The sorted ``pos`` array a caller passes is the serve engine's
*deduplicated interval index* (``serve.engine.IntervalIndex``): one
position-sorted, first-wins-deduplicated view per chromosome group per
store generation — so spans ARE post-dedup row ranges and a span width is
the exact region count.

Shapes are padded (``interval_spans``): rows to a power of two, queries to
:func:`span_query_capacity` — the ONE place the span program's query
shapes are decided (a power of two, never under :data:`SPAN_QUERY_FLOOR`).
A group of ``nq`` intervals runs the program of that capacity and no
other, so the programs a server can be asked for are
:func:`span_query_shapes` of its knobs — eight at the defaults — and
:func:`warm_spans` runs each once against an uploaded index before a
request can.  The numpy twin (``interval_spans_host``) is byte-identical
by construction (both sides run the same textbook binary search over the
same int32 values) and is the path the serving circuit breaker — or an
explicit ``host_only`` — can always take.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from annotatedvdb_tpu.ops.binindex import LEAF_SIZE, NUM_BIN_LEVELS
from annotatedvdb_tpu.utils.arrays import (
    POS_SENTINEL,
    next_pow2,
    pad_pow2,
    pad_rows,
)

#: query coordinates are clamped below the position sentinel before either
#: search path: store positions are int32 (< POS_SENTINEL by construction),
#: so the clamp never changes an answer, and the device kernel's int32
#: casts can never wrap on an absurd-but-grammatical query bound
MAX_QUERY_POS = int(POS_SENTINEL) - 16


#: the smallest query shape the span program runs at: the default
#: ``AVDB_SERVE_REGIONS_DEVICE_MIN`` (smaller groups take the host twin),
#: so a lowered minimum adds no program
SPAN_QUERY_FLOOR = 32

#: host<->device array transfers of one :func:`interval_spans` call on a
#: device-resident index: ``starts`` and ``ends`` up, ``lo``, ``hi``,
#: ``level`` and ``leaf`` back
SPAN_TRANSFERS = 6


def span_query_capacity(nq: int) -> int:
    """The query shape a span search of ``nq`` intervals runs at: the next
    power of two, never under :data:`SPAN_QUERY_FLOOR`.  The ONE place the
    shapes of ``bits_spans_kernel`` programs are decided (the
    ``store.variant_store.probe_query_capacity`` pattern)."""
    return max(next_pow2(nq), SPAN_QUERY_FLOOR)


def span_query_shapes(nq_min: int, nq_max: int) -> list[int]:
    """Every query shape a group of ``nq_min``..``nq_max`` intervals can
    take, ascending (empty when ``nq_min`` > ``nq_max``: no group reaches
    the device)."""
    if nq_min > nq_max:
        return []
    shapes = [span_query_capacity(max(nq_min, 1))]
    while shapes[-1] < span_query_capacity(nq_max):
        shapes.append(shapes[-1] * 2)
    return shapes


@jax.named_scope("avdb.bits_spans")
def bits_spans_kernel(pos, starts, ends):
    """BITS spans + bin tokens for a batch of query intervals.

    ``pos`` [R] — one chromosome group's position-sorted (deduplicated)
    row coordinates; ``starts``/``ends`` [Q] — 1-based inclusive query
    intervals.  Returns ``(lo [Q] int32, hi [Q] int32, level [Q] int8,
    leaf [Q] int32)``: ``[lo, hi)`` is each interval's row span (``hi-lo``
    the count), ``(level, leaf)`` its deepest enclosing hierarchical bin
    (identical arithmetic to ``ops.binindex.bin_index_kernel``)."""
    pos = pos.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    ends = ends.astype(jnp.int32)
    lo = jnp.searchsorted(pos, starts, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(pos, ends, side="right").astype(jnp.int32)
    a = (starts - 1) // LEAF_SIZE
    b = (ends - 1) // LEAF_SIZE
    x = a ^ b
    shifts = jnp.arange(NUM_BIN_LEVELS, dtype=jnp.int32)            # [13]
    mism = jnp.sum(
        (x[:, None] >> shifts[None, :]) != 0, axis=1, dtype=jnp.int32
    )
    level = (NUM_BIN_LEVELS - mism).astype(jnp.int8)
    return lo, hi, level, a


bits_spans_kernel_jit = jax.jit(bits_spans_kernel)


@jax.named_scope("avdb.bits_spans_stacked")
def bits_spans_stacked(pos, starts, ends):
    """BITS spans + bin tokens for a STACK of chromosome groups — the
    mesh-sharded panel kernel.

    ``pos`` [B, R] — one sentinel-padded position row per group (empty
    groups are all-sentinel rows); ``starts``/``ends`` [B, Q] — each
    group's query intervals, zero-padded to the common Q.  Sharded over
    axis 0 (``parallel.mesh.batch_sharding``) this answers EVERY group of
    a region panel in ONE device call: each device searches only the
    groups placed on it, and materializing the outputs is the cross-
    device gather.  Row-for-row identical to :func:`bits_spans_kernel`
    on the same (pos row, query row) — the stacking adds a vmap, never
    arithmetic."""
    pos = pos.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    ends = ends.astype(jnp.int32)
    lo = jax.vmap(
        lambda p, s: jnp.searchsorted(p, s, side="left")
    )(pos, starts).astype(jnp.int32)
    hi = jax.vmap(
        lambda p, e: jnp.searchsorted(p, e, side="right")
    )(pos, ends).astype(jnp.int32)
    a = (starts - 1) // LEAF_SIZE
    b = (ends - 1) // LEAF_SIZE
    x = a ^ b
    shifts = jnp.arange(NUM_BIN_LEVELS, dtype=jnp.int32)
    mism = jnp.sum(
        (x[:, :, None] >> shifts[None, None, :]) != 0, axis=2,
        dtype=jnp.int32,
    )
    level = (NUM_BIN_LEVELS - mism).astype(jnp.int8)
    return lo, hi, level, a


bits_spans_stacked_jit = jax.jit(bits_spans_stacked)


def bits_spans_stacked_host(pos, starts, ends):
    """Numpy twin of :func:`bits_spans_stacked` — the registered host
    fallback (``ops.TWINS``): the same per-row binary searches and bin
    arithmetic over the same int32 values, byte-identical by
    construction."""
    pos = np.asarray(pos, np.int32)
    starts = np.asarray(starts, np.int32)
    ends = np.asarray(ends, np.int32)
    lo = np.stack([
        np.searchsorted(pos[i], starts[i], side="left").astype(np.int32)
        for i in range(pos.shape[0])
    ]) if pos.shape[0] else np.zeros(starts.shape, np.int32)
    hi = np.stack([
        np.searchsorted(pos[i], ends[i], side="right").astype(np.int32)
        for i in range(pos.shape[0])
    ]) if pos.shape[0] else np.zeros(ends.shape, np.int32)
    a = (starts.astype(np.int64) - 1) // LEAF_SIZE
    b = (ends.astype(np.int64) - 1) // LEAF_SIZE
    x = a ^ b
    shifts = np.arange(NUM_BIN_LEVELS, dtype=np.int64)
    mism = ((x[:, :, None] >> shifts[None, None, :]) != 0).sum(axis=2)
    level = (NUM_BIN_LEVELS - mism).astype(np.int8)
    return lo, hi, level, a.astype(np.int32)


def _clamped_queries(starts, ends):
    """int32 query bounds, clamped into the representable position range
    (both search paths clamp identically, so they stay byte-identical)."""
    starts = np.clip(np.asarray(starts, np.int64), 0, MAX_QUERY_POS)
    ends = np.clip(np.asarray(ends, np.int64), 0, MAX_QUERY_POS)
    return starts.astype(np.int32), ends.astype(np.int32)


#: public spelling of the clamp every search path applies (the serve
#: engine pre-clamps panel queries for the mesh path with it, so mesh and
#: single-device spans stay byte-identical on absurd bounds)
def clamped_queries(starts, ends):
    return _clamped_queries(starts, ends)


def interval_spans(pos, starts, ends, *, pos_padded: bool = False):
    """Device entry point: pad to capacities (rows to a power of two with
    the position sentinel, queries to :func:`span_query_capacity` with
    zeros), run the jitted kernel once, slice the padding back off.
    Returns numpy ``(lo, hi, level, leaf)``.

    ``pos_padded=True`` marks ``pos`` as already sentinel-padded (e.g. a
    device-resident array uploaded once per index) and skips the host-side
    pad — re-materializing a resident array on host per call would defeat
    the residency.  Sentinel-padded rows sort after every real position
    and every clamped query bound, so real spans never reach into the
    padding; padded query slots produce garbage spans that are sliced
    away before return."""
    starts, ends = _clamped_queries(starts, ends)
    nq = starts.shape[0]
    pos_p = pos if pos_padded \
        else pad_pow2(np.asarray(pos, np.int32), POS_SENTINEL)
    cap = span_query_capacity(nq)
    lo, hi, level, leaf = bits_spans_kernel_jit(
        pos_p, pad_rows(starts, cap, 0), pad_rows(ends, cap, 0)
    )
    return (
        np.asarray(lo)[:nq], np.asarray(hi)[:nq],
        np.asarray(level)[:nq], np.asarray(leaf)[:nq],
    )


def warm_spans(pos_padded, nq_min: int, nq_max: int) -> None:
    """Run the span program once at every query shape a group of
    ``nq_min``..``nq_max`` intervals can take (:func:`span_query_shapes`),
    on all-zero queries, against the sentinel-padded device array
    ``pos_padded`` — so the programs a panel can need are compiled and
    loaded before traffic asks for them."""
    for cap in span_query_shapes(nq_min, nq_max):
        zeros = np.zeros(cap, np.int32)
        jax.block_until_ready(bits_spans_kernel_jit(pos_padded, zeros, zeros))


def interval_spans_host(pos: np.ndarray, starts, ends):
    """Numpy twin of :func:`interval_spans` — the circuit-breaker /
    ``host_only`` fallback.  Byte-identical answers: the same clamped
    int32 inputs through the same binary-search definition."""
    starts, ends = _clamped_queries(starts, ends)
    lo = np.searchsorted(pos, starts, side="left").astype(np.int32)
    hi = np.searchsorted(pos, ends, side="right").astype(np.int32)
    level, leaf = bin_tokens_host(starts, ends)
    return lo, hi, level, leaf


def bin_tokens_host(starts, ends):
    """Vectorized closed-form (level, leaf) bins on host — the scalar
    definition of ``oracle.binindex.closed_form_bin`` over arrays, with
    the same :data:`MAX_QUERY_POS` clamp every other search path applies
    (so bins agree across routes even on absurd query bounds)."""
    starts = np.clip(np.asarray(starts, np.int64), 0, MAX_QUERY_POS)
    ends = np.clip(np.asarray(ends, np.int64), 0, MAX_QUERY_POS)
    a = (starts - 1) // LEAF_SIZE
    b = (ends - 1) // LEAF_SIZE
    x = a ^ b
    shifts = np.arange(NUM_BIN_LEVELS, dtype=np.int64)
    mism = ((x[:, None] >> shifts[None, :]) != 0).sum(axis=1)
    level = (NUM_BIN_LEVELS - mism).astype(np.int8)
    return level, a.astype(np.int32)
