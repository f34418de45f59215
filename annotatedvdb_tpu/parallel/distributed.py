"""Distributed annotate step: chromosome re-shard + annotate + global counters.

TPU-native mapping of the reference's share-nothing per-chromosome worker pool
(SURVEY.md §2.5): instead of demuxing a VCF into per-chromosome files and
forking processes, every shard ingests an arbitrary slice of the input,
routes each row to its owning shard with one ``all_to_all``, annotates
locally, and aggregates counters with ``psum``.  Chromosome ownership keeps
the store's partition invariant (one shard owns a chromosome's rows, so
dedup/update never crosses shards — the same lock-avoidance layout the
reference gets from Postgres LIST partitions, ``createVariant.sql:29-50``).

Ownership is **variant-count balanced**: chromosomes are assigned to shards
by greedy longest-first packing over GRCh38 chromosome lengths (a static
proxy for variant counts), the deterministic analog of the reference's
chromosome-order shuffle (``load_cadd_scores.py:306``).

The default exchange capacity is **lossless**: each source shard can send
its entire local slice to a single owner, so chromosome-sorted input (the
common case — VCFs are sorted) routes without drops.  Callers chasing
throughput on chromosome-interleaved input may pass a smaller ``capacity``;
overflow is then dropped *with accounting* (``n_dropped``).
"""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from annotatedvdb_tpu.models.pipeline import annotate_pipeline
from annotatedvdb_tpu.parallel.mesh import SHARD_AXIS
from annotatedvdb_tpu.types import NUM_CHROMOSOMES, VariantBatch


def _bucketize(owner, arrays, n_buckets: int, capacity: int):
    """Pack rows into [n_buckets * capacity] slots by owner (pad = dropped).

    Returns (packed arrays, valid mask).  Rows beyond a bucket's capacity are
    dropped and must be counted by the caller (no silent loss: the returned
    ``n_dropped`` reports them)."""
    n = owner.shape[0]
    order = jnp.argsort(owner, stable=True)
    owner_sorted = owner[order]
    # first row index of each bucket in the sorted order
    starts = jnp.searchsorted(owner_sorted, jnp.arange(n_buckets, dtype=owner.dtype))
    rank_in_bucket = jnp.arange(n, dtype=jnp.int32) - starts[owner_sorted]
    in_capacity = rank_in_bucket < capacity
    slot = jnp.where(
        in_capacity, owner_sorted * capacity + rank_in_bucket, n_buckets * capacity
    )

    def pack(x):
        x_sorted = x[order]
        out_shape = (n_buckets * capacity,) + x.shape[1:]
        return jnp.zeros(out_shape, x.dtype).at[slot].set(
            x_sorted, mode="drop", unique_indices=True
        )

    packed = jax.tree.map(pack, arrays)
    valid = (
        jnp.zeros((n_buckets * capacity,), jnp.bool_)
        .at[slot]
        .set(in_capacity, mode="drop", unique_indices=True)
    )
    n_dropped = jnp.sum(~in_capacity, dtype=jnp.int32)
    return packed, valid, n_dropped


def reshard_by_owner(owner, arrays, n_shards: int, capacity: int, axis=SHARD_AXIS):
    """Inside shard_map: route rows to ``owner``-th shard via one all_to_all.

    Each shard sends up to ``capacity`` rows to each destination; returns the
    received rows [n_shards * capacity, ...], their validity mask, and the
    per-shard dropped-row count (psum'd to a global)."""
    packed, valid, n_dropped = _bucketize(owner, arrays, n_shards, capacity)

    def exchange(x):
        grouped = x.reshape((n_shards, capacity) + x.shape[1:])
        received = jax.lax.all_to_all(grouped, axis, split_axis=0, concat_axis=0)
        return received.reshape((n_shards * capacity,) + x.shape[1:])

    received = jax.tree.map(exchange, packed)
    valid = exchange(valid)
    total_dropped = jax.lax.psum(n_dropped, axis)
    return received, valid, total_dropped


@lru_cache(maxsize=None)
def chromosome_owner_table(n_shards: int, build: str = "GRCh38") -> tuple:
    """[NUM_CHROMOSOMES + 1] owner table: greedy longest-first packing of
    chromosomes onto shards weighted by chromosome length — ~proportional to
    variant count, so shard loads stay within ~1.5x of each other (chr1 is
    ~15x chr21; contiguous blocks would skew ~5x).  Index 0 (pad rows) maps
    to shard 0."""
    from annotatedvdb_tpu.genome.assemblies import chromosome_lengths

    lengths = chromosome_lengths(build)
    table = [0] * (NUM_CHROMOSOMES + 1)
    load = [0] * n_shards
    for code in sorted(lengths, key=lambda c: -lengths[c]):
        s = min(range(n_shards), key=load.__getitem__)
        table[code] = s
        load[s] += lengths[code]
    return tuple(table)


def chromosome_owner(chrom, n_shards: int):
    """Owning shard of each row's chromosome code (balanced static table)."""
    table = jnp.asarray(chromosome_owner_table(n_shards), jnp.int32)
    return table[jnp.clip(chrom.astype(jnp.int32), 0, NUM_CHROMOSOMES)]


POSITION_BLOCK_BITS = 14  # 16kb blocks: fine-grained spread, bin-cache friendly


def position_block_owner(chrom, pos, n_shards: int) -> np.ndarray:
    """Host-side owner map for annotate-only fan-out: round-robin 16kb
    position blocks across shards.  Chromosome-sorted input (every VCF) then
    spreads evenly instead of serializing onto one chromosome owner — the
    right routing while dedup/store remain host-side and no device holds
    persistent per-chromosome state.  Chromosome enters the rotation so
    chromosomes don't all start on shard 0."""
    blocks = (np.asarray(pos).astype(np.int64) >> POSITION_BLOCK_BITS)
    return ((blocks + np.asarray(chrom).astype(np.int64)) % n_shards).astype(
        np.int32
    )


def exact_capacity(owner: np.ndarray, n_shards: int) -> int:
    """Smallest per-(source, destination) slot count that loses no rows for
    this owner map, rounded up to a power of two (bounds the set of compiled
    exchange shapes)."""
    from annotatedvdb_tpu.utils.arrays import next_pow2

    per_source = np.asarray(owner).reshape(n_shards, -1)
    cap = 1
    for s in range(n_shards):
        counts = np.bincount(per_source[s], minlength=n_shards)
        cap = max(cap, int(counts.max()))
    return next_pow2(cap)


def _step_prologue(mesh, batch: VariantBatch, capacity: int | None, row_id,
                   owner: np.ndarray | None = None):
    """Shared entry checks/defaults for the three distributed steps:
    divisibility, lossless default capacity for the owner map, and the
    identity row-id map.  Returns (n_shards, capacity, row_id)."""
    n_shards = mesh.devices.size
    if batch.n % n_shards:
        raise ValueError(
            f"batch size {batch.n} not divisible by {n_shards} shards — pad "
            "with chrom-0 rows first (loaders use _pad_batch)"
        )
    n_local = batch.n // n_shards
    if capacity is None:
        if owner is not None:
            capacity = min(exact_capacity(owner, n_shards), n_local)
        else:
            host_owner = np.asarray(chromosome_owner_table(n_shards))[
                np.clip(np.asarray(batch.chrom, np.int32), 0, NUM_CHROMOSOMES)
            ]
            capacity = min(exact_capacity(host_owner, n_shards), n_local)
    if row_id is None:
        row_id = np.arange(batch.n, dtype=np.int32)
    return n_shards, capacity, row_id


def distributed_annotate_step(
    mesh, batch: VariantBatch, capacity: int | None = None, row_id=None,
    owner: np.ndarray | None = None,
):
    """Full sharded load step: reshard rows to chromosome owners, annotate,
    and count classes globally.  This is the function the driver dry-runs
    multi-chip (``__graft_entry__.dryrun_multichip``) and the path
    ``TpuVcfLoader`` takes on a multi-device mesh.

    Returns ``(ann, row_id_out, counts, n_dropped, n_fallback)``:

    - ``ann``: annotated arrays in post-exchange order;
    - ``row_id_out``: for each post-exchange slot, the caller-supplied row id
      of the input row occupying it (−1 for empty slots, pad rows, and
      dropped rows) — the host scatters annotations back to input order
      with it;
    - ``counts``: global per-class psum over device-annotated rows;
    - ``n_dropped``: rows lost to capacity overflow (0 with the lossless
      default);
    - ``n_fallback``: rows flagged for the host long-allele path.

    ``owner`` is an optional host-computed [N] shard assignment (e.g.
    :func:`position_block_owner` for annotate-only fan-out); without it,
    rows route to their chromosome's owner (the device-resident-store
    layout).  ``capacity`` bounds rows each shard sends per destination; the
    default is the host-computed exact lossless minimum for the owner map
    (for the chromosome map on sorted input that is ``n_local`` — the whole
    slice may route to one owner).  Row conservation invariant:
    ``sum(counts) + n_fallback + n_dropped == non-pad input rows``."""
    n_shards, capacity, row_id = _step_prologue(
        mesh, batch, capacity, row_id, owner
    )
    owner_in = (
        np.asarray(owner, np.int32) if owner is not None
        else np.full(batch.n, -1, np.int32)  # -1: chromosome routing in-trace
    )
    step = _annotate_step_program(mesh, n_shards, capacity, owner is None)
    return step(
        batch.chrom, batch.pos, batch.ref, batch.alt,
        batch.ref_len, batch.alt_len, row_id, owner_in,
    )


@lru_cache(maxsize=64)
def _annotate_step_program(mesh, n_shards: int, capacity: int,
                           use_chrom_owner: bool):
    """The shard_map program for :func:`distributed_annotate_step`, cached
    by (mesh, shape parameters) — rebuilding the closure per call would
    re-trace AND re-compile every step (~40s each on a virtual CPU mesh)."""
    spec = P(SHARD_AXIS)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec,) * 8,
        out_specs=(
            jax.tree.map(lambda _: spec, _annotated_specs()),
            spec, P(), P(), P(),
        ),
        check_vma=False,
    )
    def step(chrom, pos, ref, alt, ref_len, alt_len, rid, owner_rows):
        owner = (
            chromosome_owner(chrom, n_shards) if use_chrom_owner else owner_rows
        )
        arrays = (chrom, pos, ref, alt, ref_len, alt_len, rid)
        (chrom, pos, ref, alt, ref_len, alt_len, rid), valid, dropped = (
            reshard_by_owner(owner, arrays, n_shards, capacity)
        )
        ann = annotate_pipeline(chrom, pos, ref, alt, ref_len, alt_len)
        # global per-class counters (reference: per-worker counter dicts,
        # variant_loader.py:387-392 — here one psum).  Pad rows (chrom 0,
        # both in-batch padding and empty exchange slots) and truncated
        # host-fallback rows are excluded: their kernel outputs are undefined.
        real = valid & (chrom > 0)
        counted = real & ~ann.host_fallback
        counts = jnp.zeros((8,), jnp.int32).at[ann.variant_class].add(
            counted.astype(jnp.int32), mode="drop"
        )
        counts = jax.lax.psum(counts, SHARD_AXIS)
        n_fallback = jax.lax.psum(
            jnp.sum(real & ann.host_fallback, dtype=jnp.int32), SHARD_AXIS
        )
        # row ids for the host-side scatter; -1 marks unusable slots
        rid_out = jnp.where(real, rid, -1)
        return ann, rid_out, counts, dropped, n_fallback

    # one jitted program: shard_map OUTSIDE jit executes eagerly, paying a
    # per-primitive dispatch (measured ~1000x slower on a CPU mesh)
    return jax.jit(step)


def _annotated_specs():
    from annotatedvdb_tpu.types import AnnotatedBatch

    return AnnotatedBatch(*([0] * len(AnnotatedBatch._fields)))


def distributed_insert_step(mesh, batch: VariantBatch, dev_store=None,
                            capacity: int | None = None, row_id=None):
    """Full sharded INSERT step: chromosome re-shard + annotate + in-batch
    dedup + store membership, all inside one mesh program (VERDICT r3 #4 —
    previously only annotate ran on the mesh; duplicate detection and store
    probes serialized on the host after device fan-in).

    Rows route to their chromosome's owning shard (``chromosome_owner``), so
    each shard sees every row of the chromosomes it owns — the partition
    invariant that makes per-shard dedup GLOBALLY correct (the reference
    gets the same guarantee from per-chromosome worker processes sharing a
    DB, ``database/variant.py:287-309``).

    ``dev_store``: optional
    :class:`~annotatedvdb_tpu.parallel.device_store.DeviceShardStore`
    snapshot; when present each shard probes its resident slice with the
    sorted two-level search (``ops.dedup.lookup_in_sorted_multi``) and
    duplicate counts ride one psum.  Returns
    ``(ann, rid_out, flags, counters)``:

    - ``ann``: annotated arrays in post-exchange order;
    - ``rid_out``: input row id per slot (-1 = empty/pad/dropped);
    - ``flags``: dict of per-slot bool arrays ``dup_batch`` (duplicates an
      earlier row of this batch) and ``in_store`` (identity already present
      in the snapshot) — scatter back with ``rid_out`` exactly like the
      annotate outputs;
    - ``counters``: dict of psum'd globals (``class_counts``, ``n_dropped``,
      ``n_fallback``, ``n_batch_dup``, ``n_store_dup``).

    Host-fallback rows (alleles wider than the device arrays) are excluded
    from both verdicts — their truncated-prefix identity could collide, so
    the host re-checks them exactly as the single-device path does."""
    n_shards, capacity, row_id = _step_prologue(mesh, batch, capacity, row_id)
    has_store = dev_store is not None
    store_arrays = tuple(dev_store[:7]) if has_store else ()
    step = _insert_step_program(mesh, n_shards, capacity, has_store)
    return step(
        batch.chrom, batch.pos, batch.ref, batch.alt,
        batch.ref_len, batch.alt_len, row_id, *store_arrays,
    )


def distributed_update_step(mesh, batch: VariantBatch, dev_store,
                            capacity: int | None = None, row_id=None,
                            routing: str = "chrom"):
    """Sharded UPDATE-identity step: chromosome re-shard + in-mesh store
    lookup, one mesh program.  The TPU mapping of the reference's
    multi-process update fan-out (``load_vep_result.py:304-311``,
    ``load_cadd_scores.py:305-313``): each shard resolves the update rows
    of the chromosomes it owns against its resident snapshot slice, and
    the host gets back *store row ids* — it applies the annotation writes
    directly, no host-side identity search remains.

    No annotate kernel runs (updates need identity only), so the step is
    one all_to_all + hash + two-level sorted lookup per shard plus psum'd
    match counters.

    Returns ``(rid_out, found, store_row, counters)``:

    - ``rid_out``: input row id per post-exchange slot (-1 = empty/pad);
    - ``found``: bool per slot — identity present in the snapshot;
    - ``store_row``: int64 host-store global row id per slot (-1 when not
      found) — valid until the host shard is appended/compacted;
    - ``counters``: psum'd ``{"n_matched", "n_missing", "n_fallback",
      "n_dropped"}``; fallback rows (alleles wider than the device arrays)
      are excluded from both verdicts and re-checked host-side, exactly
      like the insert step.  ``n_dropped`` is nonzero only with an
      explicit undersized ``capacity`` — dropped rows return no rid, so
      callers must treat them as unresolved, not missing.

    ``routing`` must match the snapshot's partition
    (``build_device_shard_store``): ``"chrom"`` routes whole chromosomes,
    ``"position"`` spreads 16kb position blocks across shards — the right
    choice for chromosome-sorted update streams, which would otherwise
    land every flush on one shard."""
    if routing not in ("chrom", "position"):
        raise ValueError(f"unknown update routing {routing!r}")
    owner = (
        position_block_owner(
            np.asarray(batch.chrom, np.int64),
            np.asarray(batch.pos, np.int64), mesh.devices.size,
        )
        if routing == "position" else None
    )
    n_shards, capacity, row_id = _step_prologue(
        mesh, batch, capacity, row_id, owner
    )
    step = _update_step_program(mesh, n_shards, capacity,
                                routing == "position")
    return step(
        batch.chrom, batch.pos, batch.ref, batch.alt,
        batch.ref_len, batch.alt_len, row_id,
        *(dev_store[:7] + (dev_store.row_id,)),
    )


def distributed_serve_lookup_step(mesh, chrom, pos, hm, ref, alt,
                                  ref_len, alt_len, dev_store,
                                  capacity: int | None = None,
                                  row_id=None):
    """Sharded SERVE bulk lookup: chromosome re-shard + in-mesh store
    membership, one mesh program — the serving read path's twin of
    :func:`distributed_update_step`.

    Differences that matter to serving byte-parity:

    - the identity hash arrives **host-computed** (``hm``: the loaders'
      ``identity_hashes`` full-string hash, chromosome-mixed) instead of
      being re-derived in-trace from width-truncated bytes — so
      long-allele queries resolve with EXACTLY the host ``Segment.probe``
      semantics (full-string hash + truncated byte/length confirmation)
      and no host re-check pass is needed;
    - no counters ride the program (serving wants rows, and a psum per
      bulk drain is a collective the hot path should not pay).

    Returns ``(rid_out, found, store_row)``, each ``[n_shards *
    capacity]`` in post-exchange order — materializing them IS the
    cross-device gather.  Scatter back with ``rid_out`` (−1 = empty/pad
    slot); ``store_row`` is the host-store global row id (−1 = miss),
    directly renderable via ``serve.engine.render_variant``."""
    n = chrom.shape[0]
    n_shards = mesh.devices.size
    if n % n_shards:
        raise ValueError(
            f"query batch {n} not divisible by {n_shards} shards — pad "
            "with chrom-0 rows first"
        )
    if capacity is None:
        host_owner = np.asarray(chromosome_owner_table(n_shards))[
            np.clip(np.asarray(chrom, np.int32), 0, NUM_CHROMOSOMES)
        ]
        capacity = min(exact_capacity(host_owner, n_shards), n // n_shards)
    if row_id is None:
        row_id = np.arange(n, dtype=np.int32)
    step = _serve_lookup_program(mesh, n_shards, capacity)
    return step(
        chrom, pos, hm, ref, alt, ref_len, alt_len, row_id,
        *(dev_store[:7] + (dev_store.row_id,)),
    )


@lru_cache(maxsize=64)
def _serve_lookup_program(mesh, n_shards: int, capacity: int):
    """The shard_map program for :func:`distributed_serve_lookup_step`,
    cached by (mesh, shape parameters) — same re-compile trap as the
    other steps."""
    from annotatedvdb_tpu.ops.dedup import lookup_in_sorted_multi

    spec = P(SHARD_AXIS)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec,) * 8 + (spec,) * 8,
        out_specs=(spec, spec, spec),
        check_vma=False,
    )
    def step(chrom, pos, hm, ref, alt, ref_len, alt_len, rid, *store_cols):
        owner = chromosome_owner(chrom, n_shards)
        arrays = (chrom, pos, hm, ref, alt, ref_len, alt_len, rid)
        (chrom, pos, hm, ref, alt, ref_len, alt_len, rid), valid, _dropped = (
            reshard_by_owner(owner, arrays, n_shards, capacity)
        )
        (s_chrom, s_pos, s_hm, s_ref, s_alt, s_rl, s_al, s_rid) = store_cols
        s_chrom, s_pos, s_hm = s_chrom[0], s_pos[0], s_hm[0]
        s_ref, s_alt, s_rl, s_al = s_ref[0], s_alt[0], s_rl[0], s_al[0]
        s_rid = s_rid[0]
        real = valid & (chrom > 0)
        # pad/empty slots carry chrom 0 + zero identities: salt their
        # position out of the sorted probe so they can never alias a row
        slot = jnp.arange(pos.shape[0], dtype=jnp.int32)
        pos_k = jnp.where(real, pos, -1 - slot)
        found, idx = lookup_in_sorted_multi(
            s_chrom, s_pos, s_hm, s_ref, s_alt, s_rl, s_al,
            chrom, pos_k, hm, ref, alt, ref_len, alt_len,
        )
        found = found & real
        store_row = jnp.where(
            found, s_rid[jnp.clip(idx, 0, s_rid.shape[0] - 1)], -1
        )
        rid_out = jnp.where(real, rid, -1)
        return rid_out, found, store_row

    # see _annotate_step_program: un-jitted shard_map executes eagerly
    return jax.jit(step)


@lru_cache(maxsize=64)
def _update_step_program(mesh, n_shards: int, capacity: int,
                         position_routing: bool = False):
    """The shard_map program for :func:`distributed_update_step`, cached by
    (mesh, shape parameters) — same re-compile trap as the other steps."""
    from annotatedvdb_tpu.ops.dedup import lookup_in_sorted_multi, mix_chrom_hash
    from annotatedvdb_tpu.ops.hashing import allele_hash

    spec = P(SHARD_AXIS)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec,) * 7 + (spec,) * 8,
        out_specs=(
            spec, spec, spec,
            {"n_matched": P(), "n_missing": P(), "n_fallback": P(),
             "n_dropped": P()},
        ),
        check_vma=False,
    )
    def step(chrom, pos, ref, alt, ref_len, alt_len, rid, *store_cols):
        if position_routing:
            # in-trace twin of position_block_owner — must stay identical
            # to the host formula the snapshot was partitioned with.
            # int32 is exact: pos < 2^31 and the shift only shrinks it
            # (int64 would be silently truncated under 32-bit jax anyway)
            owner = (
                ((pos.astype(jnp.int32) >> POSITION_BLOCK_BITS)
                 + chrom.astype(jnp.int32)) % n_shards
            ).astype(jnp.int32)
        else:
            owner = chromosome_owner(chrom, n_shards)
        arrays = (chrom, pos, ref, alt, ref_len, alt_len, rid)
        (chrom, pos, ref, alt, ref_len, alt_len, rid), valid, dropped = (
            reshard_by_owner(owner, arrays, n_shards, capacity)
        )
        (s_chrom, s_pos, s_hm, s_ref, s_alt, s_rl, s_al, s_rid) = store_cols
        s_chrom, s_pos, s_hm = s_chrom[0], s_pos[0], s_hm[0]
        s_ref, s_alt, s_rl, s_al = s_ref[0], s_alt[0], s_rl[0], s_al[0]
        s_rid = s_rid[0]
        real = valid & (chrom > 0)
        # over-width rows: truncated-prefix identity could collide — the
        # host re-checks them with full-string hashes (same discipline as
        # the insert step)
        fallback = real & (
            (ref_len > ref.shape[1]) | (alt_len > alt.shape[1])
        )
        usable = real & ~fallback
        h = allele_hash(ref, alt, ref_len, alt_len)
        slot = jnp.arange(pos.shape[0], dtype=jnp.int32)
        pos_k = jnp.where(usable, pos, -1 - slot)
        hm = mix_chrom_hash(h, chrom)
        found, idx = lookup_in_sorted_multi(
            s_chrom, s_pos, s_hm, s_ref, s_alt, s_rl, s_al,
            chrom, pos_k, hm, ref, alt, ref_len, alt_len,
        )
        found = found & usable
        store_row = jnp.where(
            found, s_rid[jnp.clip(idx, 0, s_rid.shape[0] - 1)], -1
        )
        counters = {
            "n_matched": jax.lax.psum(
                jnp.sum(found, dtype=jnp.int32), SHARD_AXIS
            ),
            "n_missing": jax.lax.psum(
                jnp.sum(usable & ~found, dtype=jnp.int32), SHARD_AXIS
            ),
            "n_fallback": jax.lax.psum(
                jnp.sum(fallback, dtype=jnp.int32), SHARD_AXIS
            ),
            "n_dropped": dropped,
        }
        rid_out = jnp.where(real, rid, -1)
        return rid_out, found, store_row, counters

    # see _annotate_step_program: un-jitted shard_map executes eagerly
    return jax.jit(step)


@lru_cache(maxsize=64)
def _insert_step_program(mesh, n_shards: int, capacity: int, has_store: bool):
    """The shard_map program for :func:`distributed_insert_step`, cached by
    (mesh, shape parameters) — same re-compile trap as
    :func:`_annotate_step_program`."""
    from annotatedvdb_tpu.ops.dedup import (
        lookup_in_sorted_multi,
        mark_batch_duplicates_multi,
        mix_chrom_hash,
    )
    from annotatedvdb_tpu.ops.hashing import allele_hash

    spec = P(SHARD_AXIS)
    store_specs = (spec,) * (7 if has_store else 0)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec,) * 7 + store_specs,
        out_specs=(
            jax.tree.map(lambda _: spec, _annotated_specs()),
            spec,
            {"dup_batch": spec, "in_store": spec},
            {"class_counts": P(), "n_dropped": P(), "n_fallback": P(),
             "n_batch_dup": P(), "n_store_dup": P()},
        ),
        check_vma=False,
    )
    def step(chrom, pos, ref, alt, ref_len, alt_len, rid, *store_cols):
        owner = chromosome_owner(chrom, n_shards)
        arrays = (chrom, pos, ref, alt, ref_len, alt_len, rid)
        (chrom, pos, ref, alt, ref_len, alt_len, rid), valid, dropped = (
            reshard_by_owner(owner, arrays, n_shards, capacity)
        )
        ann = annotate_pipeline(chrom, pos, ref, alt, ref_len, alt_len)
        real = valid & (chrom > 0)
        usable = real & ~ann.host_fallback
        h = allele_hash(ref, alt, ref_len, alt_len)
        # pad/empty slots carry chrom 0 + zero alleles and would dedup
        # against each other: salt them out of every identity comparison
        # by replacing their position with a unique negative sentinel
        slot = jnp.arange(pos.shape[0], dtype=jnp.int32)
        pos_k = jnp.where(usable, pos, -1 - slot)
        dup_batch = mark_batch_duplicates_multi(
            chrom, pos_k, h, ref, alt, ref_len, alt_len
        ) & usable
        if store_cols:
            (s_chrom, s_pos, s_hm, s_ref, s_alt, s_rl, s_al) = store_cols
            # shard_map passes the [1, M, ...] local block; drop the axis
            s_chrom, s_pos, s_hm = s_chrom[0], s_pos[0], s_hm[0]
            s_ref, s_alt, s_rl, s_al = s_ref[0], s_alt[0], s_rl[0], s_al[0]
            hm = mix_chrom_hash(h, chrom)
            in_store, _ = lookup_in_sorted_multi(
                s_chrom, s_pos, s_hm, s_ref, s_alt, s_rl, s_al,
                chrom, pos_k, hm, ref, alt, ref_len, alt_len,
            )
            # disjoint verdicts: a row that duplicates an earlier batch row
            # AND exists in the store counts once, as an in-batch dup —
            # matching the host loader's order (dedup filters first, then
            # membership probes survivors) and keeping the conservation
            # identity n_new + n_batch_dup + n_store_dup + n_fallback == n
            in_store = in_store & usable & ~dup_batch
        else:
            in_store = jnp.zeros(pos.shape, jnp.bool_)
        counted = usable & ~dup_batch & ~in_store
        counts = jnp.zeros((8,), jnp.int32).at[ann.variant_class].add(
            counted.astype(jnp.int32), mode="drop"
        )
        counters = {
            "class_counts": jax.lax.psum(counts, SHARD_AXIS),
            "n_dropped": dropped,
            "n_fallback": jax.lax.psum(
                jnp.sum(real & ann.host_fallback, dtype=jnp.int32), SHARD_AXIS
            ),
            "n_batch_dup": jax.lax.psum(
                jnp.sum(dup_batch, dtype=jnp.int32), SHARD_AXIS
            ),
            "n_store_dup": jax.lax.psum(
                jnp.sum(in_store, dtype=jnp.int32), SHARD_AXIS
            ),
        }
        rid_out = jnp.where(real, rid, -1)
        return ann, rid_out, {"dup_batch": dup_batch, "in_store": in_store}, counters

    # see _annotate_step_program: un-jitted shard_map executes eagerly
    return jax.jit(step)
