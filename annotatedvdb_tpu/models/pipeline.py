"""The flagship annotation pipeline: the framework's jittable "forward step".

One fused XLA program per batch replaces the reference's per-variant hot loop
(``Load/bin/load_vcf_file.py:99-171`` — parse → normalize → PK → bin-index →
buffer, with a Postgres round-trip per duplicate check and per bin-cache
miss).  Everything here is elementwise/gather math, so XLA fuses it into a
few HBM-bandwidth-bound loops; there is no data-dependent control flow.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from annotatedvdb_tpu.ops.annotate import annotate_kernel
from annotatedvdb_tpu.ops.binindex import bin_index_kernel
from annotatedvdb_tpu.types import AnnotatedBatch, VariantBatch


def annotate_pipeline(chrom, pos, ref, alt, ref_len, alt_len) -> AnnotatedBatch:
    """Full annotate step for one batch: normalization + end location +
    variant class + bin index.

    The bin lookup takes the raw VCF position and the inferred end location,
    matching the reference call site
    (``Util/lib/python/loaders/vcf_variant_loader.py:310-311``).
    ``chrom`` rides along untouched (bin paths need it only at egress)."""
    del chrom  # identity only; not needed by the device math
    ann = annotate_kernel(pos, ref, alt, ref_len, alt_len)
    bin_level, leaf_bin = bin_index_kernel(pos, ann["end_location"])
    return AnnotatedBatch(
        prefix_len=ann["prefix_len"],
        norm_ref_len=ann["norm_ref_len"],
        norm_alt_len=ann["norm_alt_len"],
        end_location=ann["end_location"],
        location_start=ann["location_start"],
        location_end=ann["location_end"],
        variant_class=ann["variant_class"],
        is_dup_motif=ann["is_dup_motif"],
        bin_level=bin_level,
        leaf_bin=leaf_bin,
        needs_digest=ann["needs_digest"],
        host_fallback=ann["host_fallback"],
    )


annotate_pipeline_jit = jax.jit(annotate_pipeline)


def annotate_pipeline_pallas(chrom, pos, ref, alt, ref_len, alt_len) -> AnnotatedBatch:
    """Same step as :func:`annotate_pipeline` via the fused Pallas kernel
    (``ops/annotate_pallas.py``) — one VMEM pass, gather-free.  Requires a
    TPU backend (the jnp path remains the portable/virtual-CPU-mesh
    default)."""
    from annotatedvdb_tpu.ops.annotate_pallas import annotate_bin_pallas

    del chrom
    out = annotate_bin_pallas(pos, ref, alt, ref_len, alt_len)
    return AnnotatedBatch(**out)


annotate_pipeline_pallas_jit = jax.jit(annotate_pipeline_pallas)


class KernelParityError(RuntimeError):
    """The Pallas annotate kernel disagreed with the jnp kernel on the
    probe batch (message names the field and the first differing row)."""


def best_annotate_pipeline():
    """(fn, name): the annotate step for the active backend.

    On a ``tpu`` backend the fused Pallas kernel is THE kernel: it is
    compiled and checked against the jnp kernel on a probe batch, and a
    compile error or a parity mismatch raises (:class:`KernelParityError`
    names the field and row) — a load never quietly runs a different
    kernel from the one it reports.  Every other backend (the CPU test
    meshes) gets the portable jnp pipeline; interpret mode is never
    chosen by the program."""
    if jax.default_backend() != "tpu":
        return annotate_pipeline_jit, "jnp"
    import numpy as np

    from annotatedvdb_tpu.io.synth import synthetic_batch

    probe = synthetic_batch(256, width=16)
    args = (probe.chrom, probe.pos, probe.ref, probe.alt,
            probe.ref_len, probe.alt_len)
    want = annotate_pipeline_jit(*args)
    got = annotate_pipeline_pallas_jit(*args)
    # host_fallback / needs_digest are identity-critical (they gate the
    # long-allele re-hash and digest-PK retention): compare them on every
    # row; kernel-math fields only where outputs are defined
    defined = ~np.asarray(want.host_fallback)
    every = np.ones_like(defined)
    for name, rows in (
        ("host_fallback", every), ("needs_digest", every),
        ("variant_class", defined), ("end_location", defined),
        ("prefix_len", defined), ("bin_level", defined),
        ("leaf_bin", defined), ("is_dup_motif", defined),
    ):
        w = np.asarray(getattr(want, name))
        g = np.asarray(getattr(got, name))
        bad = np.nonzero((w != g) & rows)[0]
        if bad.size:
            i = int(bad[0])
            raise KernelParityError(
                f"pallas annotate kernel differs from the jnp kernel in "
                f"{name!r} at probe row {i}: pallas {g[i]!r}, jnp {w[i]!r} "
                f"({bad.size} of {w.shape[0]} rows differ)"
            )
    return annotate_pipeline_pallas_jit, "pallas"


_SELECTED: tuple | None = None
_SELECT_LOCK = threading.Lock()


def annotate_fn():
    """The process-wide annotate step: :func:`best_annotate_pipeline`'s
    choice, probed once and cached.  This is what the production loaders
    call, so a real-TPU load runs the same Pallas kernel the bench
    measures.

    Selection is lock-guarded: the overlapped executor calls this from its
    dispatch *thread* (``loaders/vcf_loader.py``), and two first-callers
    racing the parity probe would compile it twice.

    Calling the returned function is an **async dispatch**: jax enqueues
    the XLA program and returns placeholder arrays immediately (CPU backend
    included — ``jax_cpu_enable_async_dispatch``), so the caller's
    subsequent host work overlaps device execution.  The block happens
    where a result is materialized (``np.asarray``/``np.array``) — the
    executor does that on its *process* stage, one pipeline step behind
    dispatch, which is what turns async dispatch into real ingest/compute
    overlap instead of an immediate stall."""
    global _SELECTED
    if _SELECTED is None:
        with _SELECT_LOCK:
            if _SELECTED is None:
                _SELECTED = best_annotate_pipeline()
    return _SELECTED[0]


def selected_kernel(resolve: bool = True) -> str | None:
    """'pallas' or 'jnp' — which kernel :func:`annotate_fn` resolved to.
    ``resolve=False`` only reports: None when nothing has asked yet (a
    summary written on an abort path must not start a compile)."""
    if resolve:
        annotate_fn()
    return _SELECTED[1] if _SELECTED is not None else None


def annotate_batch(batch: VariantBatch) -> AnnotatedBatch:
    """Annotate a :class:`VariantBatch` with the selected step.  Shapes are
    static per (N, W): pad batches to a fixed size to avoid recompiles
    (``loaders.vcf_loader._pad_batch``)."""
    return annotate_fn()(
        batch.chrom, batch.pos, batch.ref, batch.alt,
        batch.ref_len, batch.alt_len,
    )
