"""Device kernels and the device/host twin contract.

Every jitted kernel in this package has a **host twin**: a numpy (or
scalar-oracle) function producing byte-identical answers on the host.
The twins are what the serving circuit breaker's ``host_only`` fallback,
the remote-link loaders, and every deviceless environment actually run —
so the pairing is a registry, not a convention.

:data:`TWINS` is the canonical mapping (the ``faults.POINTS`` pattern),
``"<kernel>": "<twin>"`` as package-relative dotted names.  The static
analyzer enforces it three ways: a jitted function under ``ops/`` missing
from the registry is **AVDB901**, an entry that doesn't resolve is
**AVDB902**, and a pair no single test file exercises together is
**AVDB903** (``tests/test_twins.py`` is the canonical parity suite).
"""

from .annotate import annotate_kernel
from .binindex import bin_index_kernel, LEAF_SIZE, NUM_BIN_LEVELS

#: canonical device-kernel -> host-twin registry (dotted names relative
#: to ``annotatedvdb_tpu``).  A new jitted kernel lands with an entry
#: here AND a parity test referencing both names (tests/test_twins.py),
#: the same way a new fault point lands with a matrix case.
TWINS: dict = {
    "ops.annotate.annotate_kernel_jit": "ops.annotate.annotate_kernel_np",
    "ops.annotate_pallas.annotate_bin_pallas":
        "ops.annotate.annotate_kernel_np",
    "ops.binindex.bin_index_kernel_jit": "oracle.binindex.closed_form_bin",
    "ops.cadd_join.cadd_join_kernel": "ops.cadd_join.cadd_join_host",
    "ops.dedup.mark_batch_duplicates_jit":
        "ops.dedup.mark_batch_duplicates_np",
    "ops.dedup.mark_batch_duplicates_multi_jit":
        "ops.dedup.mark_batch_duplicates_multi_np",
    "ops.dedup.lookup_in_sorted_jit": "ops.dedup.lookup_in_sorted_np",
    # the store's device probe: the same search, its queries one packed
    # buffer in and the index alone out (found is index >= 0)
    "ops.dedup.lookup_in_sorted_packed_jit": "ops.dedup.lookup_in_sorted_np",
    "ops.dedup.lookup_in_sorted_multi_jit":
        "ops.dedup.lookup_in_sorted_multi_np",
    "ops.hashing.allele_hash_jit": "ops.hashing.allele_hash_np",
    "ops.intervals.bits_spans_kernel_jit":
        "ops.intervals.interval_spans_host",
    # mesh-sharded (pjit-with-sharded-inputs) kernel surfaces: same math,
    # same numpy twins — the mesh only changes WHERE the rows compute
    "ops.annotate.annotate_kernel_mesh": "ops.annotate.annotate_kernel_np",
    "ops.hashing.allele_hash_mesh": "ops.hashing.allele_hash_np",
    "ops.binindex.bin_index_kernel_mesh": "oracle.binindex.closed_form_bin",
    "ops.dedup.mark_batch_duplicates_mesh":
        "ops.dedup.mark_batch_duplicates_np",
    "ops.intervals.bits_spans_stacked_jit":
        "ops.intervals.bits_spans_stacked_host",
    "ops.pack.pack_outputs_jit": "ops.pack.pack_outputs_np",
    "ops.pack.inflate_alleles_jit": "ops.pack.inflate_alleles_np",
    "ops.pack.pack_vep_outputs_jit": "ops.pack.pack_vep_outputs_np",
    # fused analytics kernels (ops/stats.py): integer-only segmented
    # reductions, so the twins are byte-exact by construction
    "ops.stats.stats_panel_kernel_jit": "ops.stats.stats_panel_host",
    "ops.stats.windowed_stats_kernel_jit": "ops.stats.windowed_stats_host",
    # corpus export packing (ops/export_pack.py): elementwise int32/int8
    # tokenize+mask, so the twin is byte-exact by construction
    "ops.export_pack.export_pack_kernel_jit":
        "ops.export_pack.export_pack_host",
}

__all__ = ["annotate_kernel", "bin_index_kernel", "LEAF_SIZE",
           "NUM_BIN_LEVELS", "TWINS"]
