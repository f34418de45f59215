"""Closed-form hierarchical bin-index kernel.

The reference resolves every bin lookup through a Postgres ``find_bin_index()``
round-trip against a materialized 14-level tree (64 Mb bins halving to
15.625 kb leaves, ``BinIndex/bin/generate_bin_index_references.py:93``), with a
current-bin cache exploiting sorted input
(``BinIndex/lib/python/bin_index.py:43-75``).

Because the tree is a fixed halving hierarchy, the deepest bin containing an
interval is pure integer arithmetic — no table, no cache, no I/O:

- global leaf index of a 1-based position ``p`` is ``(p-1) // 15625``
  (bins are ``(lower, upper]``);
- the level-l bin index is the leaf index shifted right by ``13-l``;
- the deepest level on which ``start`` and ``end`` agree is
  ``13 - popcount-style run of (leaf_a XOR leaf_b)``.

The kernel emits (level, leaf_bin) integer pairs; ltree path strings are
materialized only at egress (``oracle/binindex.py:closed_form_path``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from annotatedvdb_tpu.parallel.mesh import mesh_pjit

LEAF_SIZE = 15_625
NUM_BIN_LEVELS = 13  # levels 1..13 below the whole-chromosome level 0


@jax.named_scope("avdb.bin_index")
def bin_index_kernel(start, end):
    """Deepest enclosing bin for [start, end] intervals (1-based, inclusive).

    Returns (bin_level [N] int8 in 0..13, leaf_bin [N] int32 — the global
    level-13 bin of ``start``; at level l the global bin is
    ``leaf_bin >> (13-l)``)."""
    start = start.astype(jnp.int32)
    end = end.astype(jnp.int32)
    a = (start - 1) // LEAF_SIZE
    b = (end - 1) // LEAF_SIZE
    x = a ^ b
    # number of k in [0, 13) with (x >> k) != 0  ==  min(13, bit_length(x))
    shifts = jnp.arange(NUM_BIN_LEVELS, dtype=jnp.int32)            # [13]
    mism = jnp.sum(
        (x[:, None] >> shifts[None, :]) != 0, axis=1, dtype=jnp.int32
    )
    level = (NUM_BIN_LEVELS - mism).astype(jnp.int8)
    return level, a


bin_index_kernel_jit = jax.jit(bin_index_kernel)


# the sharded-call surface (pjit with batch-dim-sharded inputs) — the bin
# stage of the sharded ingest pipeline; pure per-row integer arithmetic,
# so sharding is trivially exact.  Host twin: the scalar oracle
# (oracle.binindex.closed_form_bin).
bin_index_kernel_mesh = mesh_pjit(bin_index_kernel_jit, ("one", "one"))
