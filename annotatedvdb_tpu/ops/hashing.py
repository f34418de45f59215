"""Vectorized allele-identity hashing.

The reference's variant identity is the metaseq string ``chr:pos:ref:alt``
(``variant_annotator.py:124-126``), compared via SQL lookups.  On device the
identity is (chrom, pos, allele hash): a 32-bit FNV-1a over
(ref_len, alt_len, ref bytes, alt bytes).  The hash is used only to order and
bucket rows — every hash match is confirmed with a full byte compare
(``ops/dedup.py``), so collisions cost a false candidate, never a wrong
answer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from annotatedvdb_tpu.parallel.mesh import mesh_pjit

# numpy scalars, NOT jnp: a module-level jnp constant initializes the JAX
# backend at import time, before entry points can pin the platform (and
# takes the chip in a process that only meant to import the module)
FNV_OFFSET = np.uint32(2166136261)
FNV_PRIME = np.uint32(16777619)


def _fnv_step(h, byte):
    return (h ^ byte.astype(jnp.uint32)) * FNV_PRIME


@jax.named_scope("avdb.hash")
def allele_hash(ref, alt, ref_len, alt_len):
    """[N] uint32 hash of the allele identity (lengths + padded byte content).

    Pad bytes are zeros and lengths are hashed first, so e.g. ref 'AA'/alt 'A'
    and ref 'A'/alt 'AA' hash differently even though their padded
    concatenations match."""
    h = jnp.full(ref.shape[:1], FNV_OFFSET, jnp.uint32)
    h = _fnv_step(h, ref_len.astype(jnp.uint32) & 0xFF)
    h = _fnv_step(h, alt_len.astype(jnp.uint32) & 0xFF)
    for i in range(ref.shape[1]):
        h = _fnv_step(h, ref[:, i])
    for i in range(alt.shape[1]):
        h = _fnv_step(h, alt[:, i])
    return h


allele_hash_jit = jax.jit(allele_hash)


# the sharded-call surface (pjit with batch-dim-sharded inputs); pad rows
# hash to garbage that is sliced away.  Host twin: allele_hash_np.
allele_hash_mesh = mesh_pjit(
    allele_hash_jit, ("zero", "zero", "one", "one")
)


def allele_hash_np(ref, alt, ref_len, alt_len) -> np.ndarray:
    """Bit-exact numpy twin of :func:`allele_hash`.

    Where the measured upload rate is low (see
    ``store.variant_store._transfer_fast``) the update loaders hash on
    host: the device round trip costs more than the FNV loop saves.  Parity with the jitted kernel is pinned by
    ``tests/test_pack.py`` — store membership compares these hashes against
    device-computed ones, so they must never diverge."""
    ref = np.asarray(ref, np.uint8)
    alt = np.asarray(alt, np.uint8)
    h = np.full(ref.shape[0], FNV_OFFSET, np.uint32)
    prime = FNV_PRIME

    def step(h, byte):
        return (h ^ byte.astype(np.uint32)) * prime

    h = step(h, np.asarray(ref_len).astype(np.uint32) & 0xFF)
    h = step(h, np.asarray(alt_len).astype(np.uint32) & 0xFF)
    for i in range(ref.shape[1]):
        h = step(h, ref[:, i])
    for i in range(alt.shape[1]):
        h = step(h, alt[:, i])
    return h
