"""The main path's kernels, compiled for a described v5e at the shapes
``chip_smoke.py`` runs them at — what the chip's compiler refuses (a slice
off the tiling, too much VMEM, a program that does not fit HBM) fails here,
without a chip.  A compile that passes is not a chip run: it says nothing
about results or times.

The topology is described inside a module-scoped fixture and every compile
happens in the test's own process: one process at a time may load the TPU
library, so nothing here may run at import or collection time, and all the
cases live in this one file (xdist hands a file to one worker)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from annotatedvdb_tpu.types import DEFAULT_ALLELE_WIDTH
from annotatedvdb_tpu.utils.arrays import next_pow2

#: the loader's padded batch: next_pow2(--commitAfter default)
BATCH = next_pow2(1 << 16)
WIDTH = DEFAULT_ALLELE_WIDTH
#: chip_smoke sizes: one compacted segment holds at most all 2,097,152
#: loaded rows; a chromosome's interval index pads to 2**20 rows
SEGMENT_ROWS = 1 << 21
BULK_QUERIES = 1 << 12
INDEX_ROWS = 1 << 20
PANEL_QUERIES = 128
STATS_QUERIES = 32
EXPORT_BATCH = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shape builder on the first described chip.  The persistent cache is
    off while this module compiles: an entry written for a described
    device cannot be read back without one and would only warn."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    yield shape
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _identity(shape, n, width=WIDTH):
    """(pos, h, ref, alt, ref_len, alt_len) shapes of ``n`` rows."""
    return (
        shape((n,), jnp.int32), shape((n,), jnp.uint32),
        shape((n, width), jnp.uint8), shape((n, width), jnp.uint8),
        shape((n,), jnp.int32), shape((n,), jnp.int32),
    )


@pytest.mark.parametrize("width", [8, 16, 32, WIDTH])
def test_annotate_pallas_compiles(one_chip, width):
    from annotatedvdb_tpu.ops.annotate_pallas import annotate_bin_pallas

    pos, _h, ref, alt, ref_len, alt_len = _identity(one_chip, BATCH, width)
    compiled = annotate_bin_pallas.lower(
        pos, ref, alt, ref_len, alt_len
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _hash(shape):
    from annotatedvdb_tpu.ops.hashing import allele_hash_jit

    _pos, _h, ref, alt, ref_len, alt_len = _identity(shape, BATCH)
    return allele_hash_jit, (ref, alt, ref_len, alt_len)


def _dedup(shape):
    from annotatedvdb_tpu.ops.dedup import mark_batch_duplicates_jit

    return mark_batch_duplicates_jit, _identity(shape, BATCH)


def _pack_outputs(shape):
    from annotatedvdb_tpu.ops.pack import pack_outputs_jit

    n = (BATCH,)
    return pack_outputs_jit, (
        shape(n, jnp.uint32), shape(n, jnp.bool_), shape(n, jnp.int8),
        shape(n, jnp.int32), shape(n, jnp.bool_), shape(n, jnp.bool_),
    )


def _nibble_inflate(shape):
    from annotatedvdb_tpu.ops.pack import inflate_alleles_jit

    packed = shape((BATCH, (WIDTH + 1) // 2), jnp.uint8)
    return inflate_alleles_jit, (packed, packed, WIDTH)


def _store_probe(shape):
    from annotatedvdb_tpu.ops.dedup import lookup_in_sorted_jit

    return lookup_in_sorted_jit, (
        *_identity(shape, SEGMENT_ROWS), *_identity(shape, BULK_QUERIES)
    )


def _store_probe_packed(shape):
    """The store's own probe: the same segment, the queries one packed
    buffer (``ops/dedup.pack_queries``: 16 + 2 x width bytes a query)."""
    from annotatedvdb_tpu.ops.dedup import lookup_in_sorted_packed_jit

    return lookup_in_sorted_packed_jit, (
        *_identity(shape, SEGMENT_ROWS),
        shape((BULK_QUERIES * (16 + 2 * WIDTH),), jnp.uint8),
    )


def _bits_spans(shape):
    from annotatedvdb_tpu.ops.intervals import bits_spans_kernel_jit

    q = shape((PANEL_QUERIES,), jnp.int32)
    return bits_spans_kernel_jit, (shape((INDEX_ROWS,), jnp.int32), q, q)


def _stats_panel(shape):
    from annotatedvdb_tpu.ops.stats import stats_panel_kernel_jit

    col = shape((INDEX_ROWS,), jnp.int32)
    q = shape((STATS_QUERIES,), jnp.int32)
    return stats_panel_kernel_jit, (col, col, col, col, q, q)


def _export_pack(shape):
    from annotatedvdb_tpu.ops.export_pack import export_pack_kernel_jit

    col = shape((EXPORT_BATCH,), jnp.int32)
    return export_pack_kernel_jit, (*([col] * 7), shape((), jnp.int32))


@pytest.mark.parametrize("case", [
    _hash, _dedup, _pack_outputs, _nibble_inflate, _store_probe,
    _bits_spans, _stats_panel, _export_pack, _store_probe_packed,
], ids=lambda case: case.__name__.lstrip("_"))
def test_kernel_compiles(one_chip, case):
    kernel, args = case(one_chip)
    compiled = kernel.lower(*args).compile()
    # the device's 16 GB must hold the program's own buffers
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert need < 16e9, f"{case.__name__}: {need} bytes on one chip"
