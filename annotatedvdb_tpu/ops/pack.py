"""Single-fetch packing of the per-chunk device outputs.

Every host<->device materialization is a separate transfer with its own
fixed cost — six per-chunk ``np.asarray`` calls are six transfers.  The
insert path needs six small outputs per row (hash, duplicate flag, bin
level, leaf bin, needs-digest, host-fallback = 10 bytes); ``pack_outputs``
bitcasts and concatenates them into one ``[n, 10]`` uint8 buffer ON DEVICE
so the host fetches exactly once, and ``unpack_outputs`` slices the columns
back out with numpy views.

The reference has no analog — its per-row outputs ride individual Postgres
result sets (``variant_loader.py:479-486``); this is the transfer-layer
counterpart of batching those round trips.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from annotatedvdb_tpu.utils.profiling import startup_phase

#: packed row layout (little-endian byte order on both TPU and x86 hosts)
_H = slice(0, 4)          # uint32 allele hash
_LEAF = slice(4, 8)       # int32 leaf bin
_LEVEL = 8                # uint8 bin level
_FLAGS = 9                # bit0 dup, bit1 needs_digest, bit2 host_fallback
WIDTH = 10


@jax.named_scope("avdb.pack_outputs")
def pack_outputs(h, dup, bin_level, leaf_bin, needs_digest, host_fallback):
    """[n] device outputs -> [n, 10] uint8 (one transferable buffer)."""
    n = h.shape[0]
    h_b = lax.bitcast_convert_type(h.astype(jnp.uint32), jnp.uint8)
    leaf_b = lax.bitcast_convert_type(
        leaf_bin.astype(jnp.int32), jnp.uint8
    )
    level_b = bin_level.astype(jnp.uint8).reshape(n, 1)
    flags = (
        dup.astype(jnp.uint8)
        | (needs_digest.astype(jnp.uint8) << 1)
        | (host_fallback.astype(jnp.uint8) << 2)
    ).reshape(n, 1)
    return jnp.concatenate([h_b, leaf_b, level_b, flags], axis=1)


pack_outputs_jit = jax.jit(pack_outputs)


def pack_outputs_np(h, dup, bin_level, leaf_bin, needs_digest,
                    host_fallback):
    """Numpy twin of :func:`pack_outputs` (ops.TWINS): same [n, 10]
    little-endian byte layout from host arrays — the packer a breaker-
    tripped or deviceless path can run, round-tripping through
    :func:`unpack_outputs` exactly like the kernel output does (parity
    pinned by tests/test_twins.py)."""
    h = np.ascontiguousarray(np.asarray(h, "<u4"))
    leaf = np.ascontiguousarray(np.asarray(leaf_bin, "<i4"))
    n = h.shape[0]
    h_b = h.view(np.uint8).reshape(n, 4)
    leaf_b = leaf.view(np.uint8).reshape(n, 4)
    level_b = np.asarray(bin_level).astype(np.uint8).reshape(n, 1)
    flags = (
        np.asarray(dup).astype(np.uint8)
        | (np.asarray(needs_digest).astype(np.uint8) << 1)
        | (np.asarray(host_fallback).astype(np.uint8) << 2)
    ).reshape(n, 1)
    return np.concatenate([h_b, leaf_b, level_b, flags], axis=1)


# ---- nibble-packed allele uploads ------------------------------------
#
# The [n, width] ref/alt byte matrices are ~90% of the insert path's upload
# bytes.  Alleles are (almost) always drawn from a tiny alphabet, so the
# host packs two bases per byte and a jitted preamble inflates them back to
# the exact ASCII matrices on device — the annotate/hash/dedup kernels are
# unchanged.
# Chunks containing any out-of-alphabet byte (symbolic alleles, breakends)
# upload unpacked; correctness never depends on packing.

#: code 0 is the zero pad byte; 15 codes remain for the allele alphabet
_ALPHABET = b"ACGTNacgtn*.-"
_ENC = np.full(256, 255, np.uint8)
_ENC[0] = 0
for _i, _c in enumerate(_ALPHABET, start=1):
    _ENC[_c] = _i
_DEC = np.zeros(16, np.uint8)
for _i, _c in enumerate(_ALPHABET, start=1):
    _DEC[_i] = _c


def encode_alleles_nibble(ref: np.ndarray, alt: np.ndarray):
    """Host-side 4-bit pack of two [n, w] allele byte matrices.

    Returns ``(ref_packed, alt_packed)`` of shape [n, ceil(w/2)] — or None
    when any byte falls outside the packable alphabet (caller uploads the
    raw matrices instead)."""
    w = ref.shape[1]
    cols = (w + 1) // 2
    codes_r = _ENC[ref]
    codes_a = _ENC[alt]
    if (codes_r == 255).any() or (codes_a == 255).any():
        return None
    if w % 2:
        pad = ((0, 0), (0, 1))
        codes_r = np.pad(codes_r, pad)
        codes_a = np.pad(codes_a, pad)
    rp = codes_r[:, 0::2] | (codes_r[:, 1::2] << 4)
    ap = codes_a[:, 0::2] | (codes_a[:, 1::2] << 4)
    assert rp.shape[1] == cols
    return rp, ap


def _inflate_one(packed, width: int):
    n, cols = packed.shape
    lo = packed & jnp.uint8(0xF)
    hi = packed >> jnp.uint8(4)
    codes = jnp.stack([lo, hi], axis=2).reshape(n, 2 * cols)
    # numpy table, traced as a constant: a module-level jnp array would
    # initialize the backend at import, before an entry point can pin it
    return jnp.take(jnp.asarray(_DEC), codes, axis=0)[:, :width]


@jax.named_scope("avdb.inflate_alleles")
def inflate_alleles(ref_packed, alt_packed, width: int):
    """Device-side inverse of :func:`encode_alleles_nibble`."""
    return _inflate_one(ref_packed, width), _inflate_one(alt_packed, width)


inflate_alleles_jit = jax.jit(inflate_alleles, static_argnums=2)


def inflate_alleles_np(ref_packed, alt_packed, width: int):
    """Numpy twin of :func:`inflate_alleles` (ops.TWINS): the host-side
    inverse of :func:`encode_alleles_nibble`, byte-identical to the
    device inflate (parity pinned by tests/test_twins.py)."""
    def one(packed):
        packed = np.asarray(packed, np.uint8)
        n, cols = packed.shape
        lo = packed & np.uint8(0xF)
        hi = packed >> np.uint8(4)
        codes = np.stack([lo, hi], axis=2).reshape(n, 2 * cols)
        return _DEC[codes][:, :width]

    return one(ref_packed), one(alt_packed)

_TRANSPORT_WANTED: bool | None = None


def transport_wanted() -> bool:
    """Whether output packing / nibble uploads pay on this backend.

    The whole transport layer exists to batch host<->device round trips
    over a real interconnect; on the CPU backend ``device_put`` is a
    zero-copy no-op and per-field fetches are free, so the extra
    pack/inflate kernel passes are pure overhead (measurable at ~15% of
    end-to-end on a single-core host).  ``AVDB_PACK_TRANSPORT=always``
    forces packing on any backend (tests use it to exercise the packed
    path on CPU); ``=never`` disables it everywhere."""
    global _TRANSPORT_WANTED
    if _TRANSPORT_WANTED is None:
        import os

        mode = os.environ.get("AVDB_PACK_TRANSPORT", "auto")
        if mode == "always":
            _TRANSPORT_WANTED = True
        elif mode == "never":
            _TRANSPORT_WANTED = False
        else:
            _TRANSPORT_WANTED = jax.default_backend() != "cpu"
    return _TRANSPORT_WANTED


def transport_state() -> dict:
    """The three transport verdicts as they stand (None = never asked) —
    reported by load summaries; probes nothing."""
    return {
        "wanted": _TRANSPORT_WANTED,
        "outputs_verified": _TRANSPORT_OK,
        "nibble_verified": _NIBBLE_OK,
    }


_NIBBLE_OK: bool | None = None


def nibble_verified() -> bool:
    """One-time probe that encode->upload->inflate reproduces the exact
    byte matrices on this backend (same contract as
    :func:`transport_verified`; callers upload raw matrices when False)."""
    global _NIBBLE_OK
    if _NIBBLE_OK is None:
        probe = np.zeros((4, 7), np.uint8)  # odd width exercises the pad
        probe[0, :5] = np.frombuffer(b"ACGTN", np.uint8)
        probe[1, :3] = np.frombuffer(b"acg", np.uint8)
        probe[2, :7] = np.frombuffer(b"*.-TGCA", np.uint8)
        probe[3, :1] = np.frombuffer(b"G", np.uint8)
        enc = encode_alleles_nibble(probe, probe[::-1].copy())
        # a backend that cannot compile/run the tiny kernel raises here,
        # at first use — only a WRONG answer selects raw uploads
        with startup_phase("transport_probe"):
            r, a = inflate_alleles_jit(enc[0], enc[1], 7)
            _NIBBLE_OK = bool(
                (np.asarray(r) == probe).all()
                and (np.asarray(a) == probe[::-1]).all()
            )
    return _NIBBLE_OK


#: update-path row layout: uint32 hash, uint8 prefix_len, uint8 flags(bit0
#: host_fallback).  prefix_len <= allele width; callers must gate this pack
#: on width <= 255 (the uint8 lane truncates beyond that).
VEP_WIDTH = 6


@jax.named_scope("avdb.pack_vep_outputs")
def pack_vep_outputs(h, prefix_len, host_fallback):
    """[n] update-path device outputs -> [n, 6] uint8 (one fetch)."""
    n = h.shape[0]
    h_b = lax.bitcast_convert_type(h.astype(jnp.uint32), jnp.uint8)
    return jnp.concatenate(
        [
            h_b,
            prefix_len.astype(jnp.uint8).reshape(n, 1),
            host_fallback.astype(jnp.uint8).reshape(n, 1),
        ],
        axis=1,
    )


pack_vep_outputs_jit = jax.jit(pack_vep_outputs)


def pack_vep_outputs_np(h, prefix_len, host_fallback):
    """Numpy twin of :func:`pack_vep_outputs` (ops.TWINS): same [n, 6]
    little-endian layout (parity pinned by tests/test_twins.py)."""
    h = np.ascontiguousarray(np.asarray(h, "<u4"))
    n = h.shape[0]
    return np.concatenate(
        [
            h.view(np.uint8).reshape(n, 4),
            np.asarray(prefix_len).astype(np.uint8).reshape(n, 1),
            np.asarray(host_fallback).astype(np.uint8).reshape(n, 1),
        ],
        axis=1,
    )


def unpack_vep_outputs(packed: np.ndarray):
    packed = np.asarray(packed)
    return {
        "h": np.ascontiguousarray(packed[:, :4]).view(np.uint32).reshape(-1),
        "prefix_len": packed[:, 4].astype(np.int32),
        "host_fallback": packed[:, 5].astype(bool),
    }


_TRANSPORT_OK: bool | None = None


def transport_verified() -> bool:
    """One-time probe that the pack->fetch->unpack path is bit-exact on THIS
    backend/host pair (``bitcast_convert_type`` byte order is
    hardware-defined; ``unpack_outputs`` assumes little-endian views).
    Callers must fall back to per-field fetches when this returns False."""
    global _TRANSPORT_OK
    if _TRANSPORT_OK is None:
        h = np.array([0x01020304, 0xFFFFFFFF, 0, 0xDEADBEEF], np.uint32)
        leaf = np.array([-1, 2**31 - 1, -(2**31), 1234], np.int32)
        level = np.array([0, 13, 255, 7], np.int32)
        t = np.array([True, False, True, False])
        # like nibble_verified: an error raises at first use, only a
        # byte-order mismatch selects per-field fetches
        with startup_phase("transport_probe"):
            cols = unpack_outputs(
                np.asarray(pack_outputs_jit(h, t, level, leaf, ~t, t))
            )
        _TRANSPORT_OK = bool(
            (cols["h"] == h).all()
            and (cols["leaf_bin"] == leaf).all()
            and (cols["bin_level"] == (level & 0xFF)).all()
            and (cols["dup"] == t).all()
            and (cols["needs_digest"] == ~t).all()
            and (cols["host_fallback"] == t).all()
        )
    return _TRANSPORT_OK


def unpack_outputs(packed: np.ndarray):
    """[n, 10] uint8 (host) -> dict of numpy columns, zero extra copies
    beyond the contiguous slices."""
    packed = np.asarray(packed)
    h = np.ascontiguousarray(packed[:, _H]).view(np.uint32).reshape(-1)
    leaf = np.ascontiguousarray(packed[:, _LEAF]).view(np.int32).reshape(-1)
    flags = packed[:, _FLAGS]
    return {
        "h": h,
        "leaf_bin": leaf,
        "bin_level": packed[:, _LEVEL].astype(np.int32),
        "dup": (flags & 1).astype(bool),
        "needs_digest": ((flags >> 1) & 1).astype(bool),
        "host_fallback": ((flags >> 2) & 1).astype(bool),
    }
