"""``GET /export/stream``: one corpus batch over HTTP.

The serving twin of the bulk exporter: a client names a ``region`` slice
and a ``batch`` ordinal and gets back exactly what ``avdb export`` would
have packed for that slice — the same fixed-shape int32 token/feature
lanes, the same validity mask, the same per-slice sorted allele
dictionary, the same seeded disjoint-block emission order (seed ``S``
over ``N`` batches permutes identically here and in the corpus planner,
because both use :data:`~annotatedvdb_tpu.export.core.SHUFFLE_BLOCK`
windows of one ``random.Random(seed)``).  The payload builder lives here
— ``serve/aio.py`` calls :func:`stream_payload` (the ``/stats/region``
builder discipline), and the tests call it directly as the route's
oracle.

Packing rides the engine's device kernel behind its circuit breaker;
an open breaker (or a device failure, recorded) falls back to the
byte-identical numpy twin, so breaker state can never change response
bytes.  Slices are capped at :data:`STREAM_MAX_ROWS` rows — this is a
serving route under admission control, not the bulk exporter.
"""

from __future__ import annotations

import json
import random
from urllib.parse import parse_qs

import numpy as np

from annotatedvdb_tpu.export.core import (
    SHUFFLE_BLOCK,
    TOKENS_PER_ROW,
    _pad,
    pack_batch,
    parse_region,
)
from annotatedvdb_tpu.export.tokens import TOKEN_FIELDS

STREAM_ROUTE = "/export/stream"

#: hard per-request row cap: the route serves SLICES; whole-chromosome
#: pulls belong to ``avdb export``
STREAM_MAX_ROWS = 1 << 16

STREAM_DEFAULT_BATCH_ROWS = 256
STREAM_MAX_BATCH_ROWS = 4096

#: the one grammar message for a malformed query string
STREAM_QUERY_ERROR = (
    "export/stream query must be region=[chr]N:start-end with optional "
    "integer batch, batch_rows (8..4096), seed, and ordered=0|1"
)


def parse_stream_query(query: str) -> dict:
    """Validated params from the raw query string (``ValueError`` on any
    grammar violation — routes map it to the 400 above)."""
    try:
        q = parse_qs(query or "", keep_blank_values=False)
        region = q["region"][0]
        batch = int(q.get("batch", ["0"])[0])
        batch_rows = int(
            q.get("batch_rows", [str(STREAM_DEFAULT_BATCH_ROWS)])[0])
        seed = int(q.get("seed", ["0"])[0])
        ordered = q.get("ordered", ["0"])[0] not in ("0", "", "false")
    except (KeyError, ValueError, IndexError):
        raise ValueError(STREAM_QUERY_ERROR) from None
    if batch < 0 or not 8 <= batch_rows <= STREAM_MAX_BATCH_ROWS:
        raise ValueError(STREAM_QUERY_ERROR)
    code, start, end = parse_region(region)  # ValueError on bad grammar
    return {
        "code": code, "start": start, "end": end, "batch": batch,
        "batch_rows": batch_rows, "seed": seed, "ordered": ordered,
    }


def emission_order(n_batches: int, seed: int) -> list[int]:
    """Plan-order batch indices in emission order: the EXACT
    disjoint-block permutation the export spine's prefetcher applies
    (``random.Random(seed).shuffle`` per consecutive
    :data:`SHUFFLE_BLOCK`-batch window) — one definition of "seed S over
    N batches", replayable without a prefetch thread."""
    rng = random.Random(seed)
    out: list[int] = []
    for i in range(0, n_batches, SHUFFLE_BLOCK):
        block = list(range(i, min(i + SHUFFLE_BLOCK, n_batches)))
        if len(block) > 1:
            rng.shuffle(block)
        out.extend(block)
    return out


def stream_payload(engine, params: dict,
                   host_only: bool = False) -> tuple[str, int]:
    """``(rendered JSON body, n_valid)`` for one packed batch of the
    requested slice — serialization lives HERE, once, so the two front
    ends cannot drift a byte.

    Raises :class:`~annotatedvdb_tpu.serve.engine.QueryError` on semantic
    errors (unknown chromosome, over-cap slice, batch out of range) —
    routes map it to 400."""
    # imported here, not at module top: fsck/CLI consumers of the export
    # package must not pay for the accelerator runtime
    from annotatedvdb_tpu.ops.intervals import MAX_QUERY_POS
    from annotatedvdb_tpu.serve.engine import QueryError, segment_alleles
    from annotatedvdb_tpu.types import chromosome_label

    code = params["code"]
    label = chromosome_label(code)
    snap = engine.snapshots.current()
    index = engine._interval_index(snap, code)
    if index is None:
        raise QueryError(f"chromosome {label} not in store")
    lo = int(np.searchsorted(index.pos, params["start"], side="left"))
    hi = int(np.searchsorted(index.pos, params["end"], side="right"))
    n_rows = hi - lo
    if n_rows > STREAM_MAX_ROWS:
        raise QueryError(
            f"export/stream slice has {n_rows} rows (cap "
            f"{STREAM_MAX_ROWS}); narrow the region or use `avdb export`"
        )
    B = params["batch_rows"]
    n_batches = (n_rows + B - 1) // B
    if params["batch"] >= max(n_batches, 1):
        raise QueryError(
            f"batch {params['batch']} out of range: slice has "
            f"{n_batches} batch(es) of {B} rows"
        )
    feats = engine._stats_features(snap, code, index)
    shard = snap.store.shards.get(code)
    # slice-local allele dictionary: rendered through the SAME
    # segment_alleles definition as the JSON render path and the bulk
    # exporter, sorted, shipped in this response
    refs = np.empty(n_rows, object)
    alts = np.empty(n_rows, object)
    ref_len = np.zeros(n_rows, np.int32)
    si, jj = index.si[lo:hi], index.jj[lo:hi]
    for k in range(n_rows):
        seg = shard.segments[int(si[k])]
        j = int(jj[k])
        refs[k], alts[k] = segment_alleles(seg, j, shard.width)
        ref_len[k] = int(seg.cols["ref_len"][j])
    alleles = sorted(set(refs.tolist()) | set(alts.tolist()))
    lut = {s: i for i, s in enumerate(alleles)}
    ref_code = np.fromiter((lut[s] for s in refs.tolist()), np.int32,
                           n_rows)
    alt_code = np.fromiter((lut[s] for s in alts.tolist()), np.int32,
                           n_rows)
    pos = index.pos[lo:hi]
    end_col = np.minimum(
        pos.astype(np.int64) + ref_len - 1, MAX_QUERY_POS
    ).astype(np.int32)
    # emission slot -> plan-order batch (ordered mode is the identity)
    seq = params["batch"] if params["ordered"] or n_batches == 0 else \
        emission_order(n_batches, params["seed"])[params["batch"]]
    off = seq * B
    n = max(0, min(B, n_rows - off))
    sl = slice(off, off + n)
    chunk = {
        "code": code, "n_valid": n,
        "pos": _pad(pos, sl, n, B, 1),
        "end": _pad(end_col, sl, n, B, 1),
        "ref_code": _pad(ref_code, sl, n, B, -1),
        "alt_code": _pad(alt_code, sl, n, B, -1),
        "af_fp": _pad(feats.af_fp[lo:hi], sl, n, B, -1),
        "cadd_fp": _pad(feats.cadd_fp[lo:hi], sl, n, B, -1),
        "rank_i": _pad(feats.rank_i[lo:hi], sl, n, B, -1),
    }
    packed = _pack_breakered(engine, code, chunk, host_only)
    doc = {
        "region": f"{label}:{params['start']}-{params['end']}",
        "chromosome": label,
        "generation": snap.generation,
        "batch_rows": B,
        "seed": params["seed"],
        "ordered": params["ordered"],
        "rows": n_rows,
        "n_batches": n_batches,
        "batch": params["batch"],
        "seq": seq,
        "n_valid": n,
        "token_fields": list(TOKEN_FIELDS),
        "tokens_per_row": TOKENS_PER_ROW,
        "missing": -1,
        "alleles": alleles,
        "arrays": {
            "mask": packed["mask"].tolist(),
            "bin_level": packed["bin_level"].tolist(),
            "leaf_bin": packed["leaf_bin"].tolist(),
            "pos": packed["pos"].tolist(),
            "ref_code": packed["ref_code"].tolist(),
            "alt_code": packed["alt_code"].tolist(),
            "af_fp": packed["af_fp"].tolist(),
            "cadd_fp": packed["cadd_fp"].tolist(),
            "rank_i": packed["rank_i"].tolist(),
            "bin_index": packed["bin_index"].tolist(),
        },
    }
    return json.dumps(doc), n


def _pack_breakered(engine, code: int, chunk: dict, host_only: bool):
    """The pack call behind the engine's device circuit breaker (the
    ``_launch_group`` discipline): an open group — or a device failure,
    which the breaker records — pins this batch to the numpy twin.
    Either way the bytes are identical; only placement changes."""
    breaker = getattr(engine, "breaker", None)
    if host_only or (breaker is not None
                     and not breaker.allow_device(code)):
        return pack_batch(chunk, host_only=True)
    try:
        packed = pack_batch(chunk)
    except Exception as exc:
        if breaker is None:
            raise
        breaker.record_failure(code, exc)
        return pack_batch(chunk, host_only=True)
    if breaker is not None:
        breaker.record_success(code)
    return packed
