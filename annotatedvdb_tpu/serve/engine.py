"""Query engine: point, bulk, and region reads over a pinned store snapshot.

The read-side twin of the loaders.  The reference serves these queries from
Postgres — point lookups by ``record_primary_key``, range scans through the
hierarchical bin index (``find_bin_index`` + the ``bin_index`` ltree column)
— and this engine answers the same three shapes against the TPU-native
columnar store:

- **point**: ``chr:pos:ref:alt`` resolves through the SAME identity rule
  the loaders use (``loaders.lookup.identity_columns``: FNV over the
  width-bounded allele bytes, host-string override for over-width rows),
  then one sorted-merge probe per shard (``ChromosomeShard.lookup``);
- **bulk**: many thousands of ids per call, grouped per chromosome and
  probed as ONE vectorized batch — which rides the existing device probe
  path (HBM segment cache + ``ops/dedup.lookup_in_sorted``) exactly where
  a loader's membership check would;
- **region**: ``chr:start-end`` computes the enclosing hierarchical bin via
  the closed-form device kernel (``ops.binindex.bin_index_kernel``), then
  slices each sorted segment by position (rows sort by ``(pos, hash)``, so
  ``pos`` is directly ``searchsorted``-able per segment) — the BITS-style
  vectorized interval intersection, no tree walk, no per-row compare.
  Results dedup first-wins across segments (the store's duplicate policy)
  and support the two annotation filters clients actually page on:
  minimum CADD phred and ADSP consequence-rank cutoff.

Records render as JSON **text** through the same codec the egress path uses
(``store.variant_store.jsonb_dumps``): a ``RawJson`` annotation splices its
stored text verbatim — zero parse/re-serialize on the hot read path — and
rendering never mutates the snapshot (unlike ``get_ann``, which
materializes parsed trees back into the column).  ``_render_row`` is the
scalar definition (rows that keep host strings, a lone row); a point/bulk
lookup renders a chromosome group's cache misses in one columnar pass
(``render_rows``), and a region answer renders its located rows the same
way a block at a time (``render_located``): byte for byte the same text.

Rendered region responses sit in a small LRU keyed by store generation
(``AVDB_SERVE_REGION_CACHE``), so a hot region costs one dict probe until
the next loader commit swaps the generation and naturally invalidates it.

**Batched interval intersection (BITS).**  Region reads — single AND
batched — resolve through an :class:`IntervalIndex`: one position-sorted,
first-wins-deduplicated ``(pos, segment, row)`` view per chromosome group,
against which every query interval is two sorted-endpoint binary searches
(``ops/intervals``: the BITS kernel, arXiv 1208.3407).  A generation's
view is two parts (:class:`RegionParts`): the base index of the segments
the store holds, kept with its device copy across the generations live
inserts make, and a small host delta of everything newer (memtable
overlay segments), merged per read — byte-identical to one whole index.  :meth:`QueryEngine.regions_serve` answers thousands of
intervals in ONE device call per touched chromosome group — per-interval
envelopes byte-identical to N sequential :meth:`QueryEngine.region`
calls, a count-only mode that never materializes rows (a span width IS
the post-dedup count), and an interval-tokenization output (per-interval
bin token + row-id span, fixed-width arrays) for ML consumers.  The
device circuit breaker and ``host_only=True`` route the searches to a
byte-identical numpy twin.
"""

from __future__ import annotations

import base64
import contextlib
import functools
import itertools
import json
import os
import re
import threading
import time
from collections import OrderedDict

import numpy as np

from annotatedvdb_tpu.loaders.lookup import identity_columns
from annotatedvdb_tpu.obs import reqtrace
from annotatedvdb_tpu.utils.profiling import annotation
from annotatedvdb_tpu.ops import intervals as interval_ops
from annotatedvdb_tpu.ops import stats as stats_ops
from annotatedvdb_tpu.ops.binindex import bin_index_kernel_jit
from annotatedvdb_tpu.export.tokens import bin_path as _bin_path
from annotatedvdb_tpu.export.tokens import build_region_tokens
from annotatedvdb_tpu.oracle.binindex import closed_form_path
from annotatedvdb_tpu.store.variant_store import (
    _DIGEST_PK,
    _LONG_ALLELES,
    JSONB_COLUMNS,
    combined_key,
    count_overlapped,
    jsonb_dumps,
)
from annotatedvdb_tpu.types import (
    chromosome_code,
    chromosome_label,
    decode_allele,
)
from annotatedvdb_tpu.utils import faults
from annotatedvdb_tpu.utils.locks import make_lock


class QueryError(ValueError):
    """Malformed query (grammar / unknown chromosome / bad range) — the
    client's fault; HTTP maps it to 400, never 500."""


_ALLELE_RE = re.compile(r"^[ACGTUNacgtun]+$")

#: region span cap: one level-0 bin side (64Mb) covers any chromosome arm;
#: anything wider is a scan, not a region query, and must page.
MAX_REGION_SPAN = 64_000_000


def _cursor_key(code, start, end, min_cadd, max_conseq_rank) -> int:
    """FNV-1a fingerprint binding a continuation token to ONE query shape —
    a token replayed against different bounds/filters is a client error,
    not a silent wrong page."""
    h = 2166136261
    for ch in f"{code}:{start}:{end}:{min_cadd}:{max_conseq_rank}".encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h


def encode_cursor(generation: int, offset: int, key: int) -> str:
    """Opaque continuation token: urlsafe base64 of a compact JSON triple
    (generation, row offset, query fingerprint).  Opaque by contract —
    clients must round-trip it verbatim."""
    raw = json.dumps(
        {"g": generation, "o": offset, "k": key}, separators=(",", ":")
    ).encode()
    return base64.urlsafe_b64encode(raw).decode().rstrip("=")


def decode_cursor(token: str, key: int) -> int:
    """Token -> row offset.  ``""``/``"0"`` start the first page; anything
    else must be a token this query shape minted.  A token from an OLDER
    generation stays valid: the offset re-applies against the current
    generation's match list (best-effort continuation across commits, the
    same contract a Postgres keyset page would give)."""
    if token in ("", "0"):
        return 0
    try:
        raw = base64.urlsafe_b64decode(token + "=" * (-len(token) % 4))
        obj = json.loads(raw)
        offset = int(obj["o"])
        k = int(obj["k"])
        int(obj["g"])  # well-formedness only: ANY generation is accepted
    except (ValueError, KeyError, TypeError):
        raise QueryError(f"bad continuation cursor {token!r}") from None
    if k != key:
        raise QueryError(
            "continuation cursor does not belong to this region query "
            "(region or filters changed mid-page)"
        )
    if offset < 0:
        raise QueryError(f"bad continuation cursor {token!r}")
    return offset


def parse_variant_id(spec: str) -> tuple[int, int, str, str]:
    """``chr:pos:ref:alt`` -> (chrom code, pos, REF, ALT).

    Accepts a ``chr`` prefix and tolerates a trailing ``:rs<N>`` field (the
    store's own primary keys round-trip as queries).  Alleles are uppercased
    — the store encodes uppercase bytes."""
    parts = spec.split(":")
    if len(parts) == 5 and parts[4].startswith("rs"):
        parts = parts[:4]
    if len(parts) != 4:
        raise QueryError(
            f"bad variant id {spec!r}: expected chr:pos:ref:alt"
        )
    code = chromosome_code(parts[0])
    if code == 0:
        raise QueryError(f"bad variant id {spec!r}: unknown chromosome")
    try:
        pos = int(parts[1])
    except ValueError:
        raise QueryError(
            f"bad variant id {spec!r}: position is not an integer"
        ) from None
    if pos < 1:
        raise QueryError(f"bad variant id {spec!r}: position is 1-based")
    ref, alt = parts[2].upper(), parts[3].upper()
    if not _ALLELE_RE.match(ref) or not _ALLELE_RE.match(alt):
        raise QueryError(f"bad variant id {spec!r}: non-nucleotide allele")
    return code, pos, ref, alt


def parse_region(spec: str) -> tuple[int, int, int]:
    """``chr:start-end`` -> (chrom code, start, end), 1-based inclusive."""
    chrom, sep, rng = spec.partition(":")
    start_s, dash, end_s = rng.partition("-")
    if not sep or not dash:
        raise QueryError(f"bad region {spec!r}: expected chr:start-end")
    code = chromosome_code(chrom)
    if code == 0:
        raise QueryError(f"bad region {spec!r}: unknown chromosome")
    try:
        start, end = int(start_s), int(end_s)
    except ValueError:
        raise QueryError(f"bad region {spec!r}: bounds must be integers") \
            from None
    if start < 1 or end < start:
        raise QueryError(
            f"bad region {spec!r}: need 1 <= start <= end"
        )
    if end - start + 1 > MAX_REGION_SPAN:
        raise QueryError(
            f"bad region {spec!r}: span exceeds {MAX_REGION_SPAN} bp — "
            "page the query"
        )
    return code, start, end


@functools.lru_cache(maxsize=4096)
def _region_bin(start: int, end: int) -> tuple[int, int]:
    """(level, leaf_bin) of the deepest bin enclosing [start, end] — the
    closed-form device kernel, batched [1] and memoized (hot regions skip
    the dispatch; the LRU also absorbs the one-time trace cost).  The test
    suite cross-checks this answer against the scalar host oracle
    (``oracle.binindex.closed_form_bin``) per region query.  The kernel
    import lives at module top: this function runs once per region
    REQUEST (cache miss), and a per-call import-machinery lookup is
    measurable at serving QPS.  Bounds clamp below the int32 position
    sentinel EXACTLY like the batched span paths (``_clamped_queries``):
    no store position can reach the clamp, the int32 cast can never
    overflow on an absurd-but-grammatical bound, and the single and
    batch routes stay byte-identical on such specs."""
    start = min(int(start), interval_ops.MAX_QUERY_POS)
    end = min(int(end), interval_ops.MAX_QUERY_POS)
    level, leaf = bin_index_kernel_jit(
        np.asarray([start], np.int32), np.asarray([end], np.int32)
    )
    return int(level[0]), int(leaf[0])


def segment_alleles(seg, j: int, width: int) -> tuple[str, str]:
    """(ref, alt) strings for one segment row: retained original strings
    for the over-width tail, decoded device bytes otherwise (the scalar
    definition ``shard.alleles`` pins).  Single source for every renderer
    — ``_render_row`` here and the export dictionary coder both call it,
    so a corpus decode can never diverge from the serving JSON."""
    la = seg.obj[_LONG_ALLELES]
    if la is not None and la[j] is not None:
        ref, alt = la[j]
        return ref, alt
    ref_len = int(seg.cols["ref_len"][j])
    alt_len = int(seg.cols["alt_len"][j])
    if ref_len > width or alt_len > width:
        raise ValueError(
            f"allele length {max(ref_len, alt_len)} exceeds store "
            f"width {width} with no retained strings (store predates "
            "long-allele retention; reload from source)"
        )
    return decode_allele(seg.ref[j], ref_len), decode_allele(seg.alt[j], alt_len)


def render_variant(shard, code: int, gid: int) -> str:
    """One store row (by global id) as JSON text."""
    seg, j = shard.locate_row(gid)
    return _render_row(seg, j, chromosome_label(code), shard.width)


def _render_row(seg, j: int, label: str, width: int) -> str:
    """One segment row as JSON text (fixed field order; annotation values
    splice through ``jsonb_dumps`` — raw-text columns copy verbatim).  The
    scalar definition: the columnar pass (:func:`render_rows`,
    :func:`render_located`) is held to these bytes, and hands this
    function the rows it does not assemble.
    Identity strings are assembled without ``json.dumps``: alleles, labels,
    and PKs are [A-Za-z0-9:._-] by construction, nothing to escape."""
    ref, alt = segment_alleles(seg, j, width)
    pos = int(seg.cols["pos"][j])
    rs = int(seg.cols["ref_snp"][j])
    adsp = int(seg.cols["is_adsp_variant"][j])
    rs_suffix = f":rs{rs}" if rs >= 0 else ""
    # record PK: retained digest for the long-allele tail, else the literal
    # (primary_key_generator.py:99-122 semantics, same as shard.primary_key)
    dp = seg.obj[_DIGEST_PK]
    if dp is not None and dp[j] is not None:
        pk = dp[j]
    else:
        pk = f"{label}:{pos}:{ref}:{alt}{rs_suffix}"
    bin_path = _bin_path(
        label, int(seg.cols["bin_level"][j]), int(seg.cols["leaf_bin"][j])
    )
    parts = [
        f'"primary_key":"{pk}"',
        f'"metaseq_id":"{label}:{pos}:{ref}:{alt}"',
        f'"chromosome":"{label}"',
        f'"position":{pos}',
        f'"ref":"{ref}"',
        f'"alt":"{alt}"',
        '"ref_snp":' + (f'"rs{rs}"' if rs >= 0 else "null"),
        '"is_multi_allelic":'
        + ("true" if seg.cols["is_multi_allelic"][j] else "false"),
        '"is_adsp_variant":'
        + ("null" if adsp < 0 else ("true" if adsp else "false")),
        f'"bin_index":{json.dumps(bin_path)}',
    ]
    ann = []
    for c in JSONB_COLUMNS:
        col = seg.obj[c]
        if col is None:
            continue
        v = col[j]
        if v is not None:
            ann.append(f'"{c}":{jsonb_dumps(v)}')
    parts.append('"annotations":{' + ",".join(ann) + "}")
    return "{" + ",".join(parts) + "}"


def decode_allele_rows(matrix: np.ndarray, lengths) -> list:
    """Allele strings of a gathered ``[k, width]`` byte matrix: ONE ascii
    decode of the whole block, then one slice per row by its stored length
    (capped at the width) — ``decode_allele(row, length)`` for every row,
    never the ``S<width>`` view's trailing-NUL rule (which is what
    ``io.egress.decode_alleles`` applies to whole loader batches)."""
    k, width = matrix.shape
    text = matrix.tobytes().decode("ascii")
    return [
        text[o:o + n]
        for o, n in zip(range(0, k * width, width),
                        np.minimum(lengths, width).tolist())
    ]


def render_rows(shard, code: int, gids, clock=None) -> list:
    """Many store rows (by global id, any order, repeats allowed) as JSON
    text, in ``gids`` order — byte for byte ``[render_variant(shard, code,
    g) for g in gids]``, which stays the scalar definition.

    One columnar pass per touched segment: one vectorised locate for the
    call, each numeric column gathered once by fancy index, alleles decoded
    in bulk (:func:`decode_allele_rows`), each present annotation column
    taken once, the quoted ``bin_index`` built once per distinct (level,
    leaf), one format per row.  A row that keeps host strings — retained long
    alleles, a digest PK — or whose stored length exceeds the width goes
    through :func:`_render_row` (the retained strings first, else its
    ``ValueError``).  ``clock`` (a :class:`_LookupClock`) is told how many
    rows took each route, once per segment."""
    gids = np.asarray(gids, np.int64)
    n = int(gids.shape[0])
    if n == 0:
        return []
    if n == 1:
        # nothing to amortise: eight one-element gathers cost over twice
        # the scalar renderer (PERF.md section 6, PR 27)
        if clock is not None:
            clock.scalar_rows += 1
        return [render_variant(shard, code, int(gids[0]))]
    label = chromosome_label(code)
    width = shard.width
    si, off = shard.locate_rows(gids)
    first = int(si[0])
    if bool((si == first).all()):
        return _render_segment_rows(
            shard.segments[first], off, label, width, clock
        )
    out: list = [None] * n
    for s in np.unique(si).tolist():
        at = np.flatnonzero(si == s)
        texts = _render_segment_rows(
            shard.segments[s], off[at], label, width, clock
        )
        for k, text in zip(at.tolist(), texts):
            out[k] = text
    return out


def _render_segment_rows(seg, j: np.ndarray, label: str, width: int,
                         clock) -> list:
    """Rows ``j`` (local offsets, any order) of one segment as JSON text:
    :func:`_render_row`'s bytes, assembled column by column."""
    cols, obj = seg.cols, seg.obj
    ref_len = cols["ref_len"][j]
    alt_len = cols["alt_len"][j]
    # rows the columnar pass does not assemble: retained host strings
    # (long alleles, digest PK) and lengths the device bytes cannot hold
    scalar = (ref_len > width) | (alt_len > width)
    for name in (_LONG_ALLELES, _DIGEST_PK):
        col = obj[name]
        if col is not None:
            scalar |= np.fromiter(
                (v is not None for v in col[j].tolist()), np.bool_,
                count=j.shape[0],
            )
    n_scalar = int(np.count_nonzero(scalar))
    if clock is not None:
        clock.batch_rows += j.shape[0] - n_scalar
        clock.scalar_rows += n_scalar
    if n_scalar:
        out: list = [None] * j.shape[0]
        for k in np.flatnonzero(scalar).tolist():
            out[k] = _render_row(seg, int(j[k]), label, width)
        keep = np.flatnonzero(~scalar)
        if keep.shape[0]:
            texts = _render_columnar(
                seg, j[keep], ref_len[keep], alt_len[keep], label
            )
            for k, text in zip(keep.tolist(), texts):
                out[k] = text
        return out
    return _render_columnar(seg, j, ref_len, alt_len, label)


def _render_columnar(seg, j: np.ndarray, ref_len: np.ndarray,
                     alt_len: np.ndarray, label: str) -> list:
    """The columnar pass proper: every row of ``j`` has device-width
    alleles and no retained host string."""
    cols = seg.cols
    refs = decode_allele_rows(seg.ref[j], ref_len)
    alts = decode_allele_rows(seg.alt[j], alt_len)
    # quoted bin_index once per distinct (level, leaf) of the call
    bins = list(zip(cols["bin_level"][j].tolist(),
                    cols["leaf_bin"][j].tolist()))
    quoted = {
        key: json.dumps(_bin_path(label, *key)) for key in set(bins)
    }
    # annotations: each present column taken once, JSONB_COLUMNS order
    ann = None
    for c in JSONB_COLUMNS:
        col = seg.obj[c]
        if col is None:
            continue
        if ann is None:
            ann = [""] * j.shape[0]
        for k, v in enumerate(col[j].tolist()):
            if v is not None:
                field = f'"{c}":{jsonb_dumps(v)}'
                ann[k] = f"{ann[k]},{field}" if ann[k] else field
    if ann is None:
        ann = itertools.repeat("")
    out = []
    for pos, ref, alt, rs, multi, adsp, bin_index, fields in zip(
        cols["pos"][j].tolist(), refs, alts, cols["ref_snp"][j].tolist(),
        cols["is_multi_allelic"][j].tolist(),
        cols["is_adsp_variant"][j].tolist(), map(quoted.get, bins), ann,
    ):
        metaseq = f"{label}:{pos}:{ref}:{alt}"
        if rs >= 0:
            pk, ref_snp = f"{metaseq}:rs{rs}", f'"rs{rs}"'
        else:
            pk, ref_snp = metaseq, "null"
        out.append(
            f'{{"primary_key":"{pk}","metaseq_id":"{metaseq}"'
            f',"chromosome":"{label}","position":{pos}'
            f',"ref":"{ref}","alt":"{alt}","ref_snp":{ref_snp}'
            f',"is_multi_allelic":{"true" if multi else "false"}'
            ',"is_adsp_variant":'
            f'{"null" if adsp < 0 else ("true" if adsp else "false")}'
            f',"bin_index":{bin_index},"annotations":{{{fields}}}}}'
        )
    return out


#: a region answer that shows no row: (segment index, local row) arrays
_NO_ROWS = (np.empty(0, np.int32), np.empty(0, np.int64))

#: no index position selected
_NO_SEL = np.empty(0, np.int64)

#: rows a region answer hands the columnar pass at a time.  A panel's per-row
#: cost is flat from ~250 to ~1,000 rows a call (PERF.md section 6, PR 33);
#: a block is also what a streamed body holds rendered, so not larger.
REGION_RENDER_BLOCK = 512


def render_located(runs, clock=None) -> list:
    """Rows already located — ``runs`` of ``(shard, label, si, jj)``, the
    segment index and local row of each as arrays (a region page's, or
    several pages') — as JSON text, flat, in run-then-row order: byte for
    byte ``_render_row`` of each.

    :func:`render_rows`' pass without its locate: the rows are grouped by
    what they are, (chromosome shard, segment), whatever order the runs
    came in, and each group is one :func:`_render_segment_rows` call
    (which sends a row that keeps host strings, or an over-width length,
    through :func:`_render_row`).  One row in all has nothing to amortise
    and is rendered by ``_render_row``.  ``clock`` (a :class:`_PanelClock`)
    is told how many rows took each route, once per group."""
    runs = [run for run in runs if run[3].shape[0]]
    n = sum(run[3].shape[0] for run in runs)
    if n == 0:
        return []
    if n == 1:
        shard, label, si, jj = runs[0]
        if clock is not None:
            clock.scalar_rows += 1
        return [_render_row(shard.segments[int(si[0])], int(jj[0]), label,
                            shard.width)]
    by_shard: dict = {}
    for shard, label, si, jj in runs:
        group = by_shard.setdefault(id(shard), (shard, label, [], []))
        group[2].append(si)
        group[3].append(jj)
    # each shard's rows in one pass, its texts in the order its runs came
    texts = {
        key: _render_shard_rows(
            shard, label, np.concatenate(si), np.concatenate(jj), clock
        )
        for key, (shard, label, si, jj) in by_shard.items()
    }
    if len(texts) == 1:
        return texts.popitem()[1]
    # deal them back: a run's rows are the next of its shard's texts
    out: list = []
    taken = dict.fromkeys(texts, 0)
    for shard, _label, _si, jj in runs:
        lo = taken[id(shard)]
        taken[id(shard)] = hi = lo + jj.shape[0]
        out += texts[id(shard)][lo:hi]
    return out


def _render_shard_rows(shard, label: str, si: np.ndarray, jj: np.ndarray,
                       clock) -> list:
    """Rows ``(si, jj)`` of one shard as JSON text, in the order given: one
    :func:`_render_segment_rows` a touched segment (what
    :func:`render_rows` does once it has located its ids)."""
    first = int(si[0])
    if bool((si == first).all()):
        return _render_segment_rows(
            shard.segments[first], jj, label, shard.width, clock
        )
    out: list = [None] * jj.shape[0]
    for s in np.unique(si).tolist():
        at = np.flatnonzero(si == s)
        texts = _render_segment_rows(
            shard.segments[s], jj[at], label, shard.width, clock
        )
        for k, text in zip(at.tolist(), texts):
            out[k] = text
    return out


def _ann_number(seg, j: int, column: str, field: str):
    """Numeric ``field`` of row j's ``column`` annotation, or None.  Reads
    the object column without materializing (RawJson stays raw for every
    OTHER consumer; its cached parse is row-local and never written back)."""
    col = seg.obj[column]
    if col is None:
        return None
    v = col[j]
    if v is None or not hasattr(v, "get"):
        return None
    out = v.get(field)
    return out if isinstance(out, (int, float)) \
        and not isinstance(out, bool) else None


class RegionPage:
    """One prepared region answer, renderable without buffering: the fixed
    envelope (``prefix``/``suffix``) plus a row generator (``rows``) —
    what the streaming front end writes chunk by chunk, and what
    :meth:`QueryEngine.region` joins into the PR-5 byte-identical body.

    The rows to show are held as the columnar renderer takes them: ``si``
    and ``jj``, the segment index and local row of each, in response
    order (slices of the interval index, or of a cursor walk's match
    list).

    Unpaged pages (``cursor=None`` at prepare time) close with exactly
    ``]}`` — byte-identical to the pre-paging envelope; paged ones append
    a ``"next"`` field carrying the continuation token (null on the last
    page)."""

    __slots__ = ("shard", "label", "level", "bin_path", "count",
                 "generation", "si", "jj", "region_str", "next_token",
                 "paged")

    def __init__(self, shard, label, level, bin_path, count, generation,
                 si, jj, region_str, next_token, paged):
        self.shard = shard
        self.label = label
        self.level = level
        self.bin_path = bin_path
        self.count = count
        self.generation = generation
        self.si = si
        self.jj = jj
        self.region_str = region_str
        self.next_token = next_token
        self.paged = paged

    @property
    def returned(self) -> int:
        return int(self.jj.shape[0])

    def prefix(self) -> str:
        return (
            f'{{"region":{json.dumps(self.region_str)}'
            f',"bin_level":{self.level}'
            f',"bin_index":{json.dumps(self.bin_path)}'
            f',"count":{self.count}'
            f',"returned":{self.returned}'
            f',"generation":{self.generation}'
            ',"variants":['
        )

    def located(self, lo: int = 0, hi: int | None = None) -> tuple:
        """Rows ``[lo, hi)`` of the page as a :func:`render_located` run."""
        return self.shard, self.label, self.si[lo:hi], self.jj[lo:hi]

    def rows(self):
        """Rendered JSON text per row, in response order — a generator
        that renders ``REGION_RENDER_BLOCK`` rows at a time through the
        columnar pass (:func:`render_located`), so a streaming writer
        holds one block (not the whole body)."""
        for lo in range(0, self.returned, REGION_RENDER_BLOCK):
            yield from render_located(
                [self.located(lo, lo + REGION_RENDER_BLOCK)]
            )

    def suffix(self) -> str:
        if not self.paged:
            return "]}"
        nxt = json.dumps(self.next_token) if self.next_token else "null"
        return f'],"next":{nxt}}}'

    def assemble(self) -> str:
        return self.prefix() + ",".join(self.rows()) + self.suffix()


def _identity(seg, j: int) -> tuple:
    """The identity a first-wins compare uses: lengths and allele bytes."""
    return (
        int(seg.cols["ref_len"][j]), int(seg.cols["alt_len"][j]),
        seg.ref[j].tobytes(), seg.alt[j].tobytes(),
    )


def _sorted_rows(segments, first: int = 0, stop: int | None = None):
    """``(pos, h, si, jj)`` of every row of ``segments[first:stop]`` — ``si``
    the row's index in ``segments``, ``jj`` its local row — ordered by
    (pos, hash, segment age) and first-wins deduplicated EXACTLY as
    :meth:`QueryEngine._region_rows` resolves a single region.

    The common case (no (pos, hash) collision — loader-deduplicated rows)
    is three vectorized numpy ops; when collisions exist, the per-row
    identity walk runs over ONLY the colliding (pos, hash) runs (a
    singleton row can never be a duplicate), so one shadowed duplicate on
    a 100M-row chromosome costs a few rows of Python, not a
    full-chromosome loop."""
    pos_parts, h_parts, si_parts, jj_parts = [], [], [], []
    for si in range(first, len(segments) if stop is None else stop):
        seg = segments[si]
        if seg.n == 0:
            continue
        pos_parts.append(seg.cols["pos"])
        h_parts.append(seg.cols["h"])
        si_parts.append(np.full(seg.n, si, np.int32))
        jj_parts.append(np.arange(seg.n, dtype=np.int64))
    if not pos_parts:
        return (np.empty(0, np.int32), np.empty(0, np.uint32),
                np.empty(0, np.int32), np.empty(0, np.int64))
    pos = np.concatenate(pos_parts)
    h = np.concatenate(h_parts)
    si = np.concatenate(si_parts)
    jj = np.concatenate(jj_parts)
    order = np.lexsort((si, h, pos))
    ps, hs = pos[order], h[order]
    si_o, jj_o = si[order], jj[order]
    same = (ps[1:] == ps[:-1]) & (hs[1:] == hs[:-1])
    if not bool(np.any(same)):
        # no (pos, hash) collision anywhere: duplicates are impossible
        # and the sorted view IS the dedup'd view (vectorized path)
        return ps, hs, si_o, jj_o
    # collision case: only members of a multi-row (pos, hash) run can be
    # duplicates — walk those rows (and only those) with the exact
    # first-wins identity compare of _region_rows
    run_member = np.zeros(order.shape[0], bool)
    run_member[1:] |= same
    run_member[:-1] |= same
    keep = np.ones(order.shape[0], bool)
    run_key = None
    run_seen: list = []  # identities kept for the current (pos, h)
    for t in np.nonzero(run_member)[0].tolist():
        key = (int(ps[t]), int(hs[t]))
        if key != run_key:
            run_key, run_seen = key, []
        ident = _identity(segments[int(si_o[t])], int(jj_o[t]))
        if ident in run_seen:  # shadowed duplicate in a newer segment
            keep[t] = False
        else:
            run_seen.append(ident)
    return ps[keep], hs[keep], si_o[keep], jj_o[keep]


class IntervalIndex:
    """One chromosome group's deduplicated, position-sorted row view —
    the BITS "database" every interval query searches against.

    Built over a run of the shard's segments from the oldest (``segs``:
    the segment objects it covers, all of them for :meth:`build`'s
    default), ordered by (pos, hash, segment age) and first-wins
    deduplicated EXACTLY as :meth:`QueryEngine._region_rows` resolves a
    single region (:func:`_sorted_rows`) — so a query's ``[lo, hi)`` span
    over ``pos`` is the region's post-dedup match list verbatim, a span
    width is the exact region count, and an N-interval panel shares one
    O(n log n) build instead of paying N per-query dedup passes.

    A serving engine keys it by ``segs``, not by store generation: the
    segments a server holds resident are the BASE of every generation an
    insert makes on top of them (:class:`RegionParts`), so the index, its
    device copy and its decoded feature columns (``feats``) outlive the
    overlay generations.

    ``device_pos()`` lazily uploads the sentinel-padded position array
    once per index, so a panel's kernel calls re-use the resident copy
    instead of re-shipping the index per request.  An index the residency
    manager's uploader prepared (:meth:`QueryEngine.warm_region_index`)
    has its copy installed already, after every span program ran against
    it once (``warmed``)."""

    __slots__ = ("pos", "si", "jj", "segs", "feats", "_dev_pos", "warmed")

    def __init__(self, pos, si, jj, segs: tuple = ()):
        self.pos = pos  # [K] int32, sorted
        self.si = si    # [K] int32 segment index per kept row
        self.jj = jj    # [K] int64 local row per kept row
        self.segs = segs
        #: the :class:`StatsColumns` aligned to this index, decoded once
        #: (:meth:`QueryEngine._stats_features`)
        self.feats = None
        self._dev_pos = None
        #: every span program a panel can ask for has run against the
        #: installed device copy
        self.warmed = False

    @property
    def n(self) -> int:
        return int(self.pos.shape[0])

    @classmethod
    def build(cls, shard, stop: int | None = None) -> "IntervalIndex":
        """The index of ``shard.segments[:stop]`` (all of them by
        default)."""
        pos, _h, si, jj = _sorted_rows(shard.segments, 0, stop)
        return cls(np.ascontiguousarray(pos), np.ascontiguousarray(si),
                   np.ascontiguousarray(jj), tuple(shard.segments[:stop]))

    def fold(self, delta: "DeltaIndex", segs: tuple) -> "IntervalIndex":
        """This index with ``delta``'s rows merged in — the index a whole
        :meth:`build` over ``segs`` gives, in one O(n) pass instead of a
        sort.  Feature columns decoded on both sides merge the same way."""
        at = delta.at
        out = IntervalIndex(np.insert(self.pos, at, delta.pos),
                            np.insert(self.si, at, delta.si),
                            np.insert(self.jj, at, delta.jj), segs)
        if self.feats is not None and delta.feats is not None:
            out.feats = StatsColumns(*(
                np.insert(a, at, b) for a, b in
                zip(self.feats.columns(), delta.feats.columns())))
        return out

    def upload(self):
        """A fresh sentinel-padded device copy of the position array, not
        installed (a failure propagates to the caller)."""
        import jax

        from annotatedvdb_tpu.utils.arrays import POS_SENTINEL, pad_pow2

        return jax.device_put(pad_pow2(self.pos, POS_SENTINEL))

    def device_pos(self):
        """The sentinel-padded position array on device (uploaded once;
        a failure propagates to the caller, which falls back host-side
        and feeds the circuit breaker)."""
        if self._dev_pos is None:
            self._dev_pos = self.upload()
        return self._dev_pos

    def device_bytes(self) -> int:
        """Bytes the retained device copy occupies (0 when none): the
        pow2-padded int32 position array."""
        if self._dev_pos is None:
            return 0
        from annotatedvdb_tpu.utils.arrays import next_pow2

        return next_pow2(self.n) * 4

    def drop_device(self) -> None:
        """Forget a (possibly half-built) device copy after a failed
        kernel call or a budget eviction — the next device attempt
        re-uploads cleanly (host arrays stay; correctness is
        unaffected)."""
        self._dev_pos = None
        self.warmed = False


class DeltaIndex(IntervalIndex):
    """The rows of a shard that its base :class:`IntervalIndex` does not
    cover — the segments after the base's: memtable overlay segments and
    flushed segments not yet folded into a base — as an index of their
    own, host-only and small (thousands of rows, not millions).

    Built like any index (:func:`_sorted_rows`), then each row's (pos,
    hash) is searched in the base: a row that repeats an identity the base
    holds is shadowed (dropped — the base's segments are older, and
    first-wins is the store's policy), and every other row learns ``at``,
    the base row it sorts before in (pos, hash, segment age) order.  The
    base and this index then merge by ``np.insert`` without a compare: a
    read's rows, a fold into a new base (:meth:`IntervalIndex.fold`)."""

    __slots__ = ("at", "shadowed")

    @classmethod
    def build(cls, shard, base: IntervalIndex) -> "DeltaIndex":
        segments = shard.segments
        pos, h, si, jj = _sorted_rows(segments, len(base.segs))
        at = np.searchsorted(base.pos, pos, side="left")
        top = np.searchsorted(base.pos, pos, side="right")
        keep = np.ones(pos.shape[0], bool)
        # rows at a position the base holds rows at: place them by hash
        # among that position's base rows, and drop a repeated identity
        for t in np.flatnonzero(top > at).tolist():
            lo, hi = int(at[t]), int(top[t])
            b_si, b_jj = base.si[lo:hi].tolist(), base.jj[lo:hi].tolist()
            b_h = [int(segments[s].cols["h"][j]) for s, j in zip(b_si, b_jj)]
            ht = int(h[t])
            ident = None
            for s, j, hb in zip(b_si, b_jj, b_h):
                if hb != ht:
                    continue
                if ident is None:
                    ident = _identity(segments[int(si[t])], int(jj[t]))
                if _identity(segments[s], j) == ident:
                    keep[t] = False
                    break
            at[t] = lo + sum(hb <= ht for hb in b_h)
        index = cls(pos[keep], si[keep], jj[keep], tuple(segments))
        index.at = at[keep]
        index.shadowed = int(pos.shape[0] - index.n)
        return index

    def spans(self, starts, ends) -> tuple:
        """(lo, hi) per query interval (the BITS search on the host)."""
        starts, ends = interval_ops.clamped_queries(starts, ends)
        return (np.searchsorted(self.pos, starts, side="left"),
                np.searchsorted(self.pos, ends, side="right"))


class RegionParts:
    """A chromosome's rows as a region read sees them in one store
    generation: the ``base`` index of the segments the server made
    resident (kept, with its device copy, across the generations inserts
    make) and the ``delta`` of everything newer (None when there is none:
    a read-only server's reads take exactly the whole-index route).

    A read's count is the two span widths summed (the delta holds no row
    the base shadows), its rows are the two spans merged in (pos, hash,
    segment age) order — byte for byte what one whole index over the
    shard answers (``tests/test_region_delta_index.py`` pins both)."""

    __slots__ = ("base", "delta", "_whole")

    def __init__(self, base: IntervalIndex, delta: "DeltaIndex | None"):
        self.base = base
        self.delta = delta
        self._whole = None

    def delta_spans(self, starts, ends) -> tuple:
        """(lo, hi) per query interval in the delta; zeros without one."""
        if self.delta is None:
            zero = np.zeros(len(starts), np.int64)
            return zero, zero
        return self.delta.spans(starts, ends)

    def rows(self, b_lo: int, b_hi: int, d_lo: int, d_hi: int,
             limit: int | None = None) -> tuple:
        """(si, jj) of the rows of base span ``[b_lo, b_hi)`` and delta
        span ``[d_lo, d_hi)`` — one interval's — merged, the first
        ``limit`` of them (all by default).  Without a delta row they are
        slices of the base index: no row is touched before the render."""
        base, delta = self.base, self.delta
        if limit is not None:
            b_hi = min(b_hi, b_lo + limit)
            d_hi = min(d_hi, d_lo + limit)
        si, jj = base.si[b_lo:b_hi], base.jj[b_lo:b_hi]
        if delta is None or d_hi <= d_lo:
            return si, jj
        # a delta row of the interval sorts before base row ``at``, which
        # lies inside the base span (or just past it); a base span cut by
        # ``limit`` takes the rows past its end at its end, in order
        at = np.minimum(delta.at[d_lo:d_hi] - b_lo, si.shape[0])
        si = np.insert(si, at, delta.si[d_lo:d_hi])
        jj = np.insert(jj, at, delta.jj[d_lo:d_hi])
        if limit is not None:
            si, jj = si[:limit], jj[:limit]
        return si, jj

    def selected(self, b_sel: np.ndarray, d_sel: np.ndarray) -> tuple:
        """(si, jj) of base rows ``b_sel`` and delta rows ``d_sel``
        (ascending positions in each index) merged."""
        si, jj = self.base.si[b_sel], self.base.jj[b_sel]
        if not d_sel.shape[0]:
            return si, jj
        delta = self.delta
        at = np.searchsorted(b_sel, delta.at[d_sel], side="left")
        return (np.insert(si, at, delta.si[d_sel]),
                np.insert(jj, at, delta.jj[d_sel]))

    def whole(self) -> IntervalIndex:
        """One index over the whole shard (the base itself without a
        delta; else the two folded, once): for a consumer that walks the
        index's arrays directly (``export/stream``)."""
        if self.delta is None:
            return self.base
        if self._whole is None:
            self._whole = self.base.fold(self.delta, self.delta.segs)
        return self._whole


class StatsColumns:
    """One chromosome group's decoded analytics feature columns, aligned
    row-for-row to its :class:`IntervalIndex`.

    The JSONB sidecar is decoded ONCE per index (held as its ``feats``)
    — ``feature_values`` walks every index row exactly one time — into:

    - ``cadd_f``/``rank_f`` float64 (NaN = missing): the exact values the
      ``min_cadd``/``max_conseq_rank`` filters compare, so the serving
      filter path stops re-parsing sidecar JSON per row per request (the
      old ``_ann_number``-per-row hot spot) while staying byte-identical
      to the scalar ``_passes`` definition;
    - ``af_fp``/``cadd_fp``/``rank_i`` int32 fixed point
      (``ops.stats.STATS_MISSING`` = absent): the stats kernels' inputs.

    Because the columns align to the index (position-sorted, first-wins
    deduplicated), a BITS span over the index IS a slice of these columns
    — filters vectorize and the fused stats kernel reduces over them
    directly; a base and its delta each have their own, and every number
    the kernels give is a sum, so the two parts' add up.  ``device()``
    uploads the sentinel-padded kernel columns once per index (the
    ``IntervalIndex.device_pos`` discipline; same pow2 capacity, so the
    traced program is shared)."""

    __slots__ = ("cadd_f", "rank_f", "af_fp", "cadd_fp", "rank_i", "_dev")

    def __init__(self, cadd_f, rank_f, af_fp, cadd_fp, rank_i):
        self.cadd_f = cadd_f
        self.rank_f = rank_f
        self.af_fp = af_fp
        self.cadd_fp = cadd_fp
        self.rank_i = rank_i
        self._dev = None

    def columns(self) -> tuple:
        """The five host columns, in the constructor's order."""
        return self.cadd_f, self.rank_f, self.af_fp, self.cadd_fp, \
            self.rank_i

    @classmethod
    def build(cls, shard, index: "IntervalIndex") -> "StatsColumns":
        n = index.n
        cadd_f = np.full(n, np.nan, np.float64)
        rank_f = np.full(n, np.nan, np.float64)
        af_fp = np.full(n, stats_ops.STATS_MISSING, np.int32)
        cadd_fp = np.full(n, stats_ops.STATS_MISSING, np.int32)
        rank_i = np.full(n, stats_ops.STATS_MISSING, np.int32)
        si, jj = index.si, index.jj
        # group index rows per segment in ONE stable sort + run split —
        # a per-segment boolean scan would be O(segments x rows), which
        # on an overlay-heavy pre-compaction shard is minutes of pure
        # grouping before any decode
        order = np.argsort(si, kind="stable")
        run_starts = np.nonzero(
            np.diff(si[order], prepend=si[order[0]] - 1 if order.size
                    else 0)
        )[0]
        for r, lo in enumerate(run_starts.tolist()):
            hi = run_starts[r + 1] if r + 1 < len(run_starts) \
                else order.shape[0]
            s = int(si[order[lo]])
            seg = shard.segments[s]
            cadd_col = seg.obj["cadd_scores"]
            af_col = seg.obj["allele_frequencies"]
            ms_col = seg.obj["adsp_most_severe_consequence"]
            if cadd_col is None and af_col is None and ms_col is None:
                continue  # nothing annotated: the columns stay MISSING
            for t in order[lo:hi].tolist():
                j = int(jj[t])
                cf, rf, afp, cfp, ri = stats_ops.feature_values(
                    cadd_col[j] if cadd_col is not None else None,
                    af_col[j] if af_col is not None else None,
                    ms_col[j] if ms_col is not None else None,
                )
                cadd_f[t] = cf
                rank_f[t] = rf
                af_fp[t] = afp
                cadd_fp[t] = cfp
                rank_i[t] = ri
        return cls(cadd_f, rank_f, af_fp, cadd_fp, rank_i)

    def device(self):
        """The sentinel-padded kernel columns on device (uploaded once;
        a failure propagates — the caller falls back host-side and feeds
        the circuit breaker)."""
        if self._dev is None:
            import jax

            from annotatedvdb_tpu.utils.arrays import pad_pow2

            self._dev = tuple(
                jax.device_put(pad_pow2(a, stats_ops.STATS_MISSING))
                for a in (self.af_fp, self.cadd_fp, self.rank_i)
            )
        return self._dev

    def device_bytes(self) -> int:
        """Bytes the retained device copies occupy (0 when none): three
        pow2-padded int32 columns — the INDEX_DEVICE_BYTES ledger's unit,
        same accessor contract as ``IntervalIndex.device_bytes``."""
        if self._dev is None:
            return 0
        from annotatedvdb_tpu.utils.arrays import next_pow2

        return 3 * next_pow2(int(self.af_fp.shape[0])) * 4

    def drop_device(self) -> None:
        """Forget a (possibly half-built) device copy after a failed
        kernel call or a budget eviction — host arrays stay, answers
        stay byte-identical."""
        self._dev = None


class StatsResult:
    """One prepared analytics answer: per-interval summary dicts in
    request order, wrapped as ``{"n", "generation", "metrics", "bins",
    "results"}``.  ``assemble()`` is the ONE renderer the front end
    buffers from (stats bodies are summaries — kilobytes, never
    row-materializing — so there is no streaming shape)."""

    __slots__ = ("generation", "metrics", "entries")

    def __init__(self, generation: int, metrics, entries: list):
        self.generation = generation
        self.metrics = list(metrics)
        self.entries = entries

    @property
    def returned(self) -> int:
        """Summary rows rendered (one per interval) — the metrics row
        count."""
        return len(self.entries)

    def assemble(self) -> str:
        return json.dumps({
            "n": len(self.entries),
            "generation": self.generation,
            "metrics": self.metrics,
            "bins": stats_ops.edges_payload(),
            "results": self.entries,
        }, separators=(",", ":"))


class RegionsResult:
    """One prepared batch-region answer: per-interval envelopes (each a
    :class:`RegionPage`, byte-identical to its single-``region()`` call)
    in request order, wrapped as ``{"n": N[, "tokens": {...}],
    "results": [...]}``.  Same prefix/rows/suffix surface as
    :class:`RegionPage`, so the streaming writer handles both shapes —
    ``rows()`` yields one assembled per-interval envelope at a time and
    renders a block of consecutive pages at a time (RSS holds one block's
    rows, not the panel's).  ``clock`` is the panel's
    :class:`_PanelClock`: the renderer tallies its rows' routes there,
    whoever renders the body times it there (``regions.render``) and
    hands it to :meth:`QueryEngine.regions_rendered`."""

    __slots__ = ("pages", "tokens", "clock")

    def __init__(self, pages: list, tokens: dict | None = None, clock=None):
        self.pages = pages
        self.tokens = tokens
        self.clock = clock

    @property
    def returned(self) -> int:
        """Total rows rendered across the batch (the streaming-threshold
        and metrics row count)."""
        return sum(p.returned for p in self.pages)

    def prefix(self) -> str:
        head = f'{{"n":{len(self.pages)}'
        if self.tokens is not None:
            tok = ",".join(
                f'"{k}":{json.dumps(v, separators=(",", ":"))}'
                for k, v in self.tokens.items()
            )
            head += ',"tokens":{' + tok + "}"
        return head + ',"results":['

    def rows(self):
        """One assembled envelope per interval, request order, lazily:
        consecutive pages are gathered until they hold
        ``REGION_RENDER_BLOCK`` rows, their rows rendered together
        (:func:`render_located` groups them by chromosome shard and
        segment — a panel's targets come in any order) and dealt back to
        their pages."""
        pages = self.pages
        start = 0
        while start < len(pages):
            stop, held = start, 0
            while stop < len(pages) and held < REGION_RENDER_BLOCK:
                held += pages[stop].returned
                stop += 1
            block = pages[start:stop]
            texts = render_located(
                [page.located() for page in block], self.clock
            )
            at = 0
            for page in block:
                upto = at + page.returned
                yield page.prefix() + ",".join(texts[at:upto]) \
                    + page.suffix()
                at = upto
            start = stop

    def suffix(self) -> str:
        return "]}"

    def assemble(self) -> str:
        return self.prefix() + ",".join(self.rows()) + self.suffix()


class _StageClock:
    """One engine call's sub-stage seconds (one call runs on one thread at
    a time: plain integers, no lock).  :meth:`span` times one sub-stage
    where the work happens: nanoseconds summed per stage over the call, a
    sub-span on the calling thread's active request stage (true start and
    end), and a profiler annotation ``avdb.<stage>`` — about ten a call,
    never one per id or per interval."""

    __slots__ = ("ns",)

    def __init__(self, stages):
        self.ns = dict.fromkeys(stages, 0)

    @contextlib.contextmanager
    def span(self, stage: str, **args):
        with annotation(f"avdb.{stage}", **args):
            start_ns = time.perf_counter_ns()
            try:
                yield
            finally:
                end_ns = time.perf_counter_ns()
                self.ns[stage] += end_ns - start_ns
                reqtrace.record_active(stage, start_ns, end_ns)


class _PanelClock(_StageClock):
    """One ``POST /regions`` panel's sub-stage seconds
    (``reqtrace.REGION_STAGES``) and tallies, added to the engine's
    counters once a panel (:meth:`QueryEngine._regions_done`): a
    chromosome group counts once, by who answered its span search."""

    __slots__ = ("device_groups", "host_groups", "transfers",
                 "batch_rows", "scalar_rows")

    def __init__(self):
        super().__init__(reqtrace.REGION_STAGES)
        #: groups the device answered / groups that took the numpy twin
        #: (below the minimum, breaker open, device failure, ``host_only``)
        self.device_groups = 0
        self.host_groups = 0
        #: host<->device array transfers of the collected span calls
        self.transfers = 0
        #: rows the body's render handed the columnar pass, and rows that
        #: went through the scalar ``_render_row`` (retained host strings,
        #: an over-width length, a block of one row) — told by
        #: :func:`render_located` once per (shard, segment) group of a block
        self.batch_rows = 0
        self.scalar_rows = 0


class _RegionClock(_StageClock):
    """One single-region read's sub-stage seconds
    (``reqtrace.REGION_READ_STAGES``), observed once a read
    (:meth:`QueryEngine._region_done`); ``delta`` says whether its count or
    page took a delta row."""

    __slots__ = ("delta",)

    def __init__(self):
        super().__init__(reqtrace.REGION_READ_STAGES)
        self.delta = False


class _LookupClock(_StageClock):
    """One ``lookup_many`` call's sub-stage seconds
    (``reqtrace.LOOKUP_STAGES``, parent ``device``) and render tallies.
    The tallies are added once per chromosome group (render cache) and
    once per touched segment (renderer route)."""

    __slots__ = ("found", "misses", "batch_rows", "scalar_rows",
                 "overlay_hits")

    def __init__(self):
        super().__init__(reqtrace.LOOKUP_STAGES)
        #: ids found (each is answered through the render cache) and the
        #: distinct rows among them that were not in it
        self.found = 0
        self.misses = 0
        #: of the ids found: those a memtable overlay segment answered
        #: (upserted since the last flush), added once a chromosome group
        self.overlay_hits = 0
        #: of those misses: rows the columnar pass assembled, and rows
        #: that went through the scalar ``_render_row`` (retained host
        #: strings, an over-width length, a group of one miss)
        self.batch_rows = 0
        self.scalar_rows = 0


class QueryEngine:
    """Point/bulk/region queries over a snapshot provider
    (:class:`~annotatedvdb_tpu.serve.snapshot.SnapshotManager` in a server,
    :class:`~annotatedvdb_tpu.serve.snapshot.StaticSnapshots` in tests).
    An optional :class:`~annotatedvdb_tpu.serve.residency.ResidencyManager`
    governs which probed segments stay HBM-resident."""

    #: rendered point-record LRU capacity (entries).  Keyed by
    #: (generation, chromosome, global id): a serving generation's rows
    #: are immutable, so a hot variant renders once per generation and
    #: costs a dict probe afterwards.  What it saves is the miss path of
    #: ``_render_group``: a point drain's one or two misses a chromosome
    #: render row by row (``_render_row``), a bulk call's thousands in
    #: one columnar pass (``render_rows``) — uniform bulk draws over
    #: millions of rows hardly ever hit it (PERF.md section 5).
    POINT_RENDER_CACHE = 1 << 16
    #: and a byte ceiling on the cached text: records carrying large
    #: spliced RawJson annotation blobs (tens of KB each) must not pin
    #: entries x record-size of RSS in a long-lived gc.freeze'd process
    POINT_RENDER_CACHE_BYTES = 64 << 20

    #: retained base interval indexes, keyed by the segments each covers
    #: (the LRU's cap over all chromosomes) ...
    INDEX_CACHE = 64
    #: ... and a chromosome's cap: the base a fold replaced stays one
    #: round for the requests that still hold the generation before it
    BASES_PER_CHROMOSOME = 2
    #: retained (generation, chromosome) :class:`RegionParts` — a base
    #: shared by reference and the generation's small delta
    PARTS_CACHE = 64
    #: byte ceiling on RETAINED device copies of interval indexes (the
    #: BITS kernel's search arrays) AND stats feature columns (the fused
    #: analytics kernel's inputs, ~3x the position bytes per group) —
    #: all of which live OUTSIDE the residency manager's ``--hbmBudget``
    #: plan: beyond it the least-recently-used entries drop their device
    #: copy — host arrays stay, answers are byte-identical, only the
    #: re-upload cost returns.  Without this the count-bounded caches
    #: could pin dozens of chromosome-sized arrays of HBM on a large
    #: store.
    INDEX_DEVICE_BYTES = 256 << 20

    def __init__(self, snapshots, registry=None,
                 region_cache_size: int | None = None, residency=None,
                 breaker=None, regions_max: int | None = None,
                 regions_device_min: int | None = None, mesh=None,
                 stats_max: int | None = None,
                 stats_device_min: int | None = None):
        from annotatedvdb_tpu.serve.batcher import (
            resolve_regions_knobs,
            resolve_stats_knobs,
        )

        self.snapshots = snapshots
        self.residency = residency
        self.stats_max, self.stats_device_min = resolve_stats_knobs(
            stats_max, stats_device_min
        )
        #: mesh executor (serve/mesh_exec.MeshExecutor) or None — when set,
        #: bulk lookups and region panels collapse to ONE sharded call
        #: each; every mesh miss/failure falls back to the single-device
        #: paths below, whose answers are byte-identical (tests/test_mesh)
        self.mesh = mesh
        self.regions_max, self.regions_device_min = resolve_regions_knobs(
            regions_max, regions_device_min
        )
        #: device-path circuit breaker (serve/resilience.DeviceBreaker) —
        #: None keeps the store's legacy one-failure-latches-host behavior
        self.breaker = breaker
        if breaker is not None:
            breaker.install()
        self._render_lock = make_lock("serve.engine.render")
        #: guarded by self._render_lock
        self._render_cache: OrderedDict = OrderedDict()
        #: guarded by self._render_lock
        self._render_cache_bytes = 0
        if region_cache_size is None:
            region_cache_size = int(
                os.environ.get("AVDB_SERVE_REGION_CACHE", "") or 64
            )
        self.region_cache_size = max(int(region_cache_size), 0)
        self._cache_lock = make_lock("serve.engine.cache")
        #: guarded by self._cache_lock
        self._region_cache: OrderedDict = OrderedDict()
        #: (generation, region, filters) -> (si, j) arrays of the walk's
        #: post-filter matches and whether a delta row is among them, so
        #: an N-page cursor walk scans the region once, not once per page
        #: guarded by self._cache_lock
        self._walk_cache: OrderedDict = OrderedDict()
        #: (code, ids of the segments it covers) -> base
        #: :class:`IntervalIndex` (the BITS search database per group; it
        #: holds the segments, so no id is reused while the entry lives)
        #: guarded by self._cache_lock
        self._index_cache: OrderedDict = OrderedDict()
        #: guarded by self._cache_lock; (generation, code) ->
        #: :class:`RegionParts`
        self._parts_cache: OrderedDict = OrderedDict()
        #: guarded by self._cache_lock; id(index) -> (index, bytes) for
        #: indexes holding a device copy — the INDEX_DEVICE_BYTES ledger
        self._index_device: OrderedDict = OrderedDict()
        #: serializes interval-index BUILDS (not lookups): after a
        #: generation swap every concurrent region request misses the
        #: cache at once, and a full-chromosome lexsort is seconds of CPU
        #: and a multiple of the shard's RAM — N duplicate builds would
        #: be an N-fold memory spike for identical results.  Losers wait
        #: and take the winner's entry from the cache.
        self._index_build_lock = make_lock("serve.engine.index_build")
        #: the same for a generation's deltas (milliseconds, not seconds:
        #: never queued behind a whole build)
        self._delta_build_lock = make_lock("serve.engine.delta_build")
        if registry is not None:
            self._cache_hits = registry.counter(
                "avdb_query_cache_hits_total",
                "region queries served from the rendered LRU",
            )
            self._cache_misses = registry.counter(
                "avdb_query_cache_misses_total",
                "region queries that rendered fresh",
            )
        else:
            self._cache_hits = self._cache_misses = None
        #: render-cache (point/bulk record LRU) outcomes, added once per
        #: ``lookup_many`` call from the call's own tallies — ints under
        #: the GIL, read by ``/stats`` (``render_cache``)
        self.render_cache_hits = 0
        self.render_cache_misses = 0
        #: found ids a memtable overlay segment answered (``/stats``
        #: ``memtable.read_hits``), added the same way
        self.memtable_read_hits = 0
        #: of the misses, by renderer route (``/stats`` ``render_batch``):
        #: rows of the columnar pass / rows of the scalar ``_render_row``
        self.render_batch_rows = 0
        self.render_scalar_rows = 0
        #: ``/stats`` ``region_panels``: answered ``POST /regions`` panels
        #: and what they took, added once a panel (:meth:`_regions_done`,
        #: :meth:`regions_rendered`) — ints under the GIL
        self.region_panels = dict.fromkeys(
            ("panels", "intervals", "device_groups", "host_groups",
             "rows_rendered", "rows_batched", "rows_scalar", "streamed",
             "transfers"), 0)
        #: ``/stats`` ``region_index``: whole-chromosome base indexes
        #: made (``builds``: sorted from the segments, ``base_builds``, or
        #: folded from a base and the rows a store refresh added,
        #: ``flush_builds``), of the sorts those a request's thread paid
        #: (``request_builds``), device copies uploaded, deltas built and
        #: the rows of each chromosome's newest — counted once each
        #: wherever it happened (the uploader's or the refresh's thread,
        #: or lazily a request) — ints under the GIL
        self.index_builds = 0
        self.index_uploads = 0
        self.base_builds = 0
        self.flush_builds = 0
        self.request_builds = 0
        self.delta_builds = 0
        #: guarded by self._cache_lock; code -> rows of its newest delta
        self._delta_rows: dict = {}
        #: ``/stats`` ``region_scans``: single-region reads the engine
        #: located (a region-LRU hit locates nothing) and, of those, the
        #: reads whose count or page took a delta row
        self.region_scans = {"reads": 0, "delta_reads": 0}
        if residency is not None:
            # a segment the manager uploads has its chromosome's interval
            # index built, uploaded and warmed on the same thread, before
            # the segment counts as resident
            residency.index_warmer = self.warm_region_index
        # a store refresh (a memtable flush, a loader commit) folds the
        # segments it adds into the bases it extends before the new
        # generation is pinned (:meth:`prepare_region_bases`)
        base_manager = getattr(snapshots, "base", snapshots)
        if hasattr(base_manager, "add_preparer"):
            base_manager.add_preparer(self.prepare_region_bases)
        self._lookup_hist = self._regions_hist = self._region_hist = None
        self._m_render_hits = self._m_render_misses = None
        self._m_batch_rows = self._m_scalar_rows = None
        if registry is not None:
            self._lookup_hist = reqtrace.stage_histograms(
                registry, reqtrace.LOOKUP_STAGES
            )
            self._regions_hist = reqtrace.stage_histograms(
                registry, reqtrace.REGION_STAGES
            )
            self._region_hist = reqtrace.stage_histograms(
                registry, reqtrace.REGION_READ_STAGES
            )
            self._m_render_hits = registry.counter(
                "avdb_render_cache_hits_total",
                "found ids answered from the rendered-record LRU",
            )
            self._m_render_misses = registry.counter(
                "avdb_render_cache_misses_total",
                "found ids rendered fresh (locate, render, decode)",
            )
            self._m_batch_rows, self._m_scalar_rows = (
                registry.counter(
                    "avdb_render_batch_rows_total",
                    "rows rendered fresh, by renderer route",
                    labels={"path": path},
                )
                for path in ("columnar", "scalar")
            )

    # -- point / bulk -------------------------------------------------------

    def lookup(self, variant_id: str) -> str | None:
        """JSON text of the record, or None when absent."""
        return self.lookup_many([variant_id])[0]

    def lookup_many(self, ids: list, parsed: list | None = None) -> list:
        """[JSON text | None] per id, order-preserving.  Ids are parsed up
        front (one bad id fails the CALL with :class:`QueryError` — the
        batcher pre-validates at submit so co-batched strangers never share
        a client's grammar error), then probed per chromosome as one
        vectorized batch through the loader's membership path.  The
        batcher passes the tuples it already parsed at submit via
        ``parsed`` — re-parsing a microbatch is measurable at QPS."""
        out: list = [None] * len(ids)
        if not ids:
            return out
        clock = _LookupClock()
        try:
            return self._lookup_many(ids, parsed, out, clock)
        finally:
            self._lookup_done(clock)

    def _lookup_many(self, ids: list, parsed, out: list, clock) -> list:
        if parsed is None:
            with clock.span("lookup.parse", n=len(ids)):
                parsed = [parse_variant_id(s) for s in ids]
        snap = self.snapshots.current()
        if self.residency is not None:
            self.residency.govern(snap)
        store = snap.store
        width = store.width
        overlay_from = getattr(store, "overlay_from", None)
        if self.mesh is not None and len(ids) >= self.mesh.bulk_min \
                and self.mesh.would_dispatch(snap):
            got = self._mesh_lookup_many(snap, parsed, out, clock)
            if got is not None:
                return got
        with clock.span("lookup.hash", n=len(ids)):
            by_code: dict[int, list] = {}
            for i, (code, _pos, _ref, _alt) in enumerate(parsed):
                by_code.setdefault(code, []).append(i)
        # two passes: every chromosome group's probe is launched before
        # any is collected, so group 1's program runs while groups 2 and
        # 3 are hashed, and theirs run and travel back while group 1
        # renders.  A group on the host path (breaker open, small or not
        # resident, a CPU backend) is answered in its launch.
        groups = []
        for code, idxs in by_code.items():
            shard = store.shards.get(code)
            if shard is None:
                continue  # chromosome not loaded: every id misses
            with clock.span("lookup.hash", chrom=code, n=len(idxs)):
                ref, alt, ref_len, alt_len, h = identity_columns(
                    [parsed[i][2] for i in idxs],
                    [parsed[i][3] for i in idxs], width,
                )
                pos = np.fromiter(
                    (parsed[i][1] for i in idxs), np.int32, count=len(idxs)
                )
                if self.residency is not None:
                    qkey = combined_key(pos, h)
                    self.residency.touch_window(
                        shard, qkey.min(), qkey.max(), len(idxs)
                    )
            query = (pos, h, ref, alt, ref_len, alt_len)
            with clock.span("lookup.probe", chrom=code, n=len(idxs)):
                launched, obs = self._launch_group(shard, code, query)
            groups.append((shard, code, query, launched, obs))
        count_overlapped(sum(
            bool(launched.waiting) for _s, _c, _q, launched, _o in groups
        ))
        for shard, code, query, launched, obs in groups:
            idxs = by_code[code]
            with clock.span("lookup.probe", chrom=code, n=len(idxs)):
                found, gid = self._collect_group(
                    shard, code, query, launched, obs
                )
            with clock.span("lookup.rows", chrom=code, n=len(idxs)):
                at = np.flatnonzero(found)
                if overlay_from is not None and at.size:
                    clock.overlay_hits += int(np.count_nonzero(
                        gid[at] >= overlay_from.get(code, 0)))
                texts = self._render_group(
                    shard, code, gid[at].tolist(), snap.generation, clock
                )
                for k, text in zip(at.tolist(), texts):
                    out[idxs[k]] = text
        return out

    def _lookup_done(self, clock: "_LookupClock") -> None:
        """One ``lookup_many`` call's accounts: each sub-stage's seconds
        (summed over the call's chromosome groups; 0 for a stage the call
        never entered) observed ONCE, so the four means add up to the
        ``device`` stage's; the render-cache and renderer-route tallies
        added once — no lock and no metric call per id."""
        hits = clock.found - clock.misses
        self.render_cache_hits += hits
        self.render_cache_misses += clock.misses
        self.memtable_read_hits += clock.overlay_hits
        self.render_batch_rows += clock.batch_rows
        self.render_scalar_rows += clock.scalar_rows
        if self._lookup_hist is not None:
            for stage, ns in clock.ns.items():
                self._lookup_hist[stage].observe(ns / 1e9)
            for metric, n in (
                (self._m_render_hits, hits),
                (self._m_render_misses, clock.misses),
                (self._m_batch_rows, clock.batch_rows),
                (self._m_scalar_rows, clock.scalar_rows),
            ):
                if n:
                    metric.inc(n)

    def _launch_group(self, shard, code: int, query: tuple) -> tuple:
        """One chromosome group's membership probe, launched
        (``ChromosomeShard.lookup_launch``) and routed through the device
        circuit breaker when one is installed; :meth:`_collect_group`
        takes what this returns and finishes it.

        Closed/half-open groups take the normal path (the breaker's
        half-open state admits exactly one trial); an open group pins the
        probe to the byte-identical host path — no failing-device attempt
        is paid per lookup while the device is sick.  Failures reach the
        breaker two ways: REAL device errors surface through the store's
        probe-fallback hook (``observing`` attributes them to this group,
        in this step and again around its collect — never across another
        group's step), and the ``engine.device_probe`` fault point injects
        them deterministically for the matrix/chaos runs — either way the
        caller gets correct bytes (host retry).  Returns the shard's launched
        lookup and the breaker's observation of it — None where there is
        no success left to record."""
        breaker = self.breaker
        if breaker is None:
            return shard.lookup_launch(*query), None
        if not breaker.allow_device(code):
            return shard.lookup_launch(*query, host_only=True), None
        try:
            with breaker.observing(code) as obs:
                # crash point: models a device probe/upload failure
                # surfacing from this group's membership probe — the
                # breaker must absorb it on the host path, never wrong
                # bytes
                faults.fire("engine.device_probe")
                launched = shard.lookup_launch(*query)
        except Exception as exc:
            breaker.record_failure(code, exc)
            return shard.lookup_launch(*query, host_only=True), None
        return launched, obs

    def _collect_group(self, shard, code: int, query: tuple, launched,
                       obs):
        """(found, global id) of a group :meth:`_launch_group` launched.
        A device error that surfaces only here is this group's alone: it
        is recorded against ``code``, the group is answered again from
        the host, and a success is recorded only after a clean collect."""
        if obs is None:
            return shard.lookup_collect(launched)
        breaker = self.breaker
        try:
            with breaker.observing(code, obs):
                out = shard.lookup_collect(launched)
        except Exception as exc:
            breaker.record_failure(code, exc)
            return shard.lookup(*query, host_only=True)
        if not obs.failed:
            breaker.record_success(code)
        return out

    def _mesh_lookup_many(self, snap, parsed, out, clock):
        """The mesh bulk path: every id of the batch — all chromosome
        groups at once — resolves through ONE sharded call
        (``serve.mesh_exec.MeshExecutor.bulk_lookup``), and hits render
        through the exact same generation-keyed cache the single-device
        path uses.  Returns None when the executor declines (off/tripped/
        over budget/failed) — the caller runs the per-group loop, whose
        answers are byte-identical."""
        store = snap.store
        width = store.width
        n = len(parsed)
        with clock.span("lookup.hash", n=n):
            ref, alt, ref_len, alt_len, h = identity_columns(
                [p[2] for p in parsed], [p[3] for p in parsed], width
            )
            pos = np.fromiter((p[1] for p in parsed), np.int32, count=n)
            chrom = np.fromiter((p[0] for p in parsed), np.int8, count=n)
        with clock.span("lookup.probe", n=n):
            got = self.mesh.bulk_lookup(
                snap, chrom, pos, h, ref, alt, ref_len, alt_len
            )
        if got is None:
            return None
        found, gid = got
        if self.residency is not None:
            # mesh traffic must keep feeding the residency heat scores:
            # the per-segment caches are what the single-device FALLBACK
            # serves from, and a decayed-to-zero plan would evict them
            # exactly when a tripped mesh needs them warm
            with clock.span("lookup.hash", n=n):
                qkey = combined_key(pos, h)
                by_code: dict[int, list] = {}
                for i, (code, _p, _r, _a) in enumerate(parsed):
                    by_code.setdefault(code, []).append(i)
                for code, idxs in by_code.items():
                    shard = store.shards.get(code)
                    if shard is None:
                        continue
                    k = qkey[idxs]
                    self.residency.touch_window(
                        shard, k.min(), k.max(), len(idxs)
                    )
        with clock.span("lookup.rows", n=n):
            at = np.flatnonzero(found)
            codes = chrom[at]
            for code in np.unique(codes).tolist():
                sel = at[codes == code]
                texts = self._render_group(
                    store.shards[code], code, gid[sel].tolist(),
                    snap.generation, clock,
                )
                for i, text in zip(sel.tolist(), texts):
                    out[i] = text
        return out

    def _render_group(self, shard, code: int, gids: list, generation: int,
                      clock: "_LookupClock") -> list:
        """One chromosome group's found rows (global ids, request order,
        repeats allowed) as JSON text through the generation-keyed LRU
        (stale generations age out with everything else; their keys can
        never be probed again).  Two lock holds a call, never one per id:
        the first answers the hits and moves them to the end; the distinct
        misses render in one :func:`render_rows` pass outside the lock;
        the second inserts them and evicts to both ceilings.  A gid twice
        in one call renders once and tallies one miss and one hit; tallies
        go to the call's ``clock``."""
        clock.found += len(gids)
        texts = []
        #: distinct missing key -> its place in the render; and per
        #: unanswered id (its place in ``texts``, its key's place)
        missing: dict = {}
        fill = []
        with self._render_lock:
            cache = self._render_cache
            for k, gid in enumerate(gids):
                key = (generation, code, gid)
                text = cache.get(key)
                if text is None:
                    fill.append((k, missing.setdefault(key, len(missing))))
                else:
                    cache.move_to_end(key)
                texts.append(text)
        if not fill:
            return texts
        clock.misses += len(missing)
        fresh = render_rows(
            shard, code, [key[2] for key in missing], clock
        )
        with self._render_lock:
            cache = self._render_cache
            size = self._render_cache_bytes
            for key, text in zip(missing, fresh):
                # another thread can race the same miss: replace, don't
                # double-count
                old = cache.pop(key, None)
                if old is not None:
                    size -= len(old)
                cache[key] = text
                size += len(text)
            while cache and (
                len(cache) > self.POINT_RENDER_CACHE
                or size > self.POINT_RENDER_CACHE_BYTES
            ):
                _, old = cache.popitem(last=False)
                size -= len(old)
            self._render_cache_bytes = size
        for k, at in fill:
            texts[k] = fresh[at]
        return texts

    # -- region -------------------------------------------------------------

    def region(self, spec: str, min_cadd=None, max_conseq_rank=None,
               limit: int | None = None, cursor: str | None = None,
               host_only: bool = False) -> str:
        """JSON text answering ``chr:start-end`` (with optional filters):
        ``{"region", "bin_level", "bin_index", "count", "returned",
        "generation", "variants": [...]}``.  ``count`` is the post-filter
        match total; ``variants`` carries the first ``limit`` of them.
        With ``cursor`` (``""`` starts a paged walk, a returned token
        continues it) the envelope additionally carries ``"next"``.
        ``host_only=True`` pins the interval search to the numpy twin
        (byte-identical — the circuit breaker's path)."""
        kind, payload = self.region_serve(
            spec, min_cadd=min_cadd, max_conseq_rank=max_conseq_rank,
            limit=limit, cursor=cursor, stream_threshold=None,
            host_only=host_only,
        )
        return payload if kind == "text" else payload.assemble()

    def region_serve(self, spec: str, min_cadd=None, max_conseq_rank=None,
                     limit: int | None = None, cursor: str | None = None,
                     stream_threshold: int | None = None,
                     host_only: bool = False):
        """The front end's region entry point: ``("text", str)`` for
        responses small enough to buffer (cache-eligible when unpaged), or
        ``("page", RegionPage)`` when the row count exceeds
        ``stream_threshold`` — the caller streams prefix/rows/suffix
        without ever materializing the body (large gene-panel regions stop
        holding peak RSS)."""
        code, start, end = parse_region(spec)
        snap = self.snapshots.current()
        if self.residency is not None:
            self.residency.govern(snap)
        cache_key = None
        if cursor is None:
            cache_key = (snap.generation, code, start, end,
                         min_cadd, max_conseq_rank, limit)
            text = self._cache_get(cache_key)
            if text is not None:
                return "text", text
        clock = _RegionClock()
        page = self._region_page(
            snap, code, start, end, min_cadd, max_conseq_rank, limit,
            cursor, host_only, clock,
        )
        if stream_threshold is not None and page.returned > stream_threshold:
            self._region_done(clock, rendered=False)
            return "page", page
        with clock.span("region.render", n=page.returned):
            text = page.assemble()
        self._region_done(clock, rendered=True)
        if cache_key is not None:
            self._cache_put(cache_key, text)
        return "text", text

    def _region_done(self, clock: "_RegionClock", rendered: bool) -> None:
        """One located single-region read's accounts, added once: the
        ``/stats`` ``region_scans`` tallies and its sub-stages."""
        self.region_scans["reads"] += 1
        if clock.delta:
            self.region_scans["delta_reads"] += 1
        if self._region_hist is not None:
            for stage in reqtrace.REGION_READ_STAGES:
                if rendered or stage != "region.render":
                    self._region_hist[stage].observe(clock.ns[stage] / 1e9)

    def regions_serve(self, specs: list, min_cadd=None, max_conseq_rank=None,
                      limit: int | None = None, tokenize: bool = False,
                      host_only: bool = False,
                      clock: "_PanelClock | None" = None) -> RegionsResult:
        """Bulk region join: a batch of ``chr:start-end`` specs answered
        with ONE BITS kernel call per touched chromosome group.

        Returns a :class:`RegionsResult` whose per-interval envelopes are
        **byte-identical** to ``len(specs)`` sequential :meth:`region`
        calls with the same filters/limit, in request order.  Grammar is
        validated up front — one bad spec fails the CALL with
        :class:`QueryError` (the bulk-``/variants`` contract: co-batched
        strangers never share a client's grammar error, because the front
        end maps this to one 400 for the one caller).

        ``limit=0`` with no filters is the pure count-only mode: counts
        come straight from the kernel's span widths (the index is already
        deduplicated) and NO row is ever located, filtered, or rendered.
        ``tokenize=True`` adds the fixed-width interval-token arrays
        (``bin_level``/``leaf_bin``/``bin_index`` path, ``row_lo``/
        ``row_hi`` spans into the generation's interval index, pre-filter
        ``count``) for ML consumers.

        ``clock`` is the panel's :class:`_PanelClock` when the caller
        opened one (:meth:`panel_clock`: the front end times the body's
        parse on it); the result carries it on to whoever renders."""
        if len(specs) > self.regions_max:
            raise QueryError(
                f"regions batch of {len(specs)} exceeds the "
                f"{self.regions_max}-interval cap (AVDB_SERVE_REGIONS_MAX); "
                "split the request"
            )
        if clock is None:
            clock = _PanelClock()
        with clock.span("regions.parse", n=len(specs)):
            parsed = [parse_region(s) for s in specs]
            by_code: dict[int, list[int]] = {}
            for i, (code, _s, _e) in enumerate(parsed):
                by_code.setdefault(code, []).append(i)
        snap = self.snapshots.current()
        if self.residency is not None:
            self.residency.govern(snap)
        # crash point: the batch is parsed, nothing executed — a failure
        # here must fail exactly this batch's caller and leave the engine
        # serving the next one
        faults.fire("serve.regions")
        # per-interval kernel outputs, scattered back to request order
        n = len(parsed)
        lo = np.zeros(n, np.int64)
        hi = np.zeros(n, np.int64)
        d_lo = np.zeros(n, np.int64)  # the delta's spans (zero without)
        d_hi = np.zeros(n, np.int64)
        level = np.zeros(n, np.int64)
        leaf = np.zeros(n, np.int64)
        indexes: dict[int, RegionParts | None] = {}
        mesh_spans = None
        if self.mesh is not None and not host_only:
            # ONE sharded stacked-BITS call for the whole panel (every
            # touched group answered on the device that owns it); a None
            # return or a missing code falls through to the per-group
            # path below — byte-identical either way
            with clock.span("regions.spans", n=n):
                mesh_spans = self.mesh.panel_spans(
                    snap,
                    {
                        code: interval_ops.clamped_queries(
                            [parsed[i][1] for i in idxs],
                            [parsed[i][2] for i in idxs],
                        )
                        for code, idxs in by_code.items()
                    },
                    lambda code: self._base_index(snap, code),
                )
        for code, idxs in by_code.items():
            t_group = time.perf_counter_ns()
            with clock.span("regions.spans", chrom=code, n=len(idxs)):
                starts = [parsed[i][1] for i in idxs]
                ends = [parsed[i][2] for i in idxs]
                parts = indexes[code] = self._region_parts(snap, code)
                if parts is None:
                    level[idxs], leaf[idxs] = interval_ops.bin_tokens_host(
                        starts, ends
                    )
                    continue
                self._touch_region(
                    snap.store.shards[code], min(starts), max(ends),
                    len(idxs),
                )
                if mesh_spans is not None and code in mesh_spans:
                    g_lo, g_hi, g_level, g_leaf = mesh_spans[code]
                    clock.device_groups += 1
                else:
                    g_lo, g_hi, g_level, g_leaf = self._interval_spans(
                        parts.base, code, starts, ends, host_only, clock
                    )
                lo[idxs], hi[idxs] = g_lo, g_hi
                d_lo[idxs], d_hi[idxs] = parts.delta_spans(starts, ends)
                level[idxs], leaf[idxs] = g_level, g_leaf
            # per-group sub-span onto the request's trace (no-op outside
            # an active trace): a panel's every interval shares the
            # request's trace id, and the group split is where device
            # time actually goes
            reqtrace.record_active(
                f"regions.chr{chromosome_label(code)}",
                t_group, time.perf_counter_ns(),
            )
        with clock.span("regions.rows", n=n):
            pages = self._region_pages(
                snap, parsed, indexes, lo, hi, d_lo, d_hi, level, leaf,
                min_cadd, max_conseq_rank, limit,
            )
        tokens = None
        if tokenize:
            # the PR-8 envelope now lives in export.tokens — the export
            # packer shares the exact field list and path renderer; a
            # span into the whole index is the base's and the delta's
            # summed (a row before the interval is in one or the other)
            tokens = build_region_tokens(
                snap.generation,
                [parsed[i][0] for i in range(n)],
                level, leaf, lo + d_lo, hi + d_hi,
                [indexes[parsed[i][0]] is not None for i in range(n)],
            )
        result = RegionsResult(pages, tokens, clock)
        self._regions_done(clock, n, result.returned)
        return result

    def _region_pages(self, snap, parsed, indexes, lo, hi, d_lo, d_hi,
                      level, leaf, min_cadd, max_conseq_rank,
                      limit) -> list:
        """One :class:`RegionPage` per parsed interval, request order:
        the rows of its spans located (and filtered), cut to ``limit``."""
        no_filters = min_cadd is None and max_conseq_rank is None
        take = None if limit is None else max(int(limit), 0)
        pages = []
        for i, (code, start, end) in enumerate(parsed):
            parts = indexes[code]
            shard = snap.store.shards.get(code)
            label = chromosome_label(code)
            if parts is None:
                si, jj = _NO_ROWS
                count = 0
            else:
                si, jj, count, _delta = self._span_rows(
                    shard, parts, int(lo[i]), int(hi[i]), int(d_lo[i]),
                    int(d_hi[i]), take, no_filters, min_cadd,
                    max_conseq_rank,
                )
            pages.append(RegionPage(
                shard, label, int(level[i]),
                closed_form_path(label, int(level[i]), int(leaf[i])),
                count, snap.generation, si, jj,
                f"{label}:{start}-{end}", None, paged=False,
            ))
        return pages

    def _span_rows(self, shard, parts: RegionParts, b_lo: int, b_hi: int,
                   d_lo: int, d_hi: int, take: int | None,
                   no_filters: bool, min_cadd, max_conseq_rank) -> tuple:
        """One interval's answer from its base span ``[b_lo, b_hi)`` and
        delta span ``[d_lo, d_hi)``: ``(si, jj, count, delta)`` — the
        first ``take`` matching rows (all of them for None), the
        post-filter count, and whether a delta row counted."""
        if no_filters:
            # the parts are deduplicated, so the span widths ARE the
            # post-filter count — take ONLY the rows that will render
            # (take=0 is the pure count-only mode: none)
            si, jj = parts.rows(b_lo, b_hi, d_lo, d_hi, take)
            return si, jj, (b_hi - b_lo) + (d_hi - d_lo), d_hi > d_lo
        # filters vectorize over the cached feature columns — never a
        # per-row sidecar parse (semantics pinned byte-identical to the
        # scalar _passes definition)
        b_sel = self._filter_span(shard, parts.base, b_lo, b_hi, min_cadd,
                                  max_conseq_rank)
        d_sel = _NO_SEL if d_hi <= d_lo else self._filter_span(
            shard, parts.delta, d_lo, d_hi, min_cadd, max_conseq_rank)
        count = int(b_sel.shape[0] + d_sel.shape[0])
        delta = d_sel.shape[0] > 0
        if take is not None:
            b_sel, d_sel = b_sel[:take], d_sel[:take]
        si, jj = parts.selected(b_sel, d_sel)
        if take is not None:
            si, jj = si[:take], jj[:take]
        return si, jj, count, delta

    @staticmethod
    def panel_clock() -> "_PanelClock":
        """A panel's clock, for a front end that times the body's parse on
        it (``regions.parse``) before it calls :meth:`regions_serve`."""
        return _PanelClock()

    def _regions_done(self, clock: "_PanelClock", intervals: int,
                      rows: int) -> None:
        """One answered panel's accounts, added once: the ``/stats``
        ``region_panels`` tallies and the three sub-stages the engine ran
        (summed over the panel's chromosome groups; the render is the
        renderer's to report: :meth:`regions_rendered`)."""
        tally = self.region_panels
        tally["panels"] += 1
        tally["intervals"] += intervals
        tally["device_groups"] += clock.device_groups
        tally["host_groups"] += clock.host_groups
        tally["transfers"] += clock.transfers
        tally["rows_rendered"] += rows
        if self._regions_hist is not None:
            for stage in ("regions.parse", "regions.spans", "regions.rows"):
                self._regions_hist[stage].observe(clock.ns[stage] / 1e9)

    def regions_rendered(self, clock: "_PanelClock", streamed: bool) -> None:
        """The panel's body has been rendered — buffered on the executor's
        thread or streamed chunk by chunk on the event loop's, each chunk
        inside ``clock.span("regions.render")``: one observation a
        panel, and the routes its rows took (``rows_batched`` +
        ``rows_scalar`` = the panel's ``rows_rendered``, unless a drain cut
        the body short), added once."""
        tally = self.region_panels
        tally["rows_batched"] += clock.batch_rows
        tally["rows_scalar"] += clock.scalar_rows
        if streamed:
            tally["streamed"] += 1
        if self._regions_hist is not None:
            self._regions_hist["regions.render"].observe(
                clock.ns["regions.render"] / 1e9
            )

    # -- analytics (the fused stats panel) -----------------------------------

    def stats_serve(self, specs: list, metrics=None,
                    windows: int | None = None,
                    host_only: bool = False) -> StatsResult:
        """On-device analytics over a batch of ``chr:start-end`` intervals:
        ONE fused kernel call per touched chromosome group answers the
        whole panel — per-interval row count, cohort-max allele-frequency
        spectrum + mean, CADD-phred histogram/mean/quantiles, and the
        consequence-rank rollup — over the generation's cached feature
        columns (memtable overlay rows ride the interval index, first-wins
        like every read path).

        ``metrics`` selects rendered sections (default all of
        ``ops.stats.STATS_METRICS``; the kernel always computes the full
        panel — selection is render-side, so one traced program serves
        every request shape).  ``windows=W`` adds the per-bin summary
        block: each interval subdivided into W equal windows with
        per-window row counts and CADD means (the segmented scan keyed on
        the interval spans).  ``host_only=True`` — or an open circuit
        breaker — pins the reductions to the byte-identical numpy twins.
        Grammar is validated up front: one bad spec fails the CALL with
        :class:`QueryError` (the bulk contract)."""
        if len(specs) > self.stats_max:
            raise QueryError(
                f"stats batch of {len(specs)} exceeds the "
                f"{self.stats_max}-interval cap (AVDB_SERVE_STATS_MAX); "
                "split the request"
            )
        if metrics is None:
            metrics = list(stats_ops.STATS_METRICS)
        else:
            if not isinstance(metrics, (list, tuple)) or not metrics or \
                    any(m not in stats_ops.STATS_METRICS for m in metrics):
                raise QueryError(
                    "stats metrics must be a non-empty subset of: "
                    + ", ".join(stats_ops.STATS_METRICS)
                )
            metrics = list(metrics)
        if windows is not None:
            windows = int(windows)
            if not 1 <= windows <= stats_ops.MAX_WINDOWS:
                raise QueryError(
                    f"stats windows must be in [1, {stats_ops.MAX_WINDOWS}]"
                )
        parsed = [parse_region(s) for s in specs]
        snap = self.snapshots.current()
        if self.residency is not None:
            self.residency.govern(snap)
        # crash point: the panel is parsed, nothing executed — a failure
        # here must fail exactly this request's caller (HTTP 500) and
        # leave the engine answering the next panel byte-identically
        faults.fire("serve.stats")
        by_code: dict[int, list[int]] = {}
        for i, (code, _s, _e) in enumerate(parsed):
            by_code.setdefault(code, []).append(i)
        entries: list = [None] * len(parsed)
        for code, idxs in by_code.items():
            t_group = time.perf_counter_ns()
            starts = [parsed[i][1] for i in idxs]
            ends = [parsed[i][2] for i in idxs]
            parts = self._region_parts(snap, code)
            if parts is None:
                # unloaded/empty chromosome: the zero-row reductions (the
                # host twin over empty columns keeps every shape exact)
                empty = np.empty(0, np.int32)
                panel = stats_ops.stats_panel_host(
                    empty, empty, empty, empty, starts, ends
                )
                wins = stats_ops.windowed_stats_host(
                    empty, empty, starts, ends, windows
                ) if windows is not None else None
            else:
                index = parts.base
                feats = self._stats_features(snap, code, index)
                panel = self._stats_panel(
                    code, index, feats, starts, ends, host_only
                )
                wins = self._stats_windows(
                    code, index, feats, starts, ends, windows, host_only
                ) if windows is not None else None
                if parts.delta is not None:
                    # every number of a panel is a sum over its rows: the
                    # delta's, on the host twin, add to the base's
                    delta = parts.delta
                    d_feats = self._stats_features(snap, code, delta)
                    panel = tuple(a + b for a, b in zip(
                        panel, stats_ops.stats_panel_host(
                            delta.pos, d_feats.af_fp, d_feats.cadd_fp,
                            d_feats.rank_i, starts, ends)))
                    if wins is not None:
                        wins = tuple(a + b for a, b in zip(
                            wins, stats_ops.windowed_stats_host(
                                delta.pos, d_feats.cadd_fp, starts, ends,
                                windows)))
            lo, hi, af_l, af_h, c_l, c_h, rk = panel
            for k, i in enumerate(idxs):
                block = stats_ops.windows_summary(
                    wins[0][k], wins[1][k], wins[2][k]
                ) if wins is not None else None
                code_i, start, end = parsed[i]
                entries[i] = {
                    "region": f"{chromosome_label(code_i)}:{start}-{end}",
                    **stats_ops.interval_summary(
                        int(hi[k] - lo[k]), af_l[k], af_h[k], c_l[k],
                        c_h[k], rk[k], metrics, block,
                    ),
                }
            # per-group sub-span onto the request's trace (no-op outside
            # an active trace) — the group split is where device time goes
            reqtrace.record_active(
                f"stats.chr{chromosome_label(code)}",
                t_group, time.perf_counter_ns(),
            )
        return StatsResult(snap.generation, metrics, entries)

    def _stats_features(self, snap, code: int,
                        index: IntervalIndex) -> StatsColumns:
        """``index``'s feature columns (``snap``'s chromosome ``code``)."""
        return self._features(snap.store.shards.get(code), index)

    def _features(self, shard, index: IntervalIndex) -> StatsColumns:
        """``index``'s feature columns, decoded lazily and kept on it (a
        base's outlive the generations its deltas come and go in) —
        decodes coalesce under the index's build lock (a decode is a
        full-column sidecar walk; N concurrent misses must not pay it N
        times)."""
        if index.feats is not None:
            return index.feats
        lock = self._delta_build_lock if isinstance(index, DeltaIndex) \
            else self._index_build_lock
        with lock:
            if index.feats is None:
                index.feats = StatsColumns.build(shard, index)
        return index.feats

    def _filter_span(self, shard, index: IntervalIndex, i_lo: int,
                     i_hi: int, min_cadd, max_conseq_rank):
        """Index positions of ``[i_lo, i_hi)`` passing the annotation
        filters — one vectorized compare over the cached feature columns
        instead of a JSON decode per row per request.  NaN (missing
        annotation) never satisfies a predicate, exactly like the scalar
        :meth:`_passes` definition (the reference's
        ``WHERE (col->>'x')::numeric`` NULL semantics)."""
        feats = self._features(shard, index)
        keep = np.ones(i_hi - i_lo, bool)
        with np.errstate(invalid="ignore"):  # NaN compares are the point
            if min_cadd is not None:
                keep &= feats.cadd_f[i_lo:i_hi] >= min_cadd
            if max_conseq_rank is not None:
                keep &= feats.rank_f[i_lo:i_hi] <= max_conseq_rank
        return np.nonzero(keep)[0] + i_lo

    def _device_stats(self, index: IntervalIndex, feats: StatsColumns,
                      starts, ends):
        """One fused stats-panel kernel call on device (test seam:
        monkeypatch to model a failing device)."""
        af, cadd, rank = feats.device()
        return stats_ops.stats_panel(
            self._device_pos(index), af, cadd, rank, starts, ends,
            padded=True
        )

    def _device_windows(self, index: IntervalIndex, feats: StatsColumns,
                        starts, ends, windows: int):
        """One windowed-scan kernel call on device (test seam)."""
        _af, cadd, _rank = feats.device()
        return stats_ops.windowed_stats(
            self._device_pos(index), cadd, starts, ends, windows,
            padded=True
        )

    def _stats_panel(self, code: int, index: IntervalIndex,
                     feats: StatsColumns, starts, ends, host_only: bool):
        """The fused panel for one group (breaker-guarded device
        dispatch; byte-identical host twin otherwise)."""
        return self._stats_guarded(
            code, index, feats, len(starts), host_only,
            lambda: self._device_stats(index, feats, starts, ends),
            lambda: stats_ops.stats_panel_host(
                index.pos, feats.af_fp, feats.cadd_fp, feats.rank_i,
                starts, ends,
            ),
        )

    def _stats_windows(self, code: int, index: IntervalIndex,
                       feats: StatsColumns, starts, ends, windows: int,
                       host_only: bool):
        """The windowed scan for one group (same guard)."""
        return self._stats_guarded(
            code, index, feats, len(starts), host_only,
            lambda: self._device_windows(index, feats, starts, ends,
                                         windows),
            lambda: stats_ops.windowed_stats_host(
                index.pos, feats.cadd_fp, starts, ends, windows
            ),
        )

    def _stats_guarded(self, code: int, index: IntervalIndex,
                       feats: StatsColumns, n_queries: int,
                       host_only: bool, device_fn, host_fn):
        """The ONE stats device-dispatch guard: the kernel runs when the
        batch is worth a dispatch and the group's circuit breaker allows
        it, the byte-identical numpy twin otherwise.  A device failure
        feeds the breaker and drops BOTH retained device copies (index
        position array + feature columns) with their ledger entries —
        one failure path to maintain, not one per kernel."""
        breaker = self.breaker
        if (not host_only and index.n
                and n_queries >= self.stats_device_min
                and (breaker is None or breaker.allow_device(code))):
            try:
                out = device_fn()
            except Exception as exc:
                index.drop_device()
                feats.drop_device()
                with self._cache_lock:
                    self._index_device.pop(id(index), None)
                    self._index_device.pop(id(feats), None)
                if breaker is not None:
                    breaker.record_failure(code, exc)
            else:
                if breaker is not None:
                    breaker.record_success(code)
                self._note_index_device(index)
                self._note_index_device(feats)
                return out
        return host_fn()

    #: distinct in-flight cursor walks whose match lists stay cached
    #: (two compact int64 arrays per walk, LRU; stale generations age out)
    WALK_CACHE = 8

    def _region_page(self, snap, code, start, end,
                     min_cadd, max_conseq_rank, limit,
                     cursor: str | None, host_only: bool = False,
                     clock: "_RegionClock | None" = None) -> RegionPage:
        if clock is None:
            clock = _RegionClock()
        label = chromosome_label(code)
        level, leaf = _region_bin(start, end)
        shard = snap.store.shards.get(code)
        t_page = time.perf_counter_ns()
        paged = cursor is not None
        wkey = hit = None
        if paged:
            wkey = (snap.generation, code, start, end,
                    min_cadd, max_conseq_rank)
            with self._cache_lock:
                hit = self._walk_cache.get(wkey)
                if hit is not None:
                    self._walk_cache.move_to_end(wkey)
        if hit is None:
            with clock.span("region.index", chrom=code):
                parts = self._region_parts(snap, code)
        with clock.span("region.locate", chrom=code):
            count = 0
            if hit is None:
                si, jj = _NO_ROWS  # (segment index, local row) of each match
                took_delta = False
                if parts is not None:
                    self._touch_region(shard, start, end, 1)
                    # the single-region route rides the SAME interval-index
                    # + BITS-span machinery as the batch API (one query is
                    # just a panel of one); the breaker/host_only fallback
                    # is byte-identical
                    lo, hi, _lvl, _leaf = self._interval_spans(
                        parts.base, code, [start], [end], host_only
                    )
                    d_lo, d_hi = parts.delta_spans([start], [end])
                    # unpaged: only the rows that will render; a cursor
                    # walk takes every match once, for all its pages
                    si, jj, count, took_delta = self._span_rows(
                        shard, parts, int(lo[0]), int(hi[0]),
                        int(d_lo[0]), int(d_hi[0]),
                        None if paged or limit is None
                        else max(int(limit), 0),
                        min_cadd is None and max_conseq_rank is None,
                        min_cadd, max_conseq_rank,
                    )
                if paged:
                    # without this an N-page walk re-runs the full region
                    # scan + filter pass per page (O(N x region) for what
                    # the client sees as keyset pagination); copies, so a
                    # cached walk never pins a stale generation's whole
                    # index
                    hit = (si.copy(), jj.copy(), took_delta)
                    with self._cache_lock:
                        self._walk_cache[wkey] = hit
                        while len(self._walk_cache) > self.WALK_CACHE:
                            self._walk_cache.popitem(last=False)
            clock.delta = hit[2] if paged else took_delta
            next_token = None
            if paged:
                total = count = int(hit[0].shape[0])
                ckey = _cursor_key(code, start, end, min_cadd,
                                   max_conseq_rank)
                offset = decode_cursor(cursor, ckey)
                stop = total if limit is None \
                    else min(offset + max(int(limit), 0), total)
                # a page must ADVANCE to mint a continuation (limit=0
                # count-only pages would otherwise hand back a
                # self-referential token and loop a cursor-following
                # client forever)
                if stop < total and stop > offset:
                    next_token = encode_cursor(snap.generation, stop, ckey)
                si, jj = hit[0][offset:stop], hit[1][offset:stop]
        # page sub-span: every page of a cursor walk attributes its scan
        # to the walking request's trace id (no-op untraced)
        reqtrace.record_active(f"region.chr{label}", t_page,
                               time.perf_counter_ns())
        return RegionPage(
            shard, label, level, closed_form_path(label, level, leaf),
            count, snap.generation, si, jj, f"{label}:{start}-{end}",
            next_token, paged=paged,
        )

    # -- interval index (the BITS search database) ---------------------------

    def _region_parts(self, snap, code: int) -> RegionParts | None:
        """The (generation, chromosome) :class:`RegionParts`, made lazily
        and LRU-retained; ``None`` when the chromosome is unloaded or
        empty.  The base is the retained one whose segments begin the
        shard's — sorted here only when none does, a request's build
        (``request_builds``); the delta of the segments after it is built
        here once per generation.  Stale generations age out of the cap
        like every other generation-keyed cache here."""
        shard = snap.store.shards.get(code)
        if shard is None or not shard.n:
            return None
        key = (snap.generation, code)
        with self._cache_lock:
            parts = self._parts_cache.get(key)
            if parts is not None:
                self._parts_cache.move_to_end(key)
                return parts
        base = self._base_of(code, shard, request=True)
        if len(base.segs) == len(shard.segments):
            parts = RegionParts(base, None)
        else:
            with self._delta_build_lock:
                # double-checked: concurrent readers of a fresh generation
                # build its delta once
                with self._cache_lock:
                    parts = self._parts_cache.get(key)
                if parts is not None:
                    return parts
                delta = DeltaIndex.build(shard, base)
                self.delta_builds += 1
                parts = RegionParts(base, delta if delta.n else None)
                self._install_parts(key, code, parts)
            return parts
        self._install_parts(key, code, parts)
        return parts

    def _install_parts(self, key: tuple, code: int,
                       parts: RegionParts) -> None:
        with self._cache_lock:
            self._parts_cache[key] = parts
            self._delta_rows[code] = \
                0 if parts.delta is None else parts.delta.n
            while len(self._parts_cache) > self.PARTS_CACHE:
                self._parts_cache.popitem(last=False)

    def _base_index(self, snap, code: int) -> IntervalIndex | None:
        """The chromosome's base index in ``snap`` (the mesh's stacked
        search covers the bases; the deltas stay on the host)."""
        parts = self._region_parts(snap, code)
        return None if parts is None else parts.base

    def _interval_index(self, snap, code: int) -> IntervalIndex | None:
        """One index over the whole (generation, chromosome) — the base
        itself when no delta stands beside it — for a consumer that walks
        an index's arrays directly (``export/stream``); ``None`` when the
        chromosome is unloaded or empty.  Beside a delta that is a
        whole-chromosome fold on the caller's thread, counted as a
        request's build."""
        parts = self._region_parts(snap, code)
        if parts is None:
            return None
        if parts.delta is not None and parts._whole is None:
            self.index_builds += 1
            self.request_builds += 1
        return parts.whole()

    def _cached_base(self, code: int, segs) -> IntervalIndex | None:
        """The retained base whose segments are the longest run that
        ``segs`` begins with (caller holds ``self._cache_lock``)."""
        best = None
        for (c, _ids), base in self._index_cache.items():  # avdb: noqa[AVDB201] -- helper invoked only under self._cache_lock (every call site holds it)
            k = len(base.segs)
            if c != code or k > len(segs) \
                    or (best is not None and k <= len(best.segs)):
                continue
            if all(a is b for a, b in zip(base.segs, segs)):
                best = base
        if best is not None:
            self._index_cache.move_to_end(  # avdb: noqa[AVDB201] -- helper invoked only under self._cache_lock
                (code, tuple(id(seg) for seg in best.segs)))
        return best

    def _base_of(self, code: int, shard, request: bool) -> IntervalIndex:
        """``shard``'s base index: the retained one, or built here over
        the segments the store itself holds (an overlay's memtable
        segments are the delta's) — by a request that found none, or
        ahead of requests by :meth:`warm_region_index`."""
        with self._cache_lock:
            base = self._cached_base(code, shard.segments)
        if base is not None:
            return base
        with self._index_build_lock:
            # double-checked: the winner of the race built it while this
            # thread waited — take the cached entry instead of paying a
            # duplicate full-chromosome sort
            with self._cache_lock:
                base = self._cached_base(code, shard.segments)
            if base is not None:
                return base
            base = IntervalIndex.build(
                shard, getattr(shard, "overlay_base", None))
            self.index_builds += 1
            self.base_builds += 1
            if request:
                self.request_builds += 1
            self._install_base(code, base)
        return base

    def _install_base(self, code: int, base: IntervalIndex) -> None:
        """Retain ``base``: the LRU keeps ``INDEX_CACHE`` bases over all
        chromosomes and ``BASES_PER_CHROMOSOME`` of each; an evicted base
        leaves the device-byte ledger with its copies (caller holds
        ``self._index_build_lock``)."""
        evicted: list = []
        with self._cache_lock:
            self._index_cache[(code, tuple(id(seg) for seg in base.segs))] \
                = base
            same = [key for key in self._index_cache if key[0] == code]
            for key in same[:-self.BASES_PER_CHROMOSOME]:
                evicted.append(self._index_cache.pop(key))
            while len(self._index_cache) > self.INDEX_CACHE:
                evicted.append(self._index_cache.popitem(last=False)[1])
            # the device-byte ledger must not keep an evicted index (and
            # its HBM copies) alive behind the cache's back
            held = [obj for old in evicted for obj in (old, old.feats)
                    if obj is not None
                    and self._index_device.pop(id(obj), None) is not None]
        for obj in held:
            obj.drop_device()

    def prepare_region_bases(self, store) -> None:
        """Fold the segments a store refresh adds into each retained base
        they extend, before the refreshed generation is pinned — on the
        refreshing thread (a memtable flush's, whoever refreshes after a
        loader commit), never a request's: the rows the refresh added are
        merged into the base in one O(n) pass (:meth:`IntervalIndex.fold`,
        no sort), a base with a device copy gets the folded one uploaded
        and warmed at every span shape, and its feature columns fold too.
        Until the new generation is pinned, reads keep the old base and a
        delta that holds the added rows.  A chromosome whose base no
        retained one begins (a compaction rewrote it) is left to the
        residency warm or a request."""
        for code, shard in store.shards.items():
            segs = shard.segments
            with self._cache_lock:
                base = self._cached_base(code, segs)
            if base is None or len(base.segs) == len(segs):
                continue
            with self._index_build_lock, annotation(
                    "avdb.regions.fold", chrom=code, rows=shard.n):
                delta = DeltaIndex.build(shard, base)
                if base.feats is not None:
                    delta.feats = StatsColumns.build(shard, delta)
                folded = base.fold(delta, tuple(segs))
                if base._dev_pos is not None:
                    dev = folded.upload()
                    self.index_uploads += 1
                    if base.warmed:
                        interval_ops.warm_spans(
                            dev, self.regions_device_min, self.regions_max
                        )
                    folded._dev_pos = dev
                    folded.warmed = base.warmed
                if folded.feats is not None and base.feats._dev is not None:
                    folded.feats.device()
                self.index_builds += 1
                self.flush_builds += 1
                self._install_base(code, folded)
            self._note_index_device(folded)
            if folded.feats is not None:
                self._note_index_device(folded.feats)

    def warm_region_index(self, generation: int, code: int, shard) -> None:
        """Make ``shard``'s base interval index ready for panels before
        any asks: build it, upload its position array, run the span
        program once at every query shape a panel's group can take
        (``interval_ops.span_query_shapes`` of this engine's knobs)
        against that copy, and only then install it.  The residency
        manager's uploader calls this for each segment it has uploaded,
        before the segment counts as resident — never inside a request
        (``generation`` names the governed one; a base outlives it).
        The copy comes out of ``INDEX_DEVICE_BYTES`` like a lazily
        uploaded one."""
        index = self._base_of(code, shard, request=False)
        if index.warmed or not index.n:
            return
        with annotation("avdb.regions.warm", chrom=code, rows=index.n):
            dev = index._dev_pos
            if dev is None:
                dev = index.upload()
                self.index_uploads += 1
            interval_ops.warm_spans(
                dev, self.regions_device_min, self.regions_max
            )
            index._dev_pos = dev
            index.warmed = True
        self._note_index_device(index)

    def region_index_stats(self) -> dict:
        """``/stats`` ``region_index``: of the chromosomes that hold a
        residency candidate segment in the governed generation
        (``candidates``), how many have their base interval index
        ``built`` and how many have it on the ``device``, uploaded and
        probed by every warmed shape — a server is ready for region reads
        when ``device`` equals ``candidates``; the builds, uploads and
        deltas this process has made (``__init__`` says which is which),
        and ``delta_rows``, the rows of each chromosome's newest delta,
        summed."""
        _generation, codes = (
            self.residency.candidate_chromosomes()
            if self.residency is not None else (None, ())
        )
        shards = self.snapshots.current().store.shards if codes else {}
        with self._cache_lock:
            held = [self._cached_base(code, shards[code].segments)
                    if code in shards else None for code in codes]
            delta_rows = sum(self._delta_rows.values())
        return {
            "candidates": len(held),
            "built": sum(index is not None for index in held),
            "device": sum(index is not None and index.warmed
                          for index in held),
            "builds": self.index_builds,
            "uploads": self.index_uploads,
            "base_builds": self.base_builds,
            "flush_builds": self.flush_builds,
            "request_builds": self.request_builds,
            "delta_builds": self.delta_builds,
            "delta_rows": delta_rows,
        }

    def _touch_region(self, shard, start: int, end: int, n: int) -> None:
        """A region read's window feeds the residency manager's heat like
        a probe's: ``n`` intervals between ``start`` and ``end`` touch the
        segments whose key range they overlap, so a server that is asked
        for regions holds those segments — and, through
        :meth:`warm_region_index`, their interval indexes — on the
        device."""
        if self.residency is None:
            return
        top = interval_ops.MAX_QUERY_POS
        self.residency.touch_window(
            shard,
            np.uint64(min(max(int(start), 0), top)) << np.uint64(32),
            (np.uint64(min(max(int(end), 0), top)) << np.uint64(32))
            | np.uint64(0xFFFFFFFF),
            n,
        )

    def _device_spans(self, index: IntervalIndex, starts, ends):
        """One batched BITS kernel call (test seam: monkeypatch to model
        a failing device)."""
        return interval_ops.interval_spans(
            self._device_pos(index), starts, ends, pos_padded=True
        )

    def _device_pos(self, index: IntervalIndex):
        """``index``'s position array on the device; an upload it takes is
        counted (``region_index.uploads``)."""
        if index._dev_pos is None:
            index.device_pos()
            self.index_uploads += 1
        return index._dev_pos

    def _interval_spans(self, index: IntervalIndex, code: int,
                        starts, ends, host_only: bool = False,
                        clock: "_PanelClock | None" = None):
        """(lo, hi, level, leaf) per query interval — the device kernel
        when the batch is worth a dispatch and the group's circuit
        breaker allows it, the byte-identical numpy twin otherwise.  A
        device failure feeds the breaker (so a sick device stops being
        attempted per panel) and falls back host-side: correct bytes
        either way, the serving contract.  A panel's ``clock`` is told
        who answered the group."""
        breaker = self.breaker
        if (not host_only and index.n
                and len(starts) >= self.regions_device_min
                and (breaker is None or breaker.allow_device(code))):
            try:
                out = self._device_spans(index, starts, ends)
            except Exception as exc:
                index.drop_device()
                with self._cache_lock:
                    self._index_device.pop(id(index), None)
                if breaker is not None:
                    breaker.record_failure(code, exc)
            else:
                if breaker is not None:
                    breaker.record_success(code)
                self._note_index_device(index)
                if clock is not None:
                    clock.device_groups += 1
                    clock.transfers += interval_ops.SPAN_TRANSFERS
                return out
        if clock is not None:
            clock.host_groups += 1
        return interval_ops.interval_spans_host(index.pos, starts, ends)

    def _note_index_device(self, index) -> None:
        """Account a retained device copy — an :class:`IntervalIndex`
        position array OR a :class:`StatsColumns` feature set (both
        expose ``device_bytes``/``drop_device``) — against
        ``INDEX_DEVICE_BYTES``, evicting the least-recently-used copies
        past the ceiling (the just-used entry always stays)."""
        nbytes = index.device_bytes()
        if not nbytes:
            return
        evicted: list = []
        with self._cache_lock:
            self._index_device[id(index)] = (index, nbytes)
            self._index_device.move_to_end(id(index))
            total = sum(b for _i, b in self._index_device.values())
            while total > self.INDEX_DEVICE_BYTES \
                    and len(self._index_device) > 1:
                _key, (old, b) = self._index_device.popitem(last=False)
                evicted.append(old)
                total -= b
        for old in evicted:  # the device free happens off-lock
            old.drop_device()

    @staticmethod
    def _region_rows(shard, start: int, end: int) -> list:
        """(segment index, local row) of every region row, position-sorted,
        duplicates resolved oldest-segment-first (the store's lookup
        policy).  Per segment this is two ``searchsorted`` calls — rows are
        (pos, hash)-sorted, so the position column is directly sliceable —
        then one global lexsort over only the in-region rows.  This is the
        ONE definition of the region dedup policy: serving traffic reads
        it through the :class:`IntervalIndex` built from a full-span call
        (collision case) or its vectorized equivalent (fast path)."""
        pos_parts, h_parts, si_parts, j_parts = [], [], [], []
        for si, seg in enumerate(shard.segments):
            if seg.n == 0:
                continue
            p = seg.cols["pos"]
            lo = int(np.searchsorted(p, start, side="left"))
            hi = int(np.searchsorted(p, end, side="right"))
            if hi <= lo:
                continue
            pos_parts.append(p[lo:hi])
            h_parts.append(seg.cols["h"][lo:hi])
            si_parts.append(np.full(hi - lo, si, np.int32))
            j_parts.append(np.arange(lo, hi, dtype=np.int64))
        if not pos_parts:
            return []
        pos = np.concatenate(pos_parts)
        h = np.concatenate(h_parts)
        si = np.concatenate(si_parts)
        jj = np.concatenate(j_parts)
        order = np.lexsort((si, h, pos))
        # fast path: no adjacent (pos, hash) collision in sorted order means
        # no duplicates are POSSIBLE — skip the per-row identity compare
        # (the dominant serving case: loader-deduplicated stores)
        ps, hs = pos[order], h[order]
        if not bool(np.any((ps[1:] == ps[:-1]) & (hs[1:] == hs[:-1]))):
            return [(int(si[t]), int(jj[t])) for t in order]
        kept: list[tuple[int, int]] = []
        run_key = None
        run_seen: list = []  # identities emitted for the current (pos, h)
        for t in order:
            key = (int(pos[t]), int(h[t]))
            if key != run_key:
                run_key, run_seen = key, []
            seg = shard.segments[int(si[t])]
            j = int(jj[t])
            ident = (
                int(seg.cols["ref_len"][j]), int(seg.cols["alt_len"][j]),
                seg.ref[j].tobytes(), seg.alt[j].tobytes(),
            )
            if ident in run_seen:  # shadowed duplicate in a newer segment
                continue
            run_seen.append(ident)
            kept.append((int(si[t]), j))
        return kept

    @staticmethod
    def _passes(seg, j: int, min_cadd, max_conseq_rank) -> bool:
        """Annotation filters: rows lacking the filtered annotation drop
        (matching the reference's ``WHERE (col->>'x')::numeric`` SQL, where
        a NULL column never satisfies the predicate)."""
        if min_cadd is not None:
            phred = _ann_number(seg, j, "cadd_scores", "CADD_phred")
            if phred is None or phred < min_cadd:
                return False
        if max_conseq_rank is not None:
            rank = _ann_number(
                seg, j, "adsp_most_severe_consequence", "rank"
            )
            if rank is None or rank > max_conseq_rank:
                return False
        return True

    # -- region LRU ---------------------------------------------------------

    def _cache_get(self, key):
        if not self.region_cache_size:
            return None
        with self._cache_lock:
            text = self._region_cache.get(key)
            if text is not None:
                self._region_cache.move_to_end(key)
        counter = self._cache_hits if text is not None else self._cache_misses
        if counter is not None:
            counter.inc()
        return text

    def _cache_put(self, key, text: str) -> None:
        if not self.region_cache_size:
            return
        with self._cache_lock:
            self._region_cache[key] = text
            self._region_cache.move_to_end(key)
            # stale-generation entries age out with everything else — the
            # cap bounds them, and their keys can never be probed again
            while len(self._region_cache) > self.region_cache_size:
                self._region_cache.popitem(last=False)
