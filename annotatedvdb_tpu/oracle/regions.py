"""Scalar region panel: the plain reference of ``POST /regions``.

For each ``chr:start-end`` of a panel: a linear scan of every stored row of
that chromosome, ``start <= pos <= end`` (1-based, inclusive on POS — the
reference's region scan matches on the position column); a repeated
identity is answered once, by the oldest segment that holds it
(first-wins, the store's lookup policy); rows are ordered by position and,
at one position, by the stored identity hash and then by segment age —
``limit`` cuts inside a position, so that order is part of the answer;
``count`` is the uncut number of rows, ``returned`` the number shown;
``bin_level`` / ``bin_index`` are the interval's deepest enclosing bin
(:mod:`annotatedvdb_tpu.oracle.binindex`).

No index, no search, no kernel, no cache: what the serving path
(``serve.engine.regions_serve``: interval index, BITS span kernel, pages,
buffered or streamed rendering) must equal byte for byte.  A row's JSON
text is the caller's ``render_row(shard, code, global row id)`` — the
record renderer is the one thing the two sides share
(``serve.engine.render_variant``, whose scalar ``_render_row`` is every
renderer's definition: the serving path renders a panel's rows in
columnar blocks, ``serve.engine.render_located``, held to those bytes).
"""

from __future__ import annotations

import json

from annotatedvdb_tpu.oracle.binindex import closed_form_bin, closed_form_path
from annotatedvdb_tpu.types import chromosome_code, chromosome_label


def parse_spec(spec: str) -> tuple[int, int, int]:
    """``chr:start-end`` -> (chromosome code, start, end)."""
    chrom, _, span = spec.partition(":")
    start, _, end = span.partition("-")
    return chromosome_code(chrom), int(start), int(end)


def region_rows(shard, start: int, end: int) -> list[int]:
    """Global row ids of the rows a region holds, in answer order."""
    starts = shard._starts()
    hits = []
    for si, seg in enumerate(shard.segments):
        pos, h = seg.cols["pos"], seg.cols["h"]
        for j in range(seg.n):
            p = int(pos[j])
            if start <= p <= end:
                hits.append((p, int(h[j]), si, int(starts[si]) + j))
    hits.sort()
    kept, seen = [], set()
    for p, h, _si, gid in hits:
        ident = (p, h) + tuple(shard.alleles(gid))
        if ident not in seen:  # a newer segment's copy is shadowed
            seen.add(ident)
            kept.append(gid)
    return kept


def region_envelope(store, generation: int, spec: str, limit: int | None,
                    render_row) -> str:
    """One interval's envelope as JSON text."""
    code, start, end = parse_spec(spec)
    label = chromosome_label(code)
    level, leaf = closed_form_bin(start, end)
    shard = store.shards.get(code)
    kept = region_rows(shard, start, end) if shard is not None else []
    shown = kept if limit is None else kept[:max(int(limit), 0)]
    rows = [render_row(shard, code, gid) for gid in shown]
    return (
        f'{{"region":{json.dumps(f"{label}:{start}-{end}")}'
        f',"bin_level":{level}'
        f',"bin_index":{json.dumps(closed_form_path(label, level, leaf))}'
        f',"count":{len(kept)}'
        f',"returned":{len(rows)}'
        f',"generation":{generation}'
        ',"variants":[' + ",".join(rows) + "]}"
    )


def region_panel(store, generation: int, specs: list, limit: int | None,
                 render_row) -> str:
    """The whole ``POST /regions`` body: every interval's envelope, in
    request order."""
    return (
        f'{{"n":{len(specs)},"results":['
        + ",".join(region_envelope(store, generation, spec, limit,
                                   render_row) for spec in specs)
        + "]}"
    )
