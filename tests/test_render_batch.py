"""The columnar row renderer against the scalar one.

``engine.render_rows`` renders a chromosome group's found rows in one pass
per touched segment, and ``engine.render_located`` renders a region
answer's rows the same way, a block at a time (``RegionPage.rows``,
``RegionsResult.rows``); ``engine._render_row`` stays the definition (it
also renders rows that keep host strings, and a lone row).  Parity here is
byte for byte on hand-built segments that hold every hazard the two could
disagree on; the bulk allele decode is held to ``segment_alleles`` (the
definition the export coder shares); and the cached-batch path of
``lookup_many`` (two lock holds a chromosome group) is held to the per-id
loop it replaced — a reference model of that loop lives in this file — on
answers, tallies, LRU content and byte tally.
"""

from __future__ import annotations

import json
import threading
import urllib.request
from collections import OrderedDict

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.obs.metrics import MetricsRegistry
from annotatedvdb_tpu.serve import QueryEngine, StaticSnapshots
from annotatedvdb_tpu.serve import engine as engine_mod
from annotatedvdb_tpu.serve.engine import (
    RegionPage,
    RegionsResult,
    _LookupClock,
    _PanelClock,
    _render_row,
    decode_allele_rows,
    render_rows,
    render_variant,
    segment_alleles,
)
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store.variant_store import RawJson, Segment
from annotatedvdb_tpu.types import chromosome_label, encode_allele_array
from conftest import start_server, stop_server

WIDTH = 8
CODE = 8

#: one row per hazard; ``tag`` names it for the parametrised cases.  Three
#: segments (positions disjoint, so global ids run segment by segment).
ROWS = [
    # -- segment 0
    dict(tag="snv", pos=1000, ref="A", alt="G", rs=11, adsp=1),
    dict(tag="insertion", pos=1010, ref="C", alt="CTTG", rs=-1, adsp=0),
    dict(tag="deletion", pos=1020, ref="GACT", alt="G", rs=12, adsp=-1),
    dict(tag="multi_allelic", pos=1030, ref="T", alt="C", rs=13, multi=True),
    dict(tag="ref_snp_null", pos=1040, ref="T", alt="A", rs=-1, adsp=1),
    dict(tag="at_width", pos=1050, ref="ACGTACGT", alt="TGCATGCA", rs=14),
    # -- segment 1
    dict(tag="adsp_null", pos=20_000, ref="G", alt="T", rs=21, adsp=-1),
    dict(tag="adsp_false", pos=20_010, ref="G", alt="C", rs=22, adsp=0),
    dict(tag="adsp_true", pos=20_020, ref="G", alt="A", rs=23, adsp=1),
    dict(tag="long_digest", pos=20_030, ref="A" * 20, alt="G", rs=24,
         digest="8:20030:DIGESTabc123:rs24"),
    dict(tag="long_no_digest", pos=20_040, ref="C", alt="T" * 11, rs=-1),
    dict(tag="digest_only", pos=20_050, ref="CA", alt="C", rs=-1,
         digest="8:20050:DIGESTonly"),
    # -- segment 2
    dict(tag="ann_raw", pos=3_000_000, ref="A", alt="C", rs=-1,
         ann={"vep_output": RawJson('{"input": "8:3000000",  "n":1}')}),
    dict(tag="ann_dict", pos=3_000_010, ref="A", alt="T", rs=31,
         ann={"cadd_scores": {"CADD_phred": 12.5, "CADD_raw_score": 1.25}}),
    dict(tag="ann_two", pos=3_000_020, ref="AT", alt="A", rs=32,
         ann={"allele_frequencies": RawJson('{"GnomAD":{"af":0.0123}}'),
              "adsp_most_severe_consequence":
                  {"conseq": "missense_variant", "rank": 7},
              "other_annotation": {"note": "café \"quoted\""}}),
    dict(tag="ann_none", pos=3_000_030, ref="C", alt="G", rs=33),
    dict(tag="level_0", pos=63_999_999, ref="ACGT", alt="A", rs=-1),
]
SEGMENT_OF = [0] * 6 + [1] * 6 + [2] * 5
GID = {row["tag"]: gid for gid, row in enumerate(ROWS)}


def _segment(rows, retain: bool = True) -> Segment:
    from annotatedvdb_tpu.oracle.binindex import closed_form_bin

    refs = [r["ref"] for r in rows]
    alts = [r["alt"] for r in rows]
    ref, ref_len = encode_allele_array(refs, WIDTH)
    alt, alt_len = encode_allele_array(alts, WIDTH)
    bins = [closed_form_bin(r["pos"], r["pos"] + len(r["ref"]) - 1)
            for r in rows]
    cols = {
        "pos": np.asarray([r["pos"] for r in rows], np.int32),
        # ascending, so build() keeps the rows in the order given
        "h": np.arange(len(rows), dtype=np.uint32),
        "ref_len": ref_len, "alt_len": alt_len,
        "ref_snp": np.asarray([r["rs"] for r in rows], np.int64),
        "is_multi_allelic": np.asarray(
            [r.get("multi", False) for r in rows], np.bool_),
        "is_adsp_variant": np.asarray(
            [r.get("adsp", -1) for r in rows], np.int8),
        "bin_level": np.asarray([b[0] for b in bins], np.int8),
        "leaf_bin": np.asarray([b[1] for b in bins], np.int32),
    }
    names = {c for r in rows for c in r.get("ann", {})}
    ann = {c: [r.get("ann", {}).get(c) for r in rows] for c in names}
    long_alleles = [
        (r["ref"], r["alt"])
        if retain and max(len(r["ref"]), len(r["alt"])) > WIDTH else None
        for r in rows
    ]
    return Segment.build(cols, ref, alt, annotations=ann,
                         digest_pk=[r.get("digest") for r in rows],
                         long_alleles=long_alleles)


@pytest.fixture(scope="module")
def shard():
    store = VariantStore(width=WIDTH)
    shard = store.shard(CODE)
    for s in range(3):
        shard.append_segment(_segment(
            [r for r, at in zip(ROWS, SEGMENT_OF) if at == s]))
    shard._starts_cache = None
    assert len(shard.segments) == 3 and shard.n == len(ROWS)
    return shard


def _scalar(shard, gids) -> list:
    label = chromosome_label(CODE)
    out = []
    for gid in gids:
        seg, j = shard.locate_row(gid)
        out.append(_render_row(seg, j, label, shard.width))
    return out


PLAIN = GID["snv"]  # a second row, so a one-hazard case still runs columnar

#: case -> (gids, rows expected through the scalar renderer)
CASES = {
    "snv": ([GID["snv"], GID["ann_none"]], 0),
    "insertion": ([GID["insertion"], PLAIN], 0),
    "deletion": ([GID["deletion"], PLAIN], 0),
    "multi_allelic": ([GID["multi_allelic"], PLAIN], 0),
    "ref_snp_set": ([GID["adsp_true"], PLAIN], 0),
    "ref_snp_null": ([GID["ref_snp_null"], PLAIN], 0),
    "adsp_null": ([GID["adsp_null"], GID["adsp_false"]], 0),
    "adsp_false": ([GID["adsp_false"], GID["adsp_true"]], 0),
    "adsp_true": ([GID["adsp_true"], GID["adsp_null"]], 0),
    "allele_at_width": ([GID["at_width"], PLAIN], 0),
    "long_allele_with_digest_pk": ([GID["long_digest"], GID["adsp_null"]], 1),
    "long_allele_literal_pk": ([GID["adsp_null"], GID["long_no_digest"]], 1),
    "digest_pk_on_short_alleles": ([GID["digest_only"], GID["adsp_true"]], 1),
    "only_scalar_rows": ([GID["long_digest"], GID["digest_only"]], 2),
    "annotation_rawjson": ([GID["ann_raw"], GID["ann_none"]], 0),
    "annotation_dict": ([GID["ann_dict"], GID["ann_none"]], 0),
    "annotations_in_three_columns": ([GID["ann_two"], GID["ann_raw"]], 0),
    "annotations_none": ([GID["ann_none"], GID["level_0"]], 0),
    "segment_without_annotation_columns": ([GID["snv"], GID["deletion"]], 0),
    "bin_level_0": ([GID["level_0"], GID["ann_dict"]], 0),
    "three_segments_out_of_order": (
        [GID["ann_two"], GID["snv"], GID["adsp_false"], GID["level_0"],
         GID["long_digest"], GID["at_width"], GID["ann_raw"],
         GID["insertion"], GID["adsp_null"]], 1),
    "same_gid_twice": ([GID["deletion"], PLAIN, GID["deletion"]], 0),
    "same_scalar_gid_twice": (
        [GID["long_digest"], PLAIN, GID["long_digest"]], 2),
    "every_row_reversed": (list(range(len(ROWS)))[::-1], 3),
    "one_gid": ([GID["ann_two"]], 1),
    "empty": ([], 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_render_rows_is_the_scalar_renderer_byte_for_byte(shard, case):
    gids, scalar_rows = CASES[case]
    clock = _LookupClock()
    got = render_rows(shard, CODE, gids, clock)
    assert got == _scalar(shard, gids)
    assert got == [render_variant(shard, CODE, g) for g in gids]
    assert (clock.batch_rows, clock.scalar_rows) == (
        len(gids) - scalar_rows, scalar_rows)
    # numpy ids as the engine passes them, and no clock at all
    assert render_rows(shard, CODE, np.asarray(gids, np.int64)) == got
    for text in got:
        json.loads(text)


def test_rendered_fields_are_the_rows_own(shard):
    """The renderer itself, against the input rows (parity alone would
    pass two renderers that are wrong together)."""
    for gid, text in enumerate(render_rows(shard, CODE, range(len(ROWS)))):
        row, doc = ROWS[gid], json.loads(text)
        rs = row["rs"]
        metaseq = f'8:{row["pos"]}:{row["ref"]}:{row["alt"]}'
        assert doc["metaseq_id"] == metaseq
        assert doc["primary_key"] == row.get(
            "digest", metaseq + (f":rs{rs}" if rs >= 0 else ""))
        assert (doc["chromosome"], doc["position"]) == ("8", row["pos"])
        assert (doc["ref"], doc["alt"]) == (row["ref"], row["alt"])
        assert doc["ref_snp"] == (f"rs{rs}" if rs >= 0 else None)
        assert doc["is_multi_allelic"] is row.get("multi", False)
        assert doc["is_adsp_variant"] == {-1: None, 0: False, 1: True}[
            row.get("adsp", -1)]
        assert doc["bin_index"].startswith("8") and (
            doc["bin_index"].count(".L") == (0 if row["tag"] == "level_0"
                                             else 13))
        assert list(doc["annotations"]) == [
            c for c in engine_mod.JSONB_COLUMNS if c in row.get("ann", {})]
    raw = render_rows(shard, CODE, [GID["ann_raw"], PLAIN])[0]
    assert '"vep_output":{"input": "8:3000000",  "n":1}' in raw  # verbatim


@pytest.mark.parametrize("gids", [
    [GID["long_no_digest"], PLAIN],
    [PLAIN, GID["long_digest"], GID["snv"]],
    [GID["long_digest"]],
], ids=["alt_over_width", "ref_over_width", "alone"])
def test_over_width_without_retained_strings_raises_the_same_error(gids):
    store = VariantStore(width=WIDTH)
    shard = store.shard(CODE)
    rows = [r for r in ROWS if r["tag"] in ("snv", "long_digest",
                                            "long_no_digest")]
    shard.append_segment(_segment(
        [dict(r, digest=None) for r in rows], retain=False))
    shard._starts_cache = None
    local = [[r["tag"] for r in rows].index(ROWS[g]["tag"]) for g in gids]
    with pytest.raises(ValueError) as scalar:
        _scalar(shard, local)
    with pytest.raises(ValueError) as columnar:
        render_rows(shard, CODE, local)
    assert str(columnar.value) == str(scalar.value)
    assert "exceeds store width 8 with no retained strings" in str(
        columnar.value)


# ---------------------------------------------------------------------------
# region answers: RegionPage / RegionsResult against the scalar renderer


def _page(shard, gids, paged: bool = False) -> RegionPage:
    """A page showing ``gids`` of ``shard``, in that order, as the engine
    builds one: (segment index, local row) arrays."""
    si, jj = shard.locate_rows(np.asarray(gids, np.int64))
    return RegionPage(
        shard, chromosome_label(CODE), 0, "chr8", len(gids), 7, si, jj,
        "8:1-64000000", "token" if paged else None, paged=paged)


def _scalar_envelope(page) -> str:
    """The page's body with every row through ``_render_row``, one row at
    a time: what ``RegionPage.assemble`` was before the columnar pass."""
    rows = [
        _render_row(page.shard.segments[si], j, page.label, page.shard.width)
        for si, j in zip(page.si.tolist(), page.jj.tolist())
    ]
    return page.prefix() + ",".join(rows) + page.suffix()


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_region_page_is_the_scalar_renderer_byte_for_byte(shard, case):
    gids, scalar_rows = CASES[case]
    page = _page(shard, gids)
    want = _scalar(shard, gids)
    assert list(page.rows()) == want
    assert page.assemble() == _scalar_envelope(page)
    assert page.returned == len(gids)
    assert json.loads(page.assemble())["returned"] == len(gids)
    # the same page as a panel's only one, and the routes its rows took
    clock = _PanelClock()
    result = RegionsResult([page], None, clock)
    assert list(result.rows()) == [_scalar_envelope(page)]
    assert (clock.batch_rows, clock.scalar_rows) == (
        len(gids) - scalar_rows, scalar_rows)
    assert result.assemble() == \
        '{"n":1,"results":[' + _scalar_envelope(page) + "]}"


#: panel -> the gids of each page, in order (pages over one shard: what a
#: block gathers is grouped by segment across its pages)
PANELS = {
    "pages_of_two_and_three_segments": [
        [GID["snv"], GID["adsp_null"]],
        [GID["at_width"], GID["adsp_true"], GID["ann_raw"], GID["level_0"]],
    ],
    "a_retained_long_allele_among_plain_pages": [
        [GID["snv"], GID["insertion"]], [GID["long_no_digest"], GID["snv"]],
        [GID["deletion"]],
    ],
    "a_digest_pk_among_plain_pages": [
        [GID["adsp_false"]], [GID["digest_only"]], [GID["long_digest"]],
        [GID["adsp_true"], GID["adsp_null"]],
    ],
    "every_annotation_shape": [
        [GID["ann_raw"], GID["ann_dict"]], [GID["ann_two"]],
        [GID["ann_none"], GID["snv"]],
    ],
    "empty_pages_between": [
        [], [GID["snv"], GID["ann_two"]], [], [], [GID["level_0"]], [],
    ],
    "only_empty_pages": [[], [], []],
    "one_row_in_all": [[], [GID["ann_dict"]], []],
    "one_scalar_row_in_all": [[GID["long_digest"]]],
    "one_row_a_page": [[g] for g in range(len(ROWS))],
    "every_row_twice_over": [list(range(len(ROWS))),
                             list(range(len(ROWS)))[::-1]],
}
#: rows of each panel that keep host strings (the scalar route at any block
#: size); a block that holds one row in all sends that row there too
HOST_STRING_ROWS = {GID["long_digest"], GID["long_no_digest"],
                    GID["digest_only"]}


@pytest.mark.parametrize("block", [1, 3, 512])
@pytest.mark.parametrize("panel", sorted(PANELS))
def test_a_panel_renders_in_blocks_byte_for_byte(shard, monkeypatch, panel,
                                                 block):
    monkeypatch.setattr(engine_mod, "REGION_RENDER_BLOCK", block)
    pages = [_page(shard, gids) for gids in PANELS[panel]]
    clock = _PanelClock()
    result = RegionsResult(pages, None, clock)
    want = [_scalar_envelope(page) for page in pages]
    assert list(result.rows()) == want
    rows = sum(len(gids) for gids in PANELS[panel])
    assert clock.batch_rows + clock.scalar_rows == rows == result.returned
    kept = sum(g in HOST_STRING_ROWS for gids in PANELS[panel] for g in gids)
    if block == 512:
        assert clock.scalar_rows == (1 if rows == 1 else kept)
    else:
        assert clock.scalar_rows >= kept
    assert result.assemble() == \
        f'{{"n":{len(pages)},"results":[' + ",".join(want) + "]}"
    for page, text in zip(pages, want):
        assert page.assemble() == text  # and alone, whatever the block


@pytest.mark.parametrize("gids", [
    [GID["long_no_digest"], PLAIN],
    [PLAIN, GID["long_digest"], GID["snv"]],
    [GID["long_digest"]],
], ids=["alt_over_width", "ref_over_width", "alone"])
def test_a_region_page_raises_the_scalar_error_on_an_over_width_row(gids):
    store = VariantStore(width=WIDTH)
    shard = store.shard(CODE)
    rows = [r for r in ROWS if r["tag"] in ("snv", "long_digest",
                                            "long_no_digest")]
    shard.append_segment(_segment(
        [dict(r, digest=None) for r in rows], retain=False))
    shard._starts_cache = None
    local = [[r["tag"] for r in rows].index(ROWS[g]["tag"]) for g in gids]
    with pytest.raises(ValueError) as scalar:
        _scalar(shard, local)
    page = _page(shard, local)
    with pytest.raises(ValueError) as alone:
        page.assemble()
    with pytest.raises(ValueError) as in_a_panel:
        RegionsResult([_page(shard, [0]), page]).assemble()
    assert str(alone.value) == str(in_a_panel.value) == str(scalar.value)


#: every stored row of a chromosome, and windows inside single segments
WHOLE = "{c}:1-64000000"
READS = {
    "whole_chromosome": dict(specs=[WHOLE], how={}),
    "limit_cut": dict(specs=[WHOLE], how=dict(limit=5)),
    "limit_of_one": dict(specs=[WHOLE], how=dict(limit=1)),
    "limit_zero": dict(specs=[WHOLE], how=dict(limit=0)),
    "filtered_by_cadd": dict(specs=[WHOLE], how=dict(min_cadd=10.0)),
    "filtered_by_rank": dict(specs=[WHOLE, "{c}:1-30000"],
                             how=dict(max_conseq_rank=10)),
    "filtered_and_cut": dict(specs=[WHOLE],
                             how=dict(min_cadd=1.0, limit=1)),
    "nothing_stored_there": dict(specs=["{c}:5000-6000"], how={}),
    "one_stored_row": dict(specs=["{c}:1000-1000"], how={}),
}


@pytest.mark.parametrize("read", sorted(READS))
def test_served_reads_render_the_scalar_bytes(served, read):
    """Through the engine: the single read, and the same interval in a
    panel, against the scalar envelope of the page the engine built."""
    engine, _where = served
    how = READS[read]["how"]
    for code in (CODE, 1):
        specs = [s.format(c=chromosome_label(code))
                 for s in READS[read]["specs"]]
        result = engine.regions_serve(specs, **how)
        want = [_scalar_envelope(page) for page in result.pages]
        assert list(result.rows()) == want
        for spec, page, text in zip(specs, result.pages, want):
            assert engine.region(spec, **how) == text
            doc = json.loads(text)
            assert doc["returned"] == page.returned == len(doc["variants"])
            if "limit" in how:
                assert doc["returned"] <= how["limit"]
            else:
                assert doc["returned"] == doc["count"]
    whole = json.loads(engine.region(WHOLE.format(c="8")))
    assert whole["count"] == len(ROWS)  # the reads above reach every hazard


@pytest.mark.parametrize("limit", [1, 4, 6, 17, 40])
def test_a_cursor_walk_renders_the_scalar_bytes_page_by_page(served, limit):
    engine, _where = served
    spec = WHOLE.format(c="8")
    snap = engine.snapshots.current()
    unpaged = json.loads(engine.region(spec))["variants"]
    rows, cursor = [], ""
    while cursor is not None:
        page = engine._region_page(snap, CODE, 1, 64_000_000, None, None,
                                   limit, cursor)
        assert page.paged and page.returned <= limit
        text = engine.region(spec, limit=limit, cursor=cursor)
        assert text == _scalar_envelope(page) == page.assemble()
        doc = json.loads(text)
        rows.extend(doc["variants"])
        cursor = doc["next"]
    assert rows == unpaged and len(rows) == len(ROWS)


@pytest.mark.parametrize("block", [2, 5, 512])
def test_a_block_whose_pages_alternate_chromosomes(served, monkeypatch,
                                                   block):
    """A panel's targets come in the order drawn: consecutive pages sit on
    different shards, and a block's rows are grouped by what they are."""
    monkeypatch.setattr(engine_mod, "REGION_RENDER_BLOCK", block)
    engine, _where = served
    windows = ["1-64000000", "1000-1030", "20000-20050", "5000-6000",
               "3000000-3000020", "1-64000000", "1050-1050"]
    specs = [f"{chromosome_label(code)}:{w}"
             for w in windows for code in (CODE, 1)]
    specs += specs[::-1][:5]  # and two of one chromosome in a row
    result = engine.regions_serve(specs, limit=9)
    want = [_scalar_envelope(page) for page in result.pages]
    assert [page.label for page in result.pages[:4]] == ["8", "1", "8", "1"]
    assert list(result.rows()) == want
    assert result.assemble() == \
        f'{{"n":{len(specs)},"results":[' + ",".join(want) + "]}"
    assert want == [engine.region(spec, limit=9) for spec in specs]
    # rendered twice above (``rows()``, ``assemble()``), tallied each time
    clock = result.clock
    assert clock.batch_rows + clock.scalar_rows == 2 * result.returned
    for doc, page in zip(json.loads(result.assemble())["results"],
                         result.pages):
        assert all(v["chromosome"] == page.label for v in doc["variants"])


def test_bulk_allele_decode_is_segment_alleles_row_by_row(shard):
    for seg in shard.segments:
        short = [j for j in range(seg.n)
                 if max(seg.cols["ref_len"][j], seg.cols["alt_len"][j])
                 <= WIDTH and seg.obj["_long_alleles"] is None
                 or seg.obj["_long_alleles"] is not None
                 and seg.obj["_long_alleles"][j] is None]
        j = np.asarray(short[::-1], np.int64)  # any order
        refs = decode_allele_rows(seg.ref[j], seg.cols["ref_len"][j])
        alts = decode_allele_rows(seg.alt[j], seg.cols["alt_len"][j])
        assert list(zip(refs, alts)) == [
            segment_alleles(seg, int(k), WIDTH) for k in j]


@pytest.mark.parametrize("row,length,want", [
    (b"ACGT\0\0\0\0", 4, "ACGT"),
    (b"ACGTACGT", 8, "ACGTACGT"),            # exactly the width
    (b"ACGTACGT", 20, "ACGTACGT"),           # stored length over the width
    (b"ACGT\0\0\0\0", 2, "AC"),              # shorter than the bytes held
    (b"AC\0\0\0\0\0\0", 4, "AC\0\0"),        # longer: NULs are kept
    (b"\0\0\0\0\0\0\0\0", 0, ""),
], ids=["padded", "at_width", "over_width", "length_short", "length_long",
        "empty"])
def test_bulk_decode_slices_by_the_stored_length(row, length, want):
    """``decode_allele(row, length)`` exactly — not the ``S<width>`` view's
    rule of dropping trailing NULs."""
    from annotatedvdb_tpu.types import decode_allele

    matrix = np.frombuffer(b"GGGGGGGG" + row + b"TTTTTTTT",
                           np.uint8).reshape(3, 8)
    assert decode_allele(matrix[1], length) == want
    assert decode_allele_rows(matrix, [8, length, 8]) == [
        "GGGGGGGG", want, "TTTTTTTT"]


def test_locate_rows_is_locate_row_for_every_id(shard):
    gids = [16, 0, 5, 6, 11, 12, 0]
    si, off = shard.locate_rows(gids)
    for g, s, j in zip(gids, si.tolist(), off.tolist()):
        seg, want = shard.locate_row(g)
        assert shard.segments[s] is seg and j == want


# ---------------------------------------------------------------------------
# the cached-batch path of lookup_many against the per-id loop it replaced


class PerIdLoop:
    """The rows loop as it stood before the columnar pass: one cache probe,
    one ``render_variant`` and one insert-and-evict per found id."""

    def __init__(self, cap: int, cap_bytes: int):
        self.cap, self.cap_bytes = cap, cap_bytes
        self.cache: OrderedDict = OrderedDict()
        self.bytes = self.hits = self.misses = 0

    def render(self, shard, code: int, gid: int, generation: int) -> str:
        key = (generation, code, gid)
        text = self.cache.get(key)
        if text is not None:
            self.cache.move_to_end(key)
            self.hits += 1
            return text
        self.misses += 1
        text = render_variant(shard, code, gid)
        self.cache[key] = text
        self.bytes += len(text)
        while self.cache and (len(self.cache) > self.cap
                              or self.bytes > self.cap_bytes):
            _, old = self.cache.popitem(last=False)
            self.bytes -= len(old)
        return text


def _vid(code: int, row: dict) -> str:
    return f'{chromosome_label(code)}:{row["pos"]}:{row["ref"]}:{row["alt"]}'


@pytest.fixture()
def served():
    """(engine, {id: (code, gid)}): two chromosomes of the hazard rows, each
    in three segments, with real identity hashes so ids resolve."""
    store = VariantStore(width=WIDTH)
    where = {}
    for code in (CODE, 1):
        shard = store.shard(code)
        for s in range(3):
            rows = [r for r, at in zip(ROWS, SEGMENT_OF) if at == s]
            seg = _segment(rows)
            refs = [r["ref"] for r in rows]
            alts = [r["alt"] for r in rows]
            seg.cols["h"][:] = identity_hashes(
                WIDTH, seg.ref, seg.alt, seg.cols["ref_len"],
                seg.cols["alt_len"], refs, alts)
            order = np.lexsort((seg.cols["h"], seg.cols["pos"]))
            assert (order == np.arange(seg.n)).all()  # distinct positions
            seg._key = None
            shard.append_segment(seg)
        shard._starts_cache = None
        for gid, row in enumerate(ROWS):
            where[_vid(code, row)] = (code, gid)
    engine = QueryEngine(StaticSnapshots(store), registry=MetricsRegistry())
    return engine, where


def _reference(model: PerIdLoop, engine, where, ids) -> list:
    """``lookup_many`` as the per-id loop ran it: chromosome groups in
    order of first appearance, found ids in request order inside each."""
    store = engine.snapshots.current().store
    generation = engine.snapshots.current().generation
    out = [None] * len(ids)
    codes = list(dict.fromkeys(
        engine_mod.parse_variant_id(i)[0] for i in ids))
    for code in codes:
        for at, vid in enumerate(ids):
            if vid in where and where[vid][0] == code:
                out[at] = model.render(store.shards[code], code,
                                       where[vid][1], generation)
    return out


def _id(tag: str, code: int = CODE) -> str:
    return _vid(code, ROWS[GID[tag]])


ABSENT = "8:999:A:C"

#: scenario -> (entry ceiling, byte ceiling or None, the calls in order)
SCENARIOS = {
    "all_misses": (64, None, [
        [_id("snv"), _id("ann_two"), ABSENT, _id("long_digest")]]),
    "second_call_all_hits": (64, None, [
        [_id("snv"), _id("ann_two"), _id("adsp_null")],
        [_id("adsp_null"), _id("snv"), _id("ann_two")]]),
    "hits_then_misses": (64, None, [
        [_id("snv"), _id("deletion")],
        [_id("deletion"), _id("snv"), _id("ann_raw"), _id("at_width")]]),
    "two_chromosomes_interleaved": (64, None, [
        [_id("snv"), _id("snv", 1), _id("ann_two"), ABSENT,
         _id("ann_two", 1), _id("long_digest", 1)],
        [_id("snv", 1), _id("insertion", 1), _id("snv"), _id("ann_none")]]),
    "duplicate_miss": (64, None, [
        [_id("ann_dict"), _id("snv"), _id("ann_dict"), _id("ann_dict")]]),
    "duplicate_hit": (64, None, [
        [_id("ann_dict")], [_id("ann_dict"), _id("ann_dict"), _id("snv")]]),
    "one_miss_in_a_group": (64, None, [[_id("snv")], [_id("ann_two", 1)]]),
    "evicts_at_the_entry_ceiling": (5, None, [
        [_id("snv"), _id("insertion"), _id("deletion"), _id("at_width")],
        [_id("adsp_null"), _id("adsp_false"), _id("adsp_true")]]),
    "hits_survive_the_entry_ceiling": (4, None, [
        [_id("snv"), _id("insertion"), _id("deletion"), _id("at_width")],
        [_id("deletion"), _id("adsp_null"), _id("adsp_false")]]),
    "more_misses_than_entries": (3, None, [
        [_id(r["tag"]) for r in ROWS[:9]]]),
    "evicts_at_the_byte_ceiling": (64, 2.5, [
        [_id("snv"), _id("insertion")],
        [_id("deletion"), _id("at_width"), _id("ref_snp_null")]]),
    "byte_ceiling_under_one_record": (64, 0.5, [
        [_id("snv"), _id("insertion")], [_id("snv")]]),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_cached_batch_path_is_the_per_id_loop(served, scenario):
    engine, where = served
    cap, cap_records, calls = SCENARIOS[scenario]
    one = len(render_variant(
        engine.snapshots.current().store.shards[CODE], CODE, GID["snv"]))
    cap_bytes = (QueryEngine.POINT_RENDER_CACHE_BYTES if cap_records is None
                 else int(one * cap_records))
    engine.POINT_RENDER_CACHE = cap
    engine.POINT_RENDER_CACHE_BYTES = cap_bytes
    model = PerIdLoop(cap, cap_bytes)
    for ids in calls:
        assert engine.lookup_many(ids) == _reference(
            model, engine, where, ids)
        assert (engine.render_cache_hits, engine.render_cache_misses) == (
            model.hits, model.misses)
        assert dict(engine._render_cache) == dict(model.cache)
        assert engine._render_cache_bytes == model.bytes == sum(
            len(t) for t in engine._render_cache.values())
    assert (engine.render_batch_rows + engine.render_scalar_rows
            == engine.render_cache_misses)


def test_a_hit_is_answered_before_the_calls_own_inserts_evict_it(served):
    """Where the two differ, by one render: the per-id loop, at the entry
    ceiling, evicts ``snv`` on inserting ``ann_raw`` and renders it again;
    the batch answered it in the first lock hold."""
    engine, where = served
    engine.POINT_RENDER_CACHE = 2
    model = PerIdLoop(2, QueryEngine.POINT_RENDER_CACHE_BYTES)
    first, second = [_id("snv"), _id("deletion")], [_id("ann_raw"), _id("snv")]
    for ids in (first, second):
        assert engine.lookup_many(ids) == _reference(
            model, engine, where, ids)
    assert (model.hits, model.misses) == (0, 4)
    assert (engine.render_cache_hits, engine.render_cache_misses) == (1, 3)
    assert set(engine._render_cache) == set(model.cache)
    assert engine._render_cache_bytes == model.bytes


def test_a_racing_insert_is_replaced_not_counted_twice(served, monkeypatch):
    engine, _where = served
    shard = engine.snapshots.current().store.shards[CODE]
    gids = [GID["snv"], GID["ann_two"], GID["deletion"]]
    real = engine_mod.render_rows

    def racing(shard, code, missing, clock=None):
        texts = real(shard, code, missing, clock)
        # another thread renders and inserts the same rows meanwhile
        with engine._render_lock:
            for gid, text in zip(missing, texts):
                engine._render_cache[(0, code, gid)] = text
                engine._render_cache_bytes += len(text)
        return texts

    monkeypatch.setattr(engine_mod, "render_rows", racing)
    clock = _LookupClock()
    got = engine._render_group(shard, CODE, gids, 0, clock)
    monkeypatch.undo()
    assert got == [render_variant(shard, CODE, g) for g in gids]
    assert (clock.found, clock.misses) == (3, 3)
    assert len(engine._render_cache) == 3
    assert engine._render_cache_bytes == sum(len(t) for t in got)


def test_threads_sharing_the_cache_keep_its_byte_tally(served):
    """More threads than cores on one small cache, switching often: every
    answer right, and the tally still the sum of what is held (a lost
    update between the two lock holds would break it)."""
    import sys

    engine, where = served
    engine.POINT_RENDER_CACHE = 7
    store = engine.snapshots.current().store
    want = {vid: render_variant(store.shards[code], code, gid)
            for vid, (code, gid) in where.items()}
    ids = sorted(want)
    wrong = []

    def reader(k: int) -> None:
        rng = np.random.default_rng(k)
        for _ in range(150):
            batch = [ids[i] for i in rng.integers(0, len(ids), 9)] + [ABSENT]
            got = engine.lookup_many(batch)
            if got != [want.get(v) for v in batch]:
                wrong.append(batch)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not wrong
    assert len(engine._render_cache) <= 7
    assert engine._render_cache_bytes == sum(
        len(t) for t in engine._render_cache.values())


def test_point_reads_take_the_same_path(served):
    engine, where = served
    vid = _id("ann_two")
    store = engine.snapshots.current().store
    want = render_variant(store.shards[CODE], CODE, where[vid][1])
    assert engine.lookup(vid) == want
    assert engine.lookup(vid) == want
    assert engine.lookup(ABSENT) is None
    assert (engine.render_cache_hits, engine.render_cache_misses) == (1, 1)
    # a group's lone miss is one row: the scalar renderer's
    assert (engine.render_batch_rows, engine.render_scalar_rows) == (0, 1)


def test_render_batch_is_added_once_per_call(served, monkeypatch):
    engine, _where = served
    done = []
    real = QueryEngine._lookup_done

    def counted(self, clock):
        done.append((clock.batch_rows, clock.scalar_rows))
        real(self, clock)

    monkeypatch.setattr(QueryEngine, "_lookup_done", counted)
    ids = [_id(r["tag"], code) for code in (CODE, 1) for r in ROWS] + [ABSENT]
    assert sum(t is not None for t in engine.lookup_many(ids)) == 2 * len(ROWS)
    # 17 rows a chromosome, 3 of them keep host strings
    assert done == [(28, 6)]
    assert (engine.render_batch_rows, engine.render_scalar_rows) == (28, 6)
    engine.lookup_many(ids)  # all hits: nothing rendered, nothing added
    assert done == [(28, 6), (0, 0)]
    assert engine._m_batch_rows.name == "avdb_render_batch_rows_total"
    assert (engine._m_batch_rows.value, engine._m_scalar_rows.value) == (28, 6)


def test_stats_and_metrics_carry_render_batch(tmp_path):
    from test_serve import _build_store
    from test_serve import _vid as vid_of

    store_dir = str(tmp_path / "vdb")
    truth = _build_store(store_dir)
    server = start_server(store_dir=store_dir)
    port, ctx = server.server_address[1], server.ctx
    try:
        def call(path, payload=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=None if payload is None
                else json.dumps(payload).encode())
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.read().decode()

        before = json.loads(call("/stats"))
        assert before["render_batch"] == {"rows": 0, "scalar_rows": 0}
        ids = [vid_of(r) for r in truth[:60]]
        body = json.loads(call("/variants", {"ids": ids}))
        assert body["found"] == 60
        after = json.loads(call("/stats"))
        batch, cache = after["render_batch"], after["render_cache"]
        assert batch["rows"] + batch["scalar_rows"] == cache["misses"] == 60
        assert batch["rows"] >= 58  # at most a lone miss a chromosome
        metrics = call("/metrics")
        assert (f'avdb_render_batch_rows_total{{path="columnar"}} '
                f'{batch["rows"]}') in metrics
    finally:
        stop_server(server)
