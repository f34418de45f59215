"""A device probe costs two transfers, and a lookup launches every
chromosome group's probe before it waits for any.

- the packed program (``ops/dedup.lookup_in_sorted_packed_jit``: one
  buffer in, the index alone out) against the host oracle
  ``lookup_in_sorted_np`` at every query capacity a probe takes, on
  present and absent queries, sentinel padding, equal-position runs and
  equal-(pos, hash) runs with different alleles;
- ``ChromosomeShard.lookup_launch`` / ``lookup_collect`` against the
  blocking ``lookup`` on overlapping segments (the oldest segment wins),
  whichever of them answer from the device;
- ``QueryEngine.lookup_many`` over 1, 3 and 5 chromosome groups, one not
  loaded and one on the host path: the oracle's bytes, in request order;
- a device failure planted at the launch or at the COLLECT of the second
  of three groups: that group alone answered again from the host, the
  breaker's failure recorded against its code alone, the fault point
  passed once a group; half-open admits one trial; without an observer
  the error propagates;
- a device copy evicted between launch and collect changes nothing;
- the accounts: ``transfers`` = 2 x ``device_probes``,
  ``overlapped_probes`` = device groups - 1 a call.

The device probe is forced the way ``test_point_cobatch`` forces it: the
latch that a CPU backend turns off is set, and the segments meant to be
resident get their device copy.
"""

from __future__ import annotations

import numpy as np
import pytest

from annotatedvdb_tpu.loaders.lookup import identity_hashes
from annotatedvdb_tpu.ops.dedup import (
    lookup_in_sorted_np,
    lookup_in_sorted_packed_jit,
    pack_queries,
)
from annotatedvdb_tpu.serve import DeviceBreaker, QueryEngine, StaticSnapshots
from annotatedvdb_tpu.serve.engine import render_variant
from annotatedvdb_tpu.store import VariantStore
from annotatedvdb_tpu.store import variant_store
from annotatedvdb_tpu.store.variant_store import (
    Segment,
    probe_query_capacity,
    probe_stats,
)
from annotatedvdb_tpu.types import chromosome_label, encode_allele_array
from annotatedvdb_tpu.utils import faults
from annotatedvdb_tpu.utils.arrays import POS_SENTINEL, pad_rows

WIDTH = 49
SEED = 2900129
#: chromosome code -> where its probe is answered; 5 is asked for and
#: never loaded
PLACES = {1: "device", 2: "device", 22: "device", 3: "host"}
NOT_LOADED = 5
BASES = np.frombuffer(b"ACGT", np.uint8)


@pytest.fixture(scope="module")
def forced_device():
    """Segments with a device copy really ride the device probe on the
    CPU backend."""
    patch = pytest.MonkeyPatch()
    patch.setattr(variant_store, "_DEVICE_LOOKUP_OK", True)
    yield
    patch.undo()


def _identities(rng, n: int) -> list:
    """``n`` distinct (pos, ref, alt) identities, unsorted: SNVs, short
    indels, and multi-allelic sites (a position several times over)."""
    pos = rng.integers(1_000, 40_000, n)
    seen, out = set(), []
    for p in pos.tolist():
        ref = "".join(rng.choice(list("ACGT"), rng.integers(1, 4)))
        alt = "".join(rng.choice(list("ACGT"), rng.integers(1, 6)))
        if ref != alt and (p, ref, alt) not in seen:
            seen.add((p, ref, alt))
            out.append((p, ref, alt))
    return out


def _columns(idents: list) -> tuple:
    """(pos, h, ref, alt, ref_len, alt_len) of identity triples, hashed
    as a request's ids are."""
    refs = [r for _p, r, _a in idents]
    alts = [a for _p, _r, a in idents]
    ref, ref_len = encode_allele_array(refs, WIDTH)
    alt, alt_len = encode_allele_array(alts, WIDTH)
    pos = np.asarray([p for p, _r, _a in idents], np.int32)
    h = identity_hashes(WIDTH, ref, alt, ref_len, alt_len, refs, alts)
    return pos, h, ref, alt, ref_len, alt_len


def _row_identity(seg: Segment, j: int) -> tuple:
    """(pos, ref, alt) of a segment's row ``j``."""
    return (int(seg.cols["pos"][j]),
            bytes(seg.ref[j][: seg.cols["ref_len"][j]]).decode(),
            bytes(seg.alt[j][: seg.cols["alt_len"][j]]).decode())


def _segment(idents: list) -> Segment:
    pos, h, ref, alt, ref_len, alt_len = _columns(idents)
    return Segment.build(
        {"pos": pos, "h": h, "ref_len": ref_len, "alt_len": alt_len},
        ref, alt,
    )


# ---------------------------------------------------------------------------
# the packed program equals the host oracle


@pytest.fixture(scope="module")
def sorted_slice():
    """A (pos, hash)-sorted store slice with the runs a probe must get
    right: positions held by several rows, and (pos, hash) pairs held by
    rows that differ in their alleles alone."""
    rng = np.random.default_rng([SEED, 1])
    n = 6_000
    pos = np.sort(rng.integers(10, 3_000, n)).astype(np.int32)  # runs of ~2
    h = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    # every 16th row starts a run of three equal (pos, hash) keys
    for at in range(8, n - 4, 16):
        pos[at:at + 3] = pos[at]
        h[at:at + 3] = h[at]
    order = np.lexsort((h, pos))
    pos, h = pos[order], h[order]
    ref_len = rng.integers(1, 5, n).astype(np.int32)
    alt_len = rng.integers(1, 9, n).astype(np.int32)
    ref = np.zeros((n, WIDTH), np.uint8)
    alt = np.zeros((n, WIDTH), np.uint8)
    for j in range(n):
        ref[j, :ref_len[j]] = rng.choice(BASES, ref_len[j])
        alt[j, :alt_len[j]] = rng.choice(BASES, alt_len[j])
    # rows of one (pos, hash) run differ in their last allele byte alone
    same = (pos[1:] == pos[:-1]) & (h[1:] == h[:-1])
    for j in (np.flatnonzero(same) + 1).tolist():
        ref[j], ref_len[j], alt_len[j] = ref[j - 1], ref_len[j - 1], \
            alt_len[j - 1]
        alt[j] = alt[j - 1]
        alt[j, alt_len[j] - 1] = BASES[(j % 3 + 1 + np.flatnonzero(
            BASES == alt[j - 1, alt_len[j] - 1])[0]) % 4]
    assert same.sum() >= 600
    return pos, h, ref, alt, ref_len, alt_len


@pytest.mark.parametrize("fill", ["full", "padded", "one"])
@pytest.mark.parametrize("cap", [32, 64, 128, 256, 512, 1024, 2048])
def test_packed_program_equals_host_oracle(sorted_slice, cap, fill):
    spos, sh, sref, salt, srl, sal = sorted_slice
    n = spos.shape[0]
    nq = {"full": cap, "padded": cap - cap // 3, "one": 1}[fill]
    assert probe_query_capacity(nq) == cap or fill == "one"
    rng = np.random.default_rng([SEED, cap, nq])
    rows = rng.integers(0, n, nq)
    # a quarter of the queries sit inside the equal-(pos, hash) runs
    same = np.flatnonzero((spos[1:] == spos[:-1]) & (sh[1:] == sh[:-1]))
    rows[::4] = rng.choice(same, rows[::4].shape[0]) + rng.integers(0, 2)
    q = [a[rows].copy() for a in sorted_slice]
    kind = np.arange(nq) % 5
    q[0][kind == 1] += 1                 # absent: no such position/hash
    q[3][kind == 2, 0] ^= 0x20           # absent: same key, another allele
    q[5][kind == 3] += 1                 # absent: another allele length
    want_found, want_index = lookup_in_sorted_np(
        spos, sh, sref, salt, srl, sal,
        pad_rows(q[0], cap, POS_SENTINEL),
        *(pad_rows(a, cap, 0) for a in q[1:]),
    )
    buf = pack_queries(*q, cap)
    assert buf.dtype == np.uint8 and buf.shape == (cap * (16 + 2 * WIDTH),)
    index = np.asarray(lookup_in_sorted_packed_jit(
        spos, sh, sref, salt, srl, sal, buf
    ))
    assert index.dtype == np.int32 and index.shape == (cap,)
    assert index.tolist() == want_index.tolist()
    assert (index >= 0).tolist() == want_found.tolist()
    # the present ones found where they are (any row of an equal run whose
    # bytes match IS the row: identities are unique), the rest absent
    present = kind[:nq] % 5 == 0
    if fill != "one":
        assert present.any() and (index[:nq][present] >= 0).all()
        got = index[:nq][present]
        for col, a in zip(sorted_slice, q):
            assert (col[got] == a[present]).all()
    assert (index[:nq][np.isin(kind, (2, 3))] == -1).all()
    assert (index[nq:] == -1).all()      # sentinel padding matches no row


# ---------------------------------------------------------------------------
# a shard's two-step lookup equals the blocking one: first wins


@pytest.fixture(scope="module")
def overlapping(forced_device):
    """One shard, three segments whose key ranges and identities overlap,
    and queries over all of them plus ids no segment holds."""
    rng = np.random.default_rng([SEED, 2])
    idents = _identities(rng, 1_500)
    parts = [idents[:700], idents[400:1_100], idents[200:500] + idents[1_000:]]
    absent = [(p + 50_000, r, a) for p, r, a in idents[:200]]
    asked = idents + absent
    asked = [asked[k] for k in rng.permutation(len(asked))]
    return parts, asked


@pytest.mark.parametrize("places", ["ddd", "dhd", "hdh", "hhd", "hhh"])
def test_shard_two_step_lookup_is_first_wins(overlapping, places):
    parts, asked = overlapping
    shard = VariantStore(width=WIDTH).shard(1)
    for part, place in zip(parts, places):
        seg = _segment(part)
        shard.append_segment(seg)
        if place == "d":
            seg._ensure_device_cache()
    assert len(shard.segments) == 3
    query = _columns(asked)
    # the oracle: the first segment, oldest first, that holds the identity
    where: dict = {}
    for si, seg in enumerate(shard.segments):
        for j in range(seg.n):
            where.setdefault(_row_identity(seg, j),
                             int(shard._starts()[si]) + j)
    want = [where.get(key, -1) for key in asked]
    assert sum(w >= 0 for w in want) == len(asked) - 200

    before = dict(probe_stats)
    launched = shard.lookup_launch(*query)
    # nothing is counted before it is collected clean
    assert probe_stats["device_probes"] == before["device_probes"]
    assert len(launched.waiting) == (3 - places.index("d")
                                     if "d" in places else 0)
    found, gid = shard.lookup_collect(launched)
    device = places.count("d")
    assert probe_stats["device_probes"] - before["device_probes"] == device
    assert probe_stats["transfers"] - before["transfers"] == 2 * device
    assert probe_stats["overlapped_probes"] - before["overlapped_probes"] \
        == max(device - 1, 0)
    assert gid.dtype == np.int64 and gid.tolist() == want
    assert found.tolist() == [w >= 0 for w in want]
    for blocking in (shard.lookup(*query),
                     shard.lookup(*query, host_only=True)):
        assert blocking[0].tolist() == found.tolist()
        assert blocking[1].tolist() == gid.tolist()


def test_a_copy_evicted_between_launch_and_collect_changes_nothing(
        overlapping):
    parts, asked = overlapping
    shard = VariantStore(width=WIDTH).shard(1)
    seg = _segment(parts[0])
    shard.append_segment(seg)
    seg._ensure_device_cache()
    query = _columns(asked)
    want = shard.lookup(*query, host_only=True)
    probes = probe_stats["device_probes"]
    launched = shard.lookup_launch(*query)
    seg._device = None  # the residency manager's eviction
    found, gid = shard.lookup_collect(launched)
    assert probe_stats["device_probes"] == probes + 1  # the device answered
    assert found.tolist() == want[0].tolist()
    assert gid.tolist() == want[1].tolist()
    # and the next probe of the evicted segment is the host's
    again = shard.lookup(*query)
    assert probe_stats["device_probes"] == probes + 1
    assert again[1].tolist() == want[1].tolist()


@pytest.mark.parametrize("where", ["_launch_device", "_collect_device"])
def test_without_an_observer_a_device_error_propagates(overlapping,
                                                       monkeypatch, where):
    """Every loader: no breaker owns the failure, so nobody hides it."""
    parts, asked = overlapping
    shard = VariantStore(width=WIDTH).shard(1)
    seg = _segment(parts[0])
    shard.append_segment(seg)
    seg._ensure_device_cache()

    def broken(*_args, **_kw):
        raise RuntimeError("planted device error")

    monkeypatch.setattr(Segment, where, broken)
    probes = probe_stats["device_probes"]
    with pytest.raises(RuntimeError, match="planted device error"):
        shard.lookup(*_columns(asked))
    assert probe_stats["device_probes"] == probes


# ---------------------------------------------------------------------------
# lookup_many: every group launched before any is collected


@pytest.fixture(scope="module")
def served(forced_device):
    """A store of four loaded chromosomes — three with a device copy, one
    probed on the host — the oracle's record of every row, and ids of each
    chromosome: stored ones, never-stored ones, and a chromosome that was
    never loaded."""
    store = VariantStore(width=WIDTH)
    ids: dict = {}
    for code, place in PLACES.items():
        rng = np.random.default_rng([SEED, 3, code])
        idents = _identities(rng, 400)
        shard = store.shard(code)
        shard.append_segment(_segment(idents))
        if place == "device":
            shard.segments[0]._ensure_device_cache()
        label = chromosome_label(code)
        ids[code] = [f"{label}:{p}:{r}:{a}" for p, r, a in idents[:120]] \
            + [f"{label}:{p + 70_000}:{r}:{a}" for p, r, a in idents[:30]]
    ids[NOT_LOADED] = [f"{chromosome_label(NOT_LOADED)}:{p}:A:C"
                       for p in range(100, 130)]
    records = {}
    for code, shard in store.shards.items():
        for gid in range(shard.n):  # one segment a shard: gid is its row
            p, r, a = _row_identity(shard.segments[0], gid)
            records[f"{chromosome_label(code)}:{p}:{r}:{a}"] = \
                render_variant(shard, code, gid)
    assert len(records) == store.n
    return store, records, ids


def _request(ids: dict, codes: tuple, seed: int) -> list:
    """The ids of ``codes``' groups, shuffled together: request order is
    no group's order."""
    rng = np.random.default_rng([SEED, 4, seed])
    asked = [i for code in codes for i in ids[code]]
    return [asked[k] for k in rng.permutation(len(asked))]


@pytest.mark.parametrize("codes", [
    (1,), (1, 3, NOT_LOADED), (1, 2, 22, 3, NOT_LOADED),
], ids=lambda codes: f"{len(codes)}-groups")
def test_lookup_many_is_the_oracles_in_request_order(served, codes):
    store, records, ids = served
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0)
    asked = _request(ids, codes, len(codes))
    device = sum(PLACES.get(code) == "device" for code in codes)
    before = dict(probe_stats)
    got = engine.lookup_many(asked)
    assert got == [records.get(i) for i in asked]
    assert sum(text is not None for text in got) \
        == 120 * sum(code in PLACES for code in codes)
    grew = {k: probe_stats[k] - before[k] for k in before}
    assert grew["device_probes"] == device
    assert grew["device_queries"] == 150 * device
    assert grew["padded_queries"] == 256 * device
    assert grew["transfers"] == 2 * device
    assert grew["overlapped_probes"] == device - 1
    # the render cache answers the same call again, byte for byte
    assert engine.lookup_many(asked) == got


# ---------------------------------------------------------------------------
# the breaker, with the failure at either step of one group


def _plant_failure(monkeypatch, where: str, nth: int) -> list:
    """The ``nth`` call of ``Segment.<where>`` raises; returns the list of
    calls seen."""
    real = getattr(Segment, where)
    calls: list = []

    def planted(*args, **kw):
        calls.append(where)
        if len(calls) == nth:
            raise RuntimeError(f"planted device error in {where}")
        return real(*args, **kw)

    if where == "_collect_device":
        planted = staticmethod(planted)
    monkeypatch.setattr(Segment, where, planted)
    return calls


def _count_fault_passes(monkeypatch) -> list:
    fire = faults.fire
    passes: list = []

    def counting(point, *args, **kw):
        passes.append(point)
        return fire(point, *args, **kw)

    monkeypatch.setattr(faults, "fire", counting)
    return passes


@pytest.mark.parametrize("where", ["_launch_device", "_collect_device"])
def test_a_failure_in_the_second_group_is_that_groups_alone(
        served, monkeypatch, where):
    store, records, ids = served
    breaker = DeviceBreaker(cooldown_s=5.0)
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0,
                         breaker=breaker)
    codes = (1, 2, 22)
    asked = _request(ids, codes, 7)
    # groups are probed in the order their first id arrives
    order = list(dict.fromkeys(int(i.split(":")[0]) for i in asked))
    second = order[1]
    calls = _plant_failure(monkeypatch, where, 2)
    passes = _count_fault_passes(monkeypatch)
    retried: list = []
    host = Segment._probe_host

    def watched(seg, *query):
        retried.append(seg)
        return host(seg, *query)

    monkeypatch.setattr(Segment, "_probe_host", watched)
    before = dict(probe_stats)
    assert engine.lookup_many(asked) == [records.get(i) for i in asked]
    assert len(calls) == 3
    assert passes.count("engine.device_probe") == 3  # once a group
    # that group alone went back to the host, once
    assert retried == [store.shards[second].segments[0]]
    # the failure is recorded against its code alone; the others' answers
    # were kept and their successes recorded
    groups = breaker.stats()["groups"]
    assert groups == {str(second): {"state": "closed", "failures": 1}}
    assert probe_stats["device_probes"] - before["device_probes"] == 2
    assert probe_stats["transfers"] - before["transfers"] == 4
    # all three were launched before any was collected — unless the
    # second never got off the ground
    assert probe_stats["overlapped_probes"] - before["overlapped_probes"] \
        == (2 if where == "_collect_device" else 1)
    # a clean call clears the count: the success is recorded only now
    monkeypatch.undo()
    assert engine.lookup_many(asked) == [records.get(i) for i in asked]
    assert breaker.stats()["groups"][str(second)]["failures"] == 0


def test_half_open_admits_one_trial_across_launch_and_collect(
        served, monkeypatch):
    store, records, ids = served
    clock = {"t": 0.0}
    breaker = DeviceBreaker(cooldown_s=5.0, failure_threshold=1,
                            clock=lambda: clock["t"])
    engine = QueryEngine(StaticSnapshots(store), region_cache_size=0,
                         breaker=breaker)
    asked = _request(ids, (1, 2, 22), 9)
    want = [records.get(i) for i in asked]
    order = list(dict.fromkeys(int(i.split(":")[0]) for i in asked))
    second = order[1]
    _plant_failure(monkeypatch, "_collect_device", 2)
    assert engine.lookup_many(asked) == want
    assert breaker.open_groups() == [second]
    monkeypatch.undo()
    # open: the group is the host's, the other two still ride the device
    probes = probe_stats["device_probes"]
    assert engine.lookup_many(asked) == want
    assert probe_stats["device_probes"] == probes + 2
    assert breaker.state(second) == "open"
    # the cooldown lapses: exactly one trial, in flight from its launch
    # to its collect — nobody else is admitted meanwhile
    clock["t"] = 6.0
    seen: list = []
    collect = Segment._collect_device

    def watching(out, nq):
        seen.append((breaker.state(second), breaker.would_allow(second),
                     breaker.allow_device(second)))
        return collect(out, nq)

    monkeypatch.setattr(Segment, "_collect_device", staticmethod(watching))
    assert engine.lookup_many(asked) == want
    # (the group is collected second: re-closed by the third's collect)
    assert seen == [("half_open", False, False)] * 2 + [("closed", True, True)]
    assert breaker.state(second) == "closed" and not breaker.open_groups()
    assert probe_stats["device_probes"] == probes + 5
