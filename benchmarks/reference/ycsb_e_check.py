"""The plain model of YCSB workload E on the store: what every scan must
return while inserts run beside it.

A scan ``GET /region/<chr>:<start>-<end>?limit=N`` reads the stored rows
(``reference/regions_check.py`` ``StoredRows``) and the inserted records
whose chromosome and position fall in the window, in key order: position,
then the identity hash (``reference/annotate.py`` ``fnv1a`` at the store's
width), stored before inserted, then insert order.  Which inserts a scan
sees is fixed by the clock readings of the clients that sent them:

- one acknowledged before the scan was sent MUST be counted, and shown if
  it falls within the first N;
- one sent before the scan's reply arrived MAY be;
- no other row may.

Every kept scan is compared whole: region string, bin, ``count`` (exact
where no insert was in flight in its window, else between what it must
and what it may hold), ``returned``, every record in order.  Records are
``answers_check.expected_record``'s for a stored row and
``ycsb_d_check.insert_record``'s for an insert.  Imports nothing of the
program; every limit is 0.
"""

from __future__ import annotations

import json

import numpy as np

from reference.annotate import closed_form_bin, closed_form_path, fnv1a, padded
from reference.answers_check import expected_record
from reference.regions_check import StoredRows
from reference.ycsb_d_check import insert_record

#: name -> limit of every number :func:`compare_scans` returns
LIMITS = {"responses_malformed": 0, "scans_wrong": 0, "acked_not_in_scan": 0}

ENVELOPE_KEYS = ("region", "bin_level", "bin_index")


class ScanModel:
    """The stored rows of ``exp`` and the new records ``new`` (insert
    number ``j`` = index into its columns), by chromosome and position."""

    def __init__(self, exp, width: int, new: dict):
        self.exp, self.new, self.width = exp, new, int(width)
        self.stored = StoredRows(exp, width)
        ref, alt = new["ref"], new["alt"]
        self.new_h = fnv1a(np.char.str_len(ref).astype(np.int32),
                           np.char.str_len(alt).astype(np.int32),
                           padded(ref, width), padded(alt, width))
        #: per chromosome: insert numbers, and their positions, in
        #: position order
        self.inserts = []
        for ci in range(len(exp.chromosomes)):
            js = np.flatnonzero(new["chrom"] == ci)
            js = js[np.argsort(new["pos"][js], kind="stable")]
            self.inserts.append((js, new["pos"][js]))

    def window(self, ci: int, start: int, end: int, limit: int,
               must: np.ndarray, may: np.ndarray) -> tuple:
        """(stored rows counted, inserts it must count, inserts it may
        count, the rows that can be among the first ``limit`` in key order
        as ``(is_insert, index, may_be_left_out)``).  ``must`` and ``may``
        are masks over insert numbers."""
        rows = self.stored.span(ci, start, end)
        js, jpos = self.inserts[ci]
        js = js[int(np.searchsorted(jpos, start, "left")):
                int(np.searchsorted(jpos, end, "right"))]
        js = js[js < must.shape[0]]
        must_js = js[must[js]]
        may_js = js[~must[js] & may[js]]
        head = rows[:limit + must_js.shape[0] + may_js.shape[0]]
        kept = self.exp.kept
        h = fnv1a(np.char.str_len(kept["ref"][head]).astype(np.int32),
                  np.char.str_len(kept["alt"][head]).astype(np.int32),
                  padded(kept["ref"][head], self.width),
                  padded(kept["alt"][head], self.width))
        keyed = [((int(kept["pos"][i]), int(hh), 0, 0), (False, int(i),
                                                         False))
                 for i, hh in zip(head.tolist(), h.tolist())]
        for j, optional in [(j, False) for j in must_js.tolist()] \
                + [(j, True) for j in may_js.tolist()]:
            keyed.append(((int(self.new["pos"][j]), int(self.new_h[j]), 1,
                           j), (True, j, optional)))
        keyed.sort(key=lambda pair: pair[0])
        return (int(rows.shape[0]), int(must_js.shape[0]),
                int(may_js.shape[0]), [row for _key, row in keyed])

    def record(self, is_insert: bool, index: int) -> dict:
        if is_insert:
            return insert_record(self.exp.chromosomes, self.new, index)
        return expected_record(self.exp, self.exp.ident(index), index)

    def envelope(self, spec: tuple, limit: int, shown: np.ndarray,
                 generation: int) -> dict:
        """The envelope of scan ``spec`` = (chromosome index, start, end)
        on a store that holds the inserts of mask ``shown``."""
        ci, start, end = spec
        none = np.zeros_like(shown)
        n_stored, n_shown, _n, rows = self.window(ci, start, end, limit,
                                                  shown, none)
        label = self.exp.chromosomes[ci]
        level, leaf = closed_form_bin(start, end)
        records = [self.record(is_insert, index)
                   for is_insert, index, _opt in rows[:limit]]
        return {"region": f"{label}:{start}-{end}", "bin_level": level,
                "bin_index": closed_form_path(label, level, leaf),
                "count": n_stored + n_shown, "returned": len(records),
                "generation": generation, "variants": records}


def compare_scan(model: ScanModel, spec: tuple, limit: int, must, may,
                 status, body: bytes) -> dict | None:
    """One kept scan against the model: ``{"wrong", "acked_missing",
    "records"}``, or None when the body is not the envelope the read API
    describes."""
    try:
        doc = json.loads(body) if status == 200 else None
    except ValueError:
        doc = None
    if not isinstance(doc, dict) or not isinstance(doc.get("variants"),
                                                   list):
        return None
    ci, start, end = spec
    label = model.exp.chromosomes[ci]
    level, leaf = closed_form_bin(start, end)
    want = {"region": f"{label}:{start}-{end}", "bin_level": level,
            "bin_index": closed_form_path(label, level, leaf)}
    n_stored, n_must, n_may, rows = model.window(ci, start, end, limit,
                                                 must, may)
    got = doc["variants"]
    count = doc.get("count")
    wrong = any(doc.get(k) != want[k] for k in ENVELOPE_KEYS) \
        or not isinstance(count, int) \
        or not n_stored + n_must <= count <= n_stored + n_must + n_may \
        or doc.get("returned") != len(got) \
        or len(got) != min(limit, count)
    acked_missing = 0
    if isinstance(count, int) and count < n_stored + n_must:
        acked_missing = min(n_must, n_stored + n_must - count)
    # walk the key order: a row it must show is the next record, a row it
    # may show is the next record or left out
    k = 0
    for is_insert, index, optional in rows:
        if k == limit:
            break
        if k < len(got) and got[k] == model.record(is_insert, index):
            k += 1
        elif not optional:
            wrong = True
            if is_insert:
                acked_missing = max(acked_missing, 1)
            break
    if k != len(got):
        wrong = True  # a record the window does not hold, or out of order
    return {"wrong": wrong, "acked_missing": acked_missing, "records": k}


def compare_scans(model: ScanModel, scans: list) -> dict:
    """``scans``: [(spec, limit, must mask, may mask, status, body)] of
    kept window scans.  The numbers compared, by the names of
    :data:`LIMITS`, and how much was compared."""
    out = dict.fromkeys(LIMITS, 0)
    out["scans_compared"] = out["records_compared"] = 0
    first_wrong = None
    for spec, limit, must, may, status, body in scans:
        seen = compare_scan(model, spec, limit, must, may, status, body)
        if seen is None:
            out["responses_malformed"] += 1
            continue
        out["scans_compared"] += 1
        out["records_compared"] += seen["records"]
        out["acked_not_in_scan"] += seen["acked_missing"]
        if seen["wrong"]:
            out["scans_wrong"] += 1
            if first_wrong is None:
                first_wrong = {"spec": list(spec), "limit": limit,
                               "body": body[:400].decode(errors="replace")}
    out["first_wrong"] = first_wrong
    return out
