"""The plain model of YCSB workload D on the store: what every read, every
read-back and the store on disk must hold.

:class:`Model` is a dict of records by identity: the stored rows, then
each acknowledged insert — insert-only and first-wins (an identity already
held keeps its record, as the store's loaders and its upsert route
promise).  A record is rendered by ``reference/answers_check.py``'s
scalar reference for a stored row and by :func:`insert_record` — the same
fields, from the insert's own columns — for an insert.  Imports nothing of
the program; every comparison is exact and every limit is 0.
"""

from __future__ import annotations

import json

from reference.annotate import (closed_form_bin, closed_form_path,
                                infer_end_location)
from reference.answers_check import expected_record
from traffic import inserts as traffic_inserts

#: name -> limit of every number the comparisons return
LIMITS = {"responses_malformed": 0, "records_wrong": 0, "reads_wrong": 0,
          "acked_not_read_back": 0, "acked_not_durable": 0}


def insert_record(chromosomes, cols: dict, j: int) -> dict:
    """The record of new record ``j`` as the read API returns it."""
    ident = traffic_inserts.ident(chromosomes, cols, j)
    label, pos, ref, alt = ident.split(":")
    pos = int(pos)
    level, leaf = closed_form_bin(pos, infer_end_location(ref, alt, pos))
    rs = f"rs{int(cols['rs'][j])}"
    return {
        "primary_key": f"{ident}:{rs}", "metaseq_id": ident,
        "chromosome": label, "position": pos, "ref": ref, "alt": alt,
        "ref_snp": rs, "is_multi_allelic": False, "is_adsp_variant": None,
        "bin_index": closed_form_path(label, level, leaf),
        "annotations": traffic_inserts.annotations(
            float(cols["freq"][j])) or {},
    }


class Model:
    """The store as YCSB D's semantics say it is: the ``exp`` rows in
    recency order ``order`` (keys ``0 .. n_base - 1``), then the
    acknowledged inserts (keys ``n_base + j``, ``j`` in insert order)."""

    def __init__(self, exp, order, new: dict):
        self.exp, self.order, self.new = exp, order, new
        self.n_base = exp.n_rows
        #: identity -> key of every record held (first wins)
        self.held: dict = {}
        self.acked: list = []

    def insert(self, j: int) -> bool:
        """Acknowledge new record ``j``; False when the identity was
        already held (the insert is shadowed, nothing changes).  New
        records are drawn absent from the stored rows
        (``traffic/inserts.py`` checks it), so only an insert can hold
        one first."""
        ident = traffic_inserts.ident(self.exp.chromosomes, self.new, j)
        if ident in self.held:
            return False
        self.held[ident] = self.n_base + j
        self.acked.append(j)
        return True

    def ident(self, key: int) -> str:
        if key < self.n_base:
            return self.exp.ident(int(self.order[key]))
        return traffic_inserts.ident(self.exp.chromosomes, self.new,
                                     key - self.n_base)

    def record(self, key: int) -> dict | None:
        """The record of ``key``; None for an insert not acknowledged."""
        if key < self.n_base:
            i = int(self.order[key])
            return expected_record(self.exp, self.exp.ident(i), i)
        j = key - self.n_base
        if self.held.get(self.ident(key)) != key:
            return None
        return insert_record(self.exp.chromosomes, self.new, j)


def compare_reads(model: Model, reads: list) -> dict:
    """``reads``: [(key, status, body)] of kept window reads.  Every key a
    read names exists when it is sent (a stored row, or an insert every
    earlier insert preceded in acknowledgement), so a 404 is wrong."""
    out = {"responses_malformed": 0, "reads_wrong": 0, "reads_compared": 0}
    first_wrong = None
    for key, status, body in reads:
        try:
            got = json.loads(body) if status in (200, 404) else None
        except ValueError:
            got = None
        if not isinstance(got, dict):
            out["responses_malformed"] += 1
            continue
        got = got if "metaseq_id" in got else None
        want = model.record(key)
        out["reads_compared"] += 1
        if got != want or want is None:
            out["reads_wrong"] += 1
            if first_wrong is None:
                first_wrong = {"id": model.ident(key), "got": got,
                               "want": want}
    out["first_wrong"] = first_wrong
    return out


def compare_read_back(model: Model, replies: list) -> dict:
    """``replies``: [(js, status, body)] — one ``POST /variants`` of the
    acknowledged inserts ``js`` each.  Every one must come back whole."""
    out = {"responses_malformed": 0, "records_wrong": 0,
           "acked_not_read_back": 0, "read_back": 0}
    for js, status, body in replies:
        try:
            doc = json.loads(body) if status == 200 else None
        except ValueError:
            doc = None
        results = doc.get("results") if isinstance(doc, dict) else None
        if not isinstance(results, list) or len(results) != len(js):
            out["responses_malformed"] += 1
            out["acked_not_read_back"] += len(js)
            continue
        for j, rec in zip(js, results):
            out["read_back"] += 1
            if rec is None:
                out["acked_not_read_back"] += 1
            elif rec != insert_record(model.exp.chromosomes, model.new, j):
                out["records_wrong"] += 1
    return out


def durable_fields(cols: dict, j: int) -> list:
    """What the store or the WAL must hold of new record ``j``: its rs
    number and its annotations, as the offline check reads them back."""
    return [int(cols["rs"][j]),
            traffic_inserts.annotations(float(cols["freq"][j])) or {}]


def compare_durable(model: Model, found: dict) -> dict:
    """``found``: {identity: {"store": fields or None, "wal": fields or
    None}} from the committed store and the WAL after a SIGKILL.  Every
    acknowledged insert is in one of them, exactly."""
    lost = 0
    for j in model.acked:
        ident = traffic_inserts.ident(model.exp.chromosomes, model.new, j)
        want = durable_fields(model.new, j)
        got = found.get(ident) or {}
        if want not in (got.get("store"), got.get("wal")):
            lost += 1
    return {"acked_not_durable": lost, "acked": len(model.acked)}
