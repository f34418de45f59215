"""Do the panels a serving window returned say what the generator says?

The reference side of ``POST /regions``: for each ``chr:start-end`` the
stored rows of that chromosome with ``start <= pos <= end`` (1-based,
inclusive on POS), ordered by position and, at one position, by the stored
identity hash (``reference/annotate.py`` ``fnv1a`` at the store's width;
the store is compacted to one segment a chromosome, so segment age breaks
no tie); ``count`` is their number, the envelope shows the first ``limit``
of them as whole records (``answers_check.expected_record``), ``returned``
is how many, ``bin_level`` / ``bin_index`` the interval's deepest enclosing
bin, ``generation`` the committed one; envelopes come in request order.

Built from the generator's kept rows alone; imports nothing of the program
and uses no index of its: the rows of an interval are found by two binary
searches over the generator's own position column, which it writes in
position order (checked).  Every envelope and every record of every
sampled reply is compared exactly; every limit is 0.
"""

from __future__ import annotations

import json

import numpy as np

from reference.annotate import closed_form_bin, closed_form_path, fnv1a, padded
from reference.answers_check import expected_record

#: name -> limit of every number :func:`compare` returns
LIMITS = {"responses_malformed": 0, "envelopes_wrong": 0, "records_wrong": 0}

ENVELOPE_KEYS = ("region", "bin_level", "bin_index", "count", "returned",
                 "generation")


class StoredRows:
    """Per chromosome: the kept rows in answer order and their positions."""

    def __init__(self, exp, width: int):
        self.exp = exp
        kept = exp.kept
        self.rows, self.pos = [], []
        for ci in range(len(exp.chromosomes)):
            rows = np.flatnonzero(kept["chrom"] == ci)
            pos = kept["pos"][rows]
            if np.any(np.diff(pos) < 0):
                raise ValueError("the generator's rows are not in position "
                                 "order")
            # rows that share a position (a multi-allelic line's alts)
            # come in the order of their identity hash
            tied = np.zeros(pos.shape[0], bool)
            same = pos[1:] == pos[:-1]
            tied[1:] |= same
            tied[:-1] |= same
            h = np.zeros(pos.shape[0], np.uint32)
            t = rows[tied]
            ref, alt = kept["ref"][t], kept["alt"][t]
            h[tied] = fnv1a(np.char.str_len(ref).astype(np.int32),
                            np.char.str_len(alt).astype(np.int32),
                            padded(ref, width), padded(alt, width))
            order = np.lexsort((h, pos))
            self.rows.append(rows[order])
            self.pos.append(pos[order])

    def span(self, ci: int, start: int, end: int,
             end_exclusive: bool = False) -> np.ndarray:
        """Kept-row indices of the interval's rows, in answer order."""
        pos = self.pos[ci]
        lo = int(np.searchsorted(pos, start, side="left"))
        hi = int(np.searchsorted(pos, end,
                                 side="left" if end_exclusive else "right"))
        return self.rows[ci][lo:max(hi, lo)]


def expected_envelope(stored: StoredRows, spec: tuple, limit,
                      generation: int, end_exclusive: bool = False) -> dict:
    """The envelope of one interval, as the read API returns it."""
    ci, start, end = spec
    exp = stored.exp
    label = exp.chromosomes[ci]
    level, leaf = closed_form_bin(start, end)
    rows = stored.span(ci, start, end, end_exclusive)
    shown = rows if limit is None else rows[:limit]
    return {
        "region": f"{label}:{start}-{end}",
        "bin_level": level,
        "bin_index": closed_form_path(label, level, leaf),
        "count": int(rows.shape[0]),
        "returned": int(shown.shape[0]),
        "generation": generation,
        "variants": [expected_record(exp, exp.ident(i), i)
                     for i in shown.tolist()],
    }


def envelopes_of(panel, body: bytes) -> list | None:
    """The per-interval envelopes of one reply, in request order; None
    when the body is not the document the read API describes."""
    try:
        doc = json.loads(body)
    except ValueError:
        return None
    results = doc.get("results") if isinstance(doc, dict) else None
    if not isinstance(results, list) or doc.get("n") != len(panel.specs) \
            or len(results) != len(panel.specs) \
            or not all(isinstance(e, dict)
                       and isinstance(e.get("variants"), list)
                       for e in results):
        return None
    return results


def compare(stored: StoredRows, sampled: list, limit,
            generation: int) -> dict:
    """``sampled``: [(panel, status, body bytes)].  The numbers compared,
    by the names of :data:`LIMITS`, and how much was compared.  An envelope
    is wrong when any of its own fields is (region string, bin, ``count``,
    ``returned``, generation — so an envelope out of request order is
    wrong) or it shows another number of records than the reference; its
    records are then compared one by one as far as both sides go."""
    out = dict.fromkeys(LIMITS, 0)
    out["envelopes_compared"] = out["records_compared"] = 0
    first_wrong = None
    for panel, status, body in sampled:
        envelopes = envelopes_of(panel, body) if status == 200 else None
        if envelopes is None:
            out["responses_malformed"] += 1
            continue
        for spec, got in zip(panel.specs, envelopes):
            want = expected_envelope(stored, spec, limit, generation)
            out["envelopes_compared"] += 1
            if any(got.get(k) != want[k] for k in ENVELOPE_KEYS) \
                    or len(got["variants"]) != len(want["variants"]):
                out["envelopes_wrong"] += 1
                if first_wrong is None:
                    first_wrong = {
                        "got": {k: got.get(k) for k in ENVELOPE_KEYS},
                        "want": {k: want[k] for k in ENVELOPE_KEYS}}
            for rec, ref in zip(got["variants"], want["variants"]):
                out["records_compared"] += 1
                if rec != ref:
                    out["records_wrong"] += 1
                    if first_wrong is None:
                        first_wrong = {"region": want["region"],
                                       "got": rec, "want": ref}
    out["first_wrong"] = first_wrong
    return out
