"""Do the bodies a serving window returned say what the generator says?

The reference side: for each id a request named, either nothing (the id
was never generated) or the whole record, built from the generator's kept
rows and ``reference/annotate.py`` — key, identity, rs number,
multi-allelic flag, bin path, frequency annotation.  Imports nothing of
the program.  Every record of every sampled response is compared exactly;
both limits are 0.
"""

from __future__ import annotations

import json

import numpy as np

from reference.annotate import (FREQ_POPULATION, closed_form_bin,
                                closed_form_path, infer_end_location)

#: name -> limit of every number :func:`compare` returns
LIMITS = {"responses_malformed": 0, "records_wrong": 0}


def expected_record(exp, ident: str, i: int) -> dict:
    """The record of kept row ``i`` as the read API returns it."""
    label, pos, ref, alt = ident.split(":")
    pos = int(pos)
    level, leaf = closed_form_bin(pos, infer_end_location(ref, alt, pos))
    rs = f"rs{int(exp.kept['rs'][i])}"
    freq = float(exp.kept["freq"][i])
    annotations = {} if np.isnan(freq) else {
        "allele_frequencies": {FREQ_POPULATION: {"gmaf": freq}}}
    return {
        "primary_key": f"{ident}:{rs}", "metaseq_id": ident,
        "chromosome": label, "position": pos, "ref": ref, "alt": alt,
        "ref_snp": rs, "is_multi_allelic": bool(exp.kept["multi"][i]),
        "is_adsp_variant": None,
        "bin_index": closed_form_path(label, level, leaf),
        "annotations": annotations,
    }


def records_of(request, body: bytes) -> list | None:
    """The per-id records of one response, in request order; None when the
    body is not the envelope the read API documents."""
    try:
        doc = json.loads(body)
    except ValueError:
        return None
    if request.method == "GET":  # a point read: the record, or a 404 body
        return [doc if "metaseq_id" in doc else None]
    results = doc.get("results") if isinstance(doc, dict) else None
    if not isinstance(results, list) or doc.get("n") != len(request.ids) \
            or len(results) != len(request.ids):
        return None
    return results


def compare(exp, sampled: list) -> dict:
    """``sampled``: [(request, status, body bytes)].  The numbers compared,
    by the names of :data:`LIMITS`, and how much was compared."""
    out = dict.fromkeys(LIMITS, 0)
    out["records_compared"] = 0
    first_wrong = None
    for request, status, body in sampled:
        records = records_of(request, body) if status in (200, 404) else None
        if records is None:
            out["responses_malformed"] += 1
            continue
        for ident, i, rec in zip(request.ids, request.rows, records):
            want = None if i < 0 else expected_record(exp, ident, i)
            out["records_compared"] += 1
            if rec != want:
                out["records_wrong"] += 1
                if first_wrong is None:
                    first_wrong = {"id": ident, "got": rec, "want": want}
    out["first_wrong"] = first_wrong
    return out
