"""Faults planted in the PROGRAM, for ``tests/test_harness.py`` only.

The test puts this directory on ``PYTHONPATH`` and names a fault in
``AVDB_BENCH_TEST_FAULT``; Python imports ``sitecustomize`` at start-up, so
the children the harness starts (and only they: ``*_child.py``) run the
program with the timed path broken underneath.  The harness itself is not
told; it has to see ``correct`` come out false.

- ``load.state_unchanged``: the store's ``save`` returns without writing —
  the commit step hands its state back unchanged;
- ``load.half_left_out``: every second flushed segment is dropped;
- ``load.answer_altered``: one rs number per flushed segment is off by one,
  where the row is produced;
- ``serve.half_left_out``: a bulk lookup answers the first half of its ids;
- ``serve.answer_altered``: the renderer flips a record's multi-allelic
  flag.
"""

import os
import sys

FAULT = os.environ.get("AVDB_BENCH_TEST_FAULT", "")


def _plant(fault: str) -> None:
    if fault == "load.state_unchanged":
        from annotatedvdb_tpu.store import variant_store

        variant_store.VariantStore.save = lambda self, path: None
    elif fault in ("load.half_left_out", "load.answer_altered"):
        from annotatedvdb_tpu.store import variant_store

        original = variant_store.ChromosomeShard.append_segment
        calls = [0]

        def append_segment(self, seg):
            calls[0] += 1
            if fault == "load.half_left_out":
                if calls[0] % 2 == 0:
                    return
            elif seg.n:
                seg.cols["ref_snp"][0] += 1
            original(self, seg)

        variant_store.ChromosomeShard.append_segment = append_segment
    elif fault == "serve.half_left_out":
        from annotatedvdb_tpu.serve import engine

        original = engine.QueryEngine.lookup_many

        def lookup_many(self, ids, parsed=None):
            half = len(ids) // 2
            if half < 2:
                return original(self, ids, parsed)
            out = original(self, ids[:half],
                           None if parsed is None else parsed[:half])
            return out + [None] * (len(ids) - half)

        engine.QueryEngine.lookup_many = lookup_many
    elif fault == "serve.answer_altered":
        from annotatedvdb_tpu.serve import engine

        original = engine._render_row

        def _render_row(seg, j, label, width):
            text = original(seg, j, label, width)
            return text.replace('"is_multi_allelic":false',
                                '"is_multi_allelic":true', 1)

        engine._render_row = _render_row
    else:
        raise SystemExit(f"sitecustomize: unknown fault {fault!r}")


if FAULT and sys.argv and sys.argv[0].endswith("_child.py"):
    # a load fault belongs to the timed load: the serving cell's store is
    # built by the same child and must stay sound under a serve fault
    kind = FAULT.split(".")[0]
    is_serve_child = sys.argv[0].endswith("serve_child.py")
    if (kind == "serve") == is_serve_child:
        _plant(FAULT)
