"""A write-path fault planted in the PROGRAM, for
``tests/test_ycsb_d_faults.py`` only (the ``faults/sitecustomize.py``
pattern, in a directory of its own).

- ``serve.ack_before_fsync``: the WAL append writes and flushes its frame
  and returns without the fsync, so the upsert is acknowledged with its
  frame in the page cache alone.  A SIGKILL does not drop the page cache,
  so the offline durability check still finds every frame; the cell's
  ``fsync_short`` has to see it.
"""

import os
import sys

FAULT = os.environ.get("AVDB_BENCH_TEST_FAULT", "")


def _plant(fault: str) -> None:
    if fault != "serve.ack_before_fsync":
        raise SystemExit(f"sitecustomize: unknown fault {fault!r}")
    from annotatedvdb_tpu.store import wal

    def append(self, payload, trace=None):
        blob = wal.json.dumps(payload, separators=(",", ":")).encode()
        frame = wal._FRAME.pack(len(blob), wal.zlib.crc32(blob)) + blob
        with self._lock:
            if self._f is None:
                path = self._path(self._seq)
                if not os.path.exists(path):
                    self._create(self._seq)
                self._f = wal.tio.open(path, "ab")
            self._f.write(frame)
            self._f.flush()
        return len(frame)

    wal.WriteAheadLog.append = append


if FAULT and sys.argv and sys.argv[0].endswith("serve_child.py"):
    _plant(FAULT)
