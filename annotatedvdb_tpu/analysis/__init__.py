"""Project-native static analysis (``avdb-check``).

Rule families (each with fixture-backed tests in
``tests/test_avdb_check.py`` and a catalog entry in README "Static
analysis & code health"):

==========  ============================================================
AVDB001     file does not parse (nothing else checked there)
AVDB1xx     trace-safety: host side effects / data-dependent branches in
            jit/pjit/shard_map code (``rules_trace``)
AVDB2xx     lock-discipline: ``#: guarded by self._lock`` attributes
            accessed outside their lock (``rules_locks``)
AVDB3xx     registry-drift: fault points vs ``faults.POINTS``; metric
            name/kind/label consistency; README refs (``rules_registry``)
AVDB4xx     env-var drift: ``AVDB_*`` reads vs ``config.ENV_VARS`` vs
            README (``rules_env``)
AVDB5xx     CLI-contract: the six loader CLIs' shared flag set
            (``rules_cli``)
AVDB6xx     hygiene: bare except, silent Exception-pass, mutable default
            args, stale noqa suppressions (``rules_hygiene``)
AVDB7xx     async-safety: blocking calls on the event loop, await under a
            sync lock (``rules_async``)
AVDB9xx     device/host twin contract: jitted ``ops/`` kernels vs the
            ``ops.TWINS`` registry and its parity tests (``rules_twins``)
AVDB10xx    durability protocol: fsync-before-rename, tmp-family
            attribution vs ``store/fsck.py`` and the corrupt_store
            fixtures, manifest-commit crash points, WAL/HTTP ack
            ordering (``rules_durability``)
==========  ============================================================

Entry point: ``python tools/avdb_check.py [--json] [--diff REV]
[paths...]`` — exit codes 0 (clean) / 1 (findings) / 2 (usage or
internal error), mirroring ``tools/store_fsck.py``.  Suppress a finding
with ``# avdb: noqa[CODE] -- reason``.

The package also carries the DYNAMIC half of the suite:
``analysis/lockorder`` — the lock-order/deadlock detector behind
``AVDB_LOCK_TRACE=1`` (see ``utils.locks.make_lock``): per-thread
acquisition-order graph, cycle detection, held-duration histograms —
and ``analysis/iotrace`` — the crash-consistency sanitizer behind
``AVDB_IO_TRACE=1`` (see ``utils.io``): a happens-before recorder over
the store's durable I/O flagging rename-before-fsync, unlinks of
manifest-referenced files, and missing directory fsyncs.
"""

from annotatedvdb_tpu.analysis.core import (  # noqa: F401 (public API)
    Finding,
    LOADER_CLIS,
    iter_python_files,
    run_paths,
)

__all__ = ["Finding", "LOADER_CLIS", "iter_python_files", "run_paths"]
