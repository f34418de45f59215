"""avdb-serve: TPU-resident query & serving subsystem.

The read path over a loaded :class:`~annotatedvdb_tpu.store.VariantStore`:

- :mod:`~annotatedvdb_tpu.serve.engine`    — point / bulk / region queries;
- :mod:`~annotatedvdb_tpu.serve.batcher`   — the knobs, admission error
  and profiler span of the point-query batcher (the batcher itself,
  ``LoopBatcher``, lives in :mod:`~annotatedvdb_tpu.serve.aio`);
- :mod:`~annotatedvdb_tpu.serve.snapshot`  — generation pinning so loader
  commits never tear in-flight reads (freshness checks coalesce to one
  manifest ``stat`` per ``AVDB_SERVE_SNAPSHOT_TTL_MS`` window);
- :mod:`~annotatedvdb_tpu.serve.residency` — HBM hot-set residency under
  an ``AVDB_SERVE_HBM_BUDGET`` byte budget (hot segments device-resident,
  cold ones serve from host);
- :mod:`~annotatedvdb_tpu.serve.aio`       — the front end: an asyncio
  event-loop server (continuous batching of point queries on the loop,
  per-client weighted admission, chunked region streaming; imported
  lazily by the CLI);
- :mod:`~annotatedvdb_tpu.serve.fleet`     — multi-process serve fleet
  (N workers on one port via SO_REUSEPORT or parent accept handoff, a
  supervisor that restarts dead workers and drains on SIGTERM);
- :mod:`~annotatedvdb_tpu.serve.http`      — the API's grammar: route
  spellings, body/query parsers, payload builders, message constants and
  ``ServeContext``.

Entry point: ``python -m annotatedvdb_tpu serve --storeDir <dir>``.
"""

from annotatedvdb_tpu.serve.batcher import QueueFull
from annotatedvdb_tpu.serve.engine import (
    IntervalIndex,
    QueryEngine,
    QueryError,
    RegionPage,
    RegionsResult,
    parse_region,
    parse_variant_id,
    render_variant,
)
from annotatedvdb_tpu.serve.mesh_exec import MeshExecutor, serve_mesh_executor
from annotatedvdb_tpu.serve.residency import ResidencyManager
from annotatedvdb_tpu.serve.resilience import (
    DeadlineExceeded,
    DeviceBreaker,
    OverloadGovernor,
    PointCache,
)
from annotatedvdb_tpu.serve.snapshot import (
    MemtableSnapshots,
    SnapshotManager,
    StaticSnapshots,
    StoreSnapshot,
)

__all__ = [
    "DeadlineExceeded", "DeviceBreaker", "IntervalIndex",
    "MemtableSnapshots", "MeshExecutor", "serve_mesh_executor",
    "OverloadGovernor", "PointCache",
    "QueueFull", "QueryEngine", "QueryError", "RegionPage",
    "RegionsResult", "ResidencyManager", "SnapshotManager",
    "StaticSnapshots", "StoreSnapshot", "parse_region", "parse_variant_id",
    "render_variant",
]
