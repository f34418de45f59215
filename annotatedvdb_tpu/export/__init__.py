"""avdb-export: the streaming tokenized training-corpus subsystem.

Turns the columnar store into accelerator-rate model input — shuffled,
fixed-shape token/feature batches for whole chromosomes (the "feature
store for genomics models" workload; genomic-interval tokenizers, arXiv
2511.01555, over the annbatch chunked-shuffle spine, arXiv 2604.01949):

- :mod:`annotatedvdb_tpu.export.tokens` — the single-source PR-8 region
  token layout shared with serve ``tokenize=True`` (import-light);
- :mod:`annotatedvdb_tpu.export.writer` — byte-deterministic corpus part
  / manifest writers under the AVDB10xx durability protocol, plus the
  ``is_export_tmp`` debris predicate fsck attributes with (import-light);
- :mod:`annotatedvdb_tpu.export.core` — planner + batch materializer over
  the PR-16 prefetch spine and the jitted ``ops/export_pack`` kernel
  (imports jax: pulled in only by the CLI/serve/bench entry points);
- :mod:`annotatedvdb_tpu.export.stream` — the ``GET /export/stream``
  grammar and payload builder the front end serves from.

Only the import-light names are re-exported here: the serve engine imports
``export.tokens`` on its module path, and fsck imports ``is_export_tmp``,
neither of which may drag in an accelerator runtime.
"""

from annotatedvdb_tpu.export.tokens import (  # noqa: F401
    TOKEN_FIELDS,
    bin_path,
    build_region_tokens,
)
from annotatedvdb_tpu.export.writer import (  # noqa: F401
    MANIFEST_NAME,
    is_export_tmp,
    part_name,
)

__all__ = [
    "TOKEN_FIELDS",
    "bin_path",
    "build_region_tokens",
    "MANIFEST_NAME",
    "is_export_tmp",
    "part_name",
]
