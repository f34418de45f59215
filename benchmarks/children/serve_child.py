"""The process that holds the chip while the store is served.

``python benchmarks/children/serve_child.py <control dir> -- <serve argv>``
calls the program's own entry point,
``annotatedvdb_tpu.cli.serve.main(<serve argv>)``, in the main thread (it
installs signal handlers) — the argv of ``python -m annotatedvdb_tpu serve``.

Only the process that holds the chip can trace it or read its memory, so a
side thread answers two requests the driver makes by files in the control
directory (polled five times a second; it does nothing else):

- ``trace.request`` ``{"dir", "seconds"}`` -> a ``jax.profiler`` capture of
  that many seconds (Python tracer off unless the request carries a
  ``python_tracer_level``, for diagnosis by hand), then ``trace.done``
  ``{"t0", "t1"}`` by ``time.monotonic``;
- ``device.request`` -> ``device.json``: the device as JAX reports it and
  ``peak_bytes_in_use`` of the fullest chip.

stdout and stderr belong to the program and go to the log files the parent
opened; the address line the parent waits for is the program's own banner.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

from _report import device_report, start_trace, write_json


def _take(path: str) -> dict | None:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    os.remove(path)
    return doc


def _side(control: str) -> None:
    while True:
        time.sleep(0.2)
        request = _take(os.path.join(control, "trace.request"))
        if request is not None:
            import jax

            start_trace(request["dir"],
                        int(request.get("python_tracer_level", 0)))
            t0 = time.monotonic()
            time.sleep(request["seconds"])
            t1 = time.monotonic()
            jax.profiler.stop_trace()  # collecting takes seconds: not traced
            write_json(os.path.join(control, "trace.done"),
                       {"t0": t0, "t1": t1})
        if _take(os.path.join(control, "device.request")) is not None:
            write_json(os.path.join(control, "device.json"), device_report())


def main(argv: list) -> int:
    control = argv[0]
    serve_argv = argv[argv.index("--") + 1:]
    from annotatedvdb_tpu.cli import serve

    threading.Thread(target=_side, args=(control,), daemon=True).start()
    return int(serve.main(serve_argv) or 0)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
