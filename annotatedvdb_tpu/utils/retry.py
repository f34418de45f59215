"""Bounded retry-with-backoff for transient I/O and device transfers.

Two failure families get the retry treatment (and ONLY these — data errors,
logic errors, and injected ``raise`` faults must propagate unchanged):

- transient filesystem errors (``EIO``/``EAGAIN``/``EBUSY``/``EINTR``/
  ``ESTALE``) on the Postgres-egress COPY writers — NFS blips and overloaded
  disks on the multi-hour export paths;
- transient accelerator-runtime errors on host->device uploads (jaxlib
  surfaces ``UNAVAILABLE``/``DEADLINE_EXCEEDED``/connection-reset strings;
  HBM OOM — ``RESOURCE_EXHAUSTED`` — is deterministic and is NOT
  retried).

Retries are bounded (default 3 attempts) with exponential backoff and are
counted in :data:`stats` for the observability exports — a load that only
succeeded through retries should say so in its metrics.
"""

from __future__ import annotations

import errno
import time

#: errno values worth a retry: transient by nature, not data-dependent.
TRANSIENT_ERRNOS = frozenset({
    errno.EIO, errno.EAGAIN, errno.EBUSY, errno.EINTR, errno.ESTALE,
})

#: errno values that mean the DISK is full (distinct from the transient
#: set: a full disk is not a blip, but it is recoverable — space frees
#: when the maintenance daemon compacts or an operator intervenes, so the
#: memtable-flush path retries these with backoff instead of wedging)
DISK_FULL_ERRNOS = frozenset({errno.ENOSPC, errno.EDQUOT})

#: substrings of accelerator-runtime errors that indicate a transient
#: transfer failure (grpc/XLA status names embedded in the message).
#: RESOURCE_EXHAUSTED is deliberately ABSENT: on a device_put it means
#: HBM OOM, which is deterministic — retrying the identical buffer only
#: delays the abort and mislabels a capacity failure as a transient one.
_TRANSIENT_DEVICE_MARKERS = (
    "UNAVAILABLE", "DEADLINE_EXCEEDED",
    "connection reset", "Socket closed",
)

#: cumulative retry accounting, exported as avdb_io_retries_total
stats = {"retries": 0, "gave_up": 0}


def is_transient_io(exc: BaseException) -> bool:
    return isinstance(exc, OSError) and exc.errno in TRANSIENT_ERRNOS


def is_disk_full(exc: BaseException) -> bool:
    return isinstance(exc, OSError) and exc.errno in DISK_FULL_ERRNOS


def is_transient_device(exc: BaseException) -> bool:
    if isinstance(exc, (KeyboardInterrupt, SystemExit)):
        return False
    msg = str(exc)
    return any(m in msg for m in _TRANSIENT_DEVICE_MARKERS)


def with_backoff(fn, *, attempts: int = 3, base_delay: float = 0.05,
                 max_delay: float = 2.0, retryable=is_transient_io,
                 log=None, what: str = "operation"):
    """Run ``fn()``; on a retryable exception, back off and re-run, at most
    ``attempts`` times total.  Non-retryable exceptions and the final
    retryable failure propagate unchanged."""
    for attempt in range(1, attempts + 1):
        try:
            return fn()
        except BaseException as exc:
            if attempt >= attempts or not retryable(exc):
                # gave_up counts RETRY EXHAUSTION only: a non-retryable
                # error after an earlier transient blip is a data/logic
                # failure, not an exhausted retry (the distinction the
                # avdb_io_retries_exhausted_total metric exists to draw)
                if attempt > 1 and retryable(exc):
                    stats["gave_up"] += 1
                raise
            stats["retries"] += 1
            delay = min(base_delay * (2 ** (attempt - 1)), max_delay)
            if log is not None:
                log(
                    f"transient failure in {what} "
                    f"(attempt {attempt}/{attempts}): {exc}; "
                    f"retrying in {delay:.2f}s"
                )
            time.sleep(delay)


def retry_preempted(run, *, retries: int = 1, base_delay: float = 0.2,
                    max_delay: float = 5.0, cancel=None, log=None,
                    what: str = "pass"):
    """Run a cooperative store pass and retry it while it reports a CLEAN
    preemption — the ONE definition of the preemption-retry policy shared
    by the maintenance daemon, ``doctor compact --retries``, and the chaos
    soak.

    ``run()`` must return a report dict; a report whose ``status`` is
    ``"aborted"`` means another writer preempted the pass under the
    cooperative commit protocol (store untouched, retry-safe by contract),
    so the pass is re-run after an exponential backoff, at most
    ``retries`` more times.  Every other status — ``compacted``/``noop``/
    ``flushed``/``error`` — and every exception returns/propagates
    unchanged: hard failures must alert, not spin.

    ``cancel`` is the CALLER's own abort flag (the same callable the pass
    observes): an abort the caller itself requested — SIGTERM, daemon
    stop, a hot-health yield — is not a preemption to retry, and
    re-running would only delay the shutdown (or re-abort against the
    same still-hot condition) behind backoff sleeps.
    """
    report = run()
    attempt = 0
    while (isinstance(report, dict) and report.get("status") == "aborted"
           and attempt < retries
           and not (cancel is not None and cancel())):
        attempt += 1
        delay = min(base_delay * (2 ** (attempt - 1)), max_delay)
        if log is not None:
            log(f"{what} preempted cleanly "
                f"({report.get('reason', 'another writer committed')}); "
                f"retry {attempt}/{retries} in {delay:.2f}s")
        time.sleep(delay)
        report = run()
    return report


def device_put(x, *, attempts: int = 3, device=None):
    """``jax.device_put`` with bounded retry on transient runtime errors —
    the upload half of every dispatch.
    ``device`` pins the destination (the residency manager's
    chromosome->device placement); None keeps the default device."""
    import jax

    return with_backoff(
        lambda: jax.device_put(x, device),
        attempts=attempts, retryable=is_transient_device,
        what="device transfer",
    )
