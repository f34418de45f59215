"""Platform choice and compile-cache placement for every entry point.

JAX selects the backend itself: on a host with a TPU that is the TPU, and
a process that cannot reach it fails at its first backend touch.  Nothing
here probes, retries or falls back — the only thing an entry point may do
is pin the CPU *explicitly* (``--platform cpu``, ``AVDB_JAX_PLATFORM=cpu``,
:func:`force_cpu_mesh`), which tests and host-only tools use.

The persistent compilation cache is placed from outside: when
``JAX_COMPILATION_CACHE_DIR`` is set it is left alone, otherwise every
entry point uses the one fixed directory ``<checkout>/.jax_cache`` (the
path is part of the cache key, so it must never move between processes).
"""

from __future__ import annotations

import os
import threading

#: the fixed fallback cache directory: ``<checkout>/.jax_cache``
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """Where this process tree keeps compiled programs: the caller's
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
    Touches neither JAX nor the environment."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", _DEFAULT_CACHE_DIR)


def ensure_compile_cache() -> None:
    """Point JAX's persistent compilation cache at the fixed checkout path
    unless the caller already placed it.  Must run before the first jit.

    With ``JAX_COMPILATION_CACHE_DIR`` in the environment (any value, empty
    included — that is how a caller turns the cache off) nothing is
    touched.  Otherwise the path goes into the environment (children
    inherit it) and into ``jax.config`` (this process may have imported
    jax, which reads the variable at import, already)."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _DEFAULT_CACHE_DIR
    # cache every program that took a second to compile, whatever its size
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes",
        int(os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"]),
    )
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]),
    )


#: what this process spent compiling, from JAX's own monitoring events —
#: cumulative, like ``utils.retry.stats``; read through compile_summary()
_compile_tally = {"seconds": 0.0, "programs": 0,
                  "cache_hits": 0, "cache_misses": 0}
_compile_lock = threading.Lock()
_compile_watch_on = False

_COMPILE_DURATION = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def _watch_compiles() -> None:
    """Register (once) the listeners behind :func:`compile_summary`."""
    global _compile_watch_on
    with _compile_lock:
        if _compile_watch_on:
            return
        _compile_watch_on = True
    import jax.monitoring

    def on_duration(event, seconds, **_kw):
        if event == _COMPILE_DURATION:
            with _compile_lock:
                _compile_tally["seconds"] += seconds
                _compile_tally["programs"] += 1

    def on_event(event, **_kw):
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            with _compile_lock:
                _compile_tally[key] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def compile_summary() -> dict:
    """Seconds this process spent in backend compiles (a persistent-cache
    hit counts its retrieval), programs compiled, cache hits/misses, and
    the cache directory — set-up time a load summary and the server's
    ``/stats`` report apart from the work itself."""
    with _compile_lock:
        out = dict(_compile_tally)
    out["seconds"] = round(out["seconds"], 3)
    out["cache_dir"] = compile_cache_dir()
    return out


def _pin_cpu(n_virtual_devices: int | None = None) -> None:
    if n_virtual_devices is not None:
        flag = f"--xla_force_host_platform_device_count={n_virtual_devices}"
        # replace an existing count (any value) rather than appending a dup
        parts = [
            p
            for p in os.environ.get("XLA_FLAGS", "").split()
            if not p.startswith("--xla_force_host_platform_device_count")
        ]
        os.environ["XLA_FLAGS"] = " ".join(parts + [flag])
    os.environ["JAX_PLATFORMS"] = "cpu"  # children inherit the pin
    import jax

    jax.config.update("jax_platforms", "cpu")


def pin_platform(prefer: str = "auto") -> str:
    """Entry-point preamble: honor an explicit CPU pin, place the compile
    cache; returns ``"cpu"`` or ``"auto"``.

    ``AVDB_JAX_PLATFORM`` wins over ``prefer`` (the ``--platform`` flag).
    ``cpu`` pins the CPU backend; anything else leaves JAX's own selection
    alone.  Must run before the first backend touch (jit dispatch,
    ``jax.devices()``, ``jax.default_backend()``)."""
    explicit = os.environ.get("AVDB_JAX_PLATFORM", "").strip().lower()
    choice = explicit or (prefer or "auto").strip().lower()
    if choice == "cpu":
        _pin_cpu()
    else:
        choice = "auto"
    ensure_compile_cache()
    _watch_compiles()
    return choice


def force_cpu_mesh(n_devices: int) -> None:
    """Pin a virtual ``n_devices``-device CPU platform (multi-chip dry runs,
    SURVEY.md §4d).  Must run before backend init; raises if the backend is
    already up with too few CPU devices to honor the request."""
    _pin_cpu(n_virtual_devices=n_devices)
    ensure_compile_cache()
    import jax

    n = len(jax.devices("cpu"))
    if n < n_devices:
        raise RuntimeError(
            f"virtual CPU mesh needs {n_devices} devices but the backend "
            f"initialized with {n}; force_cpu_mesh() must run before any "
            "JAX backend touch in this process"
        )


def device_summary() -> dict:
    """The device this process runs on, as JAX reports it (initializes the
    backend): what every load summary and the server's ``/stats`` print so
    a reader can tell a chip run from a CPU run."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
