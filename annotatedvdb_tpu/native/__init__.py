"""ctypes binding for the native ingest runtime (``native/avdb_native.cpp``).

The shared library builds lazily on first use with the system ``g++`` into a
content-hashed cache next to this package, so a source change triggers a
rebuild and stale binaries are never loaded.  Import never fails: when no
compiler is available, ``load()`` returns None and callers keep the pure
Python path (``io/vcf.py`` engine="python").
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_SOURCE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native", "avdb_native.cpp",
)
_CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")

_lock = threading.Lock()
_lib = None
_lib_error: str | None = None


def _host_tag() -> bytes:
    """CPU identity folded into the build digest: -march=native binaries
    are only valid on the microarchitecture that built them, so a cache
    directory carried to a different host (image copy, shared FS) must
    rebuild rather than SIGILL on the first vectorized call."""
    import platform

    tag = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("model name", "flags")):
                    tag += line
                    if line.startswith("flags"):
                        break
    except OSError:
        pass
    return tag.encode()


def build_shared_lib(source: str, stem: str, extra_flags: tuple = ()) -> str:
    """Content-hashed lazy g++ build shared by every native component
    (the VCF tokenizer, the VEP transformer, the pyfast extension): a
    source change triggers a rebuild, stale binaries are never loaded,
    and the tmp+rename publish is atomic under concurrent builders.
    Compiler stderr is preserved in the raised error on failure."""
    with open(source, "rb") as f:
        digest = hashlib.sha256(
            f.read() + repr(extra_flags).encode() + _host_tag()
        ).hexdigest()[:16]
    so_path = os.path.join(_CACHE_DIR, f"{stem}-{digest}.so")
    if os.path.exists(so_path):
        return so_path
    os.makedirs(_CACHE_DIR, exist_ok=True)
    tmp = so_path + f".tmp{os.getpid()}"
    try:
        subprocess.run(
            # -march=native: these libs are built AND run on the same
            # machine (content-hashed local cache), so vectorized byte
            # loops may use whatever the host offers
            ["g++", "-O3", "-march=native", "-shared", "-fPIC",
             "-std=c++17", *extra_flags, "-o", tmp, source],
            check=True, capture_output=True, text=True,
        )
    except subprocess.CalledProcessError as err:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise RuntimeError(
            f"native build of {stem} failed:\n{err.stderr[-2000:]}"
        ) from err
    os.replace(tmp, so_path)  # atomic under concurrent builders
    return so_path


def _build() -> str:
    return build_shared_lib(_SOURCE, "avdb_native")


def load():
    """The loaded CDLL, building if needed; None when unavailable."""
    global _lib, _lib_error
    if _lib is not None or _lib_error is not None:
        return _lib
    with _lock:
        if _lib is not None or _lib_error is not None:
            return _lib
        from annotatedvdb_tpu.utils.profiling import startup_phase

        try:
            with startup_phase("native"):  # build (first run) + dlopen
                lib = ctypes.CDLL(_build())
        except (OSError, RuntimeError, subprocess.CalledProcessError,
                FileNotFoundError) as err:
            _lib_error = str(err)
            return None
        c = ctypes
        lib.avdb_parse_vcf_chunk.restype = c.c_int64
        lib.avdb_parse_vcf_chunk.argtypes = [
            c.c_char_p, c.c_int64, c.c_int32, c.c_int64, c.c_int64,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,   # chrom,pos,ref,alt
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,   # rlen,alen,multi,line
            c.c_void_p, c.c_void_p,                            # ref_off, alt_off
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,   # id, qual
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,   # filter, info
            c.c_void_p, c.c_void_p,                            # format
            c.c_void_p, c.c_void_p,                            # altcol
            c.c_void_p, c.c_void_p,                            # alt_index, n_alts
            c.c_void_p, c.c_void_p,                            # rs_number, rs_weird
            c.c_void_p, c.c_void_p,                            # id_verbatim, has_freq
            c.c_void_p,                                        # hash
            c.c_void_p, c.c_void_p, c.c_void_p,               # ref_packed, alt_packed, pack_ok
            c.c_int32, c.c_int32,                              # identity_only, want_packed
            c.c_void_p, c.c_void_p, c.c_void_p,               # counters, consumed, need_more
        ]
        rows = [
            c.c_int64, c.c_int32,                              # n, width
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,   # chrom,pos,ref,alt
            c.c_void_p, c.c_void_p,                            # rlen, alen
        ]
        lib.avdb_mapping_fast_rows.restype = c.c_int64
        lib.avdb_mapping_fast_rows.argtypes = rows + [c.c_void_p]  # fast
        lib.avdb_mapping_lines.restype = c.c_int64
        lib.avdb_mapping_lines.argtypes = rows + [
            c.c_void_p,                                        # rs_number
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64,    # path idx, bytes, off, n
            c.c_void_p,                                        # fast
            c.c_void_p, c.c_void_p,                            # slow bytes, end
            c.c_void_p, c.c_int64,                            # out, cap
        ]
        lib.avdb_freq_texts.restype = c.c_int64
        lib.avdb_freq_texts.argtypes = [
            c.c_char_p, c.c_int64,                             # window, n
            c.c_void_p, c.c_void_p,                            # info_off, info_len
            c.c_void_p, c.c_void_p,                            # n_alts, alt_index
            c.c_void_p, c.c_void_p, c.c_int64,                # status, out, cap
        ]
        # a pass of microseconds on the serving loop's thread keeps the
        # GIL (PyDLL): released, the loop would queue to take it back
        # behind whichever thread took it meanwhile
        lib.avdb_identity_columns = ctypes.PyDLL(
            lib._name).avdb_identity_columns
        lib.avdb_identity_columns.restype = c.c_int64
        lib.avdb_identity_columns.argtypes = [
            c.c_char_p, c.c_void_p, c.c_int64,                # ref bytes, lens, total
            c.c_char_p, c.c_void_p, c.c_int64,                # alt bytes, lens, total
            c.c_int64, c.c_int32, c.c_void_p, c.c_int32,      # n, width, primepow, n
            c.c_void_p, c.c_void_p, c.c_void_p,               # ref, alt, h
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


def status() -> dict:
    """What :func:`load` found so far, without building anything:
    ``{"loaded": bool, "error": str | None}`` — load summaries print it,
    so a load that took the Python tokenizer because the build failed says
    so instead of only being slower."""
    return {"loaded": _lib is not None, "error": _lib_error}
