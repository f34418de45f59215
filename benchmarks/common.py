"""What every driver shares: paths, notes on stderr, child processes, small
statistics.  Never imports JAX: the children hold the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: children's logs and run artefacts worth reading after a failed run
LOG_ROOT = os.path.join(ROOT, "chiprun_out", "benchmarks")

#: exit code of a run that found no accelerator (or too few chips)
NO_DEVICE_RC = 3


class RunFailed(Exception):
    """The run cannot produce a result line; exit non-zero, print none."""

    def __init__(self, msg: str, rc: int = 1):
        super().__init__(msg)
        self.rc = rc


def note(msg: str) -> None:
    """One line for whoever reads a failed run (stderr, never stdout)."""
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def note_json(what: str, **fields) -> None:
    note(f"{what} {json.dumps(fields, default=str)}")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def write_json_atomic(path: str, doc) -> None:
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def tail(path: str, n: int = 2000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(max(os.path.getsize(path) - n, 0))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def work_dir() -> str:
    """A fresh directory for this run's files, under ``TMPDIR`` (the driver
    gives each side its own)."""
    return tempfile.mkdtemp(prefix="avdb_bench_")


def child_env(rehearse: bool) -> dict:
    """The environment of a child that runs the program.  The compile cache
    stays where ``JAX_COMPILATION_CACHE_DIR`` says if the caller set it,
    and goes to the fixed ``<checkout>/.jax_cache`` otherwise (the path is
    part of the cache's key)."""
    env = dict(os.environ)
    if "JAX_COMPILATION_CACHE_DIR" not in env:
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
        # what the program's own placement sets beside the directory
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    return env


class Children:
    """The processes a run starts; all are stopped and waited for before
    the run ends."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.procs: list = []
        os.makedirs(log_dir, exist_ok=True)

    def start(self, name: str, argv: list, env: dict):
        """Start ``python <argv>`` with stdout and stderr in
        ``<log_dir>/<name>.out|.err``; returns (process, out, err)."""
        out_path = os.path.join(self.log_dir, f"{name}.out")
        err_path = os.path.join(self.log_dir, f"{name}.err")
        with open(out_path, "wb") as out, open(err_path, "wb") as err:
            proc = subprocess.Popen(
                [sys.executable, *argv], stdout=out, stderr=err,
                stdin=subprocess.DEVNULL, cwd=ROOT, env=env,
            )
        self.procs.append(proc)
        return proc, out_path, err_path

    def wait(self, proc, name: str, err_path: str, timeout: float) -> int:
        try:
            return proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise RunFailed(f"{name}: no exit within {timeout:.0f}s\n"
                            f"{tail(err_path)}") from None

    def result(self, proc, name: str, err_path: str, result_path: str,
               timeout: float = 1100) -> dict:
        """Wait for a child that writes a result file; what it wrote.  No
        accelerator (exit code 3) and any other failure end the run."""
        rc = self.wait(proc, name, err_path, timeout)
        doc = load_json(result_path) if os.path.exists(result_path) else {}
        if rc == NO_DEVICE_RC:
            raise RunFailed(f"no accelerator: {doc.get('device')}",
                            rc=NO_DEVICE_RC)
        if rc != 0 or not doc.get("done"):
            raise RunFailed(f"{name}: exit code {rc}\n{tail(err_path)}")
        return doc

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in self.procs:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


def wait_for_file(path: str, proc, name: str, err_path: str,
                  timeout: float) -> None:
    """Until ``path`` exists; the child dying first fails the run."""
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        rc = proc.poll()
        if rc is not None and not os.path.exists(path):
            raise RunFailed(
                f"{name}: exit code {rc} before {os.path.basename(path)}\n"
                f"{tail(err_path)}",
                rc=NO_DEVICE_RC if rc == NO_DEVICE_RC else 1,
            )
        if time.monotonic() > deadline:
            raise RunFailed(f"{name}: no {os.path.basename(path)} in "
                            f"{timeout:.0f}s\n{tail(err_path)}")
        time.sleep(0.02)


def percentile(values: list, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in 0..100."""
    s = sorted(values)
    if not s:
        raise ValueError("no samples")
    k = (len(s) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def check(value, limit) -> dict:
    """One number compared beside its limit."""
    return {"value": value, "limit": limit}
