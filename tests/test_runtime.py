"""What is left of the platform preamble (``utils/runtime.py``): an explicit
``cpu`` pins the CPU, anything else leaves JAX's own selection alone (no
subprocess, no marker, no retry); the compile cache is placed from outside;
and on a ``tpu`` backend a failing Pallas kernel raises instead of quietly
selecting the jnp kernel."""

import os
import subprocess
import sys

import jax
import pytest

from annotatedvdb_tpu.utils import runtime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE = os.path.join(ROOT, ".jax_cache")


@pytest.fixture
def no_subprocess(monkeypatch):
    """Any attempt to start a process fails the test."""
    def boom(*a, **kw):
        raise AssertionError("the platform preamble started a subprocess")

    monkeypatch.setattr(subprocess, "run", boom)
    monkeypatch.setattr(subprocess, "Popen", boom)


@pytest.fixture
def cache_env(monkeypatch):
    """Isolate the cache settings: conftest placed them for the suite, and
    ``jax.config`` must come back as it was."""
    saved = {
        name: getattr(jax.config, name)
        for name in ("jax_compilation_cache_dir",
                     "jax_persistent_cache_min_entry_size_bytes",
                     "jax_persistent_cache_min_compile_time_secs")
    }
    for var in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.delenv(var, raising=False)
    yield monkeypatch
    for name, value in saved.items():
        jax.config.update(name, value)


@pytest.mark.parametrize("how", ["flag", "env"])
def test_explicit_cpu_pins_cpu(monkeypatch, no_subprocess, how):
    monkeypatch.setenv("JAX_PLATFORMS", "")
    if how == "env":
        monkeypatch.setenv("AVDB_JAX_PLATFORM", "cpu")
        choice = runtime.pin_platform("auto")
    else:
        monkeypatch.delenv("AVDB_JAX_PLATFORM", raising=False)
        choice = runtime.pin_platform("cpu")
    assert choice == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "cpu"  # children inherit it
    assert jax.config.jax_platforms == "cpu"


def test_no_choice_leaves_jax_selection_alone(monkeypatch, no_subprocess):
    monkeypatch.delenv("AVDB_JAX_PLATFORM", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "sentinel")
    before = jax.config.jax_platforms
    assert runtime.pin_platform("auto") == "auto"
    assert os.environ["JAX_PLATFORMS"] == "sentinel"
    assert jax.config.jax_platforms == before
    assert "AVDB_JAX_PLATFORM" not in os.environ


def test_cache_env_set_is_left_untouched(cache_env):
    cache_env.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    jax.config.update("jax_compilation_cache_dir", None)
    runtime.ensure_compile_cache()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    assert jax.config.jax_compilation_cache_dir is None
    assert "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES" not in os.environ
    assert runtime.compile_cache_dir() == "/some/dir"


def _cache_dir_here():
    runtime.ensure_compile_cache()
    return (os.environ["JAX_COMPILATION_CACHE_DIR"],
            jax.config.jax_compilation_cache_dir)


def _cache_dir_other_cwd(tmp_path):
    old = os.getcwd()
    os.chdir(tmp_path)
    try:
        return _cache_dir_here()
    finally:
        os.chdir(old)


def _cache_dir_child(tmp_path):
    # a child started from another directory with the variable unset: the
    # path must not depend on cwd, pid, uid, time or a temp dir
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, "-c",
         "import os, jax\n"
         "from annotatedvdb_tpu.utils import runtime\n"
         "runtime.ensure_compile_cache()\n"
         "print(os.environ['JAX_COMPILATION_CACHE_DIR'])\n"
         "print(jax.config.jax_compilation_cache_dir)\n"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return tuple(out.stdout.split())


def _cache_dir_twice(tmp_path):
    first = _cache_dir_here()
    assert _cache_dir_here() == first
    return first


@pytest.mark.parametrize("where", [
    lambda tmp_path: _cache_dir_here(),
    _cache_dir_other_cwd,
    _cache_dir_child,
    _cache_dir_twice,
], ids=["here", "other-cwd", "child-process", "twice"])
def test_cache_env_unset_is_the_checkout_path(cache_env, tmp_path, where):
    assert where(tmp_path) == (CHECKOUT_CACHE, CHECKOUT_CACHE)
    assert runtime.compile_cache_dir() == CHECKOUT_CACHE


def test_tpu_backend_pallas_failure_raises(monkeypatch):
    """On a ``tpu`` backend the Pallas kernel is THE kernel: a compile
    error propagates, no ``"jnp"`` comes back."""
    from annotatedvdb_tpu.models import pipeline

    def refused(*a, **kw):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pipeline, "annotate_pipeline_pallas_jit", refused)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        pipeline.best_annotate_pipeline()


def test_tpu_backend_parity_mismatch_names_field_and_row(monkeypatch):
    from annotatedvdb_tpu.models import pipeline

    def off_by_one(*args):
        out = pipeline.annotate_pipeline_jit(*args)
        return out._replace(
            end_location=out.end_location.at[7].add(1)
        )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pipeline, "annotate_pipeline_pallas_jit", off_by_one)
    with pytest.raises(pipeline.KernelParityError,
                       match=r"'end_location' at probe row 7"):
        pipeline.best_annotate_pipeline()


def test_cpu_backend_selects_jnp():
    from annotatedvdb_tpu.models import pipeline

    fn, name = pipeline.best_annotate_pipeline()
    assert (fn, name) == (pipeline.annotate_pipeline_jit, "jnp")
