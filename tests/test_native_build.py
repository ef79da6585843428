"""``native/build.py · ensure_lib``: the libraries are not tracked by git,
so whatever ``.so`` lies in ``native/`` must be the one built from the
``.c`` file beside it — and ``compile_cache.enable`` names one fixed
directory."""

import importlib.util
import os
import shutil
import threading

import jax
import pytest

from distkeras_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def build(tmp_path):
    """``native/build.py`` working in a private copy of ``native/``."""
    for name in ("build.py", "dk_transport.c", "dk_dataio.c"):
        shutil.copy(os.path.join(ROOT, "native", name), tmp_path / name)
    spec = importlib.util.spec_from_file_location(
        "_build_under_test", tmp_path / "build.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ensure_lib_builds_then_reuses_then_rebuilds_on_source_change(
        build, tmp_path):
    lib = tmp_path / "libdk_dataio.so"
    assert build.ensure_lib("libdk_dataio.so") == str(lib)
    built = lib.stat().st_mtime_ns
    build.ensure_lib("libdk_dataio.so")
    assert lib.stat().st_mtime_ns == built  # fresh: left alone
    with open(tmp_path / "dk_dataio.c", "a") as fh:
        fh.write("\n/* changed */\n")
    build.ensure_lib("libdk_dataio.so")
    assert lib.stat().st_mtime_ns > built  # stale: rebuilt


def test_ensure_lib_rebuilds_a_library_without_a_stamp(build, tmp_path):
    """What a tree copied with an old ``.so`` looks like."""
    lib = tmp_path / "libdk_transport.so"
    lib.write_bytes(b"not a library")
    build.ensure_lib("libdk_transport.so")
    assert lib.read_bytes()[:4] == b"\x7fELF"


def test_two_threads_that_find_the_library_missing_both_get_it(
        build, tmp_path, monkeypatch):
    """A server's and its client's first frames load the transport at
    the same moment (a fresh checkout's first serving run): with one
    temporary name a process the second rename found nothing, and that
    run carried its frames in Python."""
    both_compiled = threading.Barrier(2, timeout=60)
    run = build.subprocess.run

    def compile_then_meet(*args, **kw):
        out = run(*args, **kw)
        both_compiled.wait()
        return out

    monkeypatch.setattr(build.subprocess, "run", compile_then_meet)
    got = []

    def load():
        try:
            got.append(build.ensure_lib("libdk_transport.so"))
        except Exception as e:
            got.append(e)

    threads = [threading.Thread(target=load) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lib = tmp_path / "libdk_transport.so"
    assert got == [str(lib)] * 2
    assert lib.read_bytes()[:4] == b"\x7fELF"
    assert sorted(os.listdir(tmp_path)) == sorted(
        ["build.py", "dk_dataio.c", "dk_transport.c", "libdk_transport.so",
         "libdk_transport.so.sha256"])


def test_a_failed_build_raises(build, monkeypatch):
    monkeypatch.setenv("CC", "/nonexistent/cc")
    with pytest.raises(OSError):
        build.ensure_lib("libdk_dataio.so")


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_one_fixed_directory(
        monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path


def test_compile_cache_leaves_the_environments_directory_alone(
        monkeypatch, cache_dir_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # nothing set in code
