"""In-process bench.py units — cheap pins that belong in the quick tier
(tests/test_bench.py is soak-marked wholesale: every test there executes
bench.py in a subprocess)."""

import functools
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.cache
def _load_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(_ROOT, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = bench
    spec.loader.exec_module(bench)
    return bench


def test_git_head_matches_shared_helper(tmp_path):
    """bench.py's _git_head must stay a thin delegate of the shared
    provenance helper (one sha-stamping implementation for every entry
    point) — which answers None, not an exception, outside a git checkout
    (the copy of the tree that runs on the chip is not one)."""
    from horovod_tpu.core.provenance import git_head_sha

    bench = _load_bench()
    assert bench._git_head() == git_head_sha(_ROOT)
    assert bench._git_head()  # this repo is a git checkout
    assert git_head_sha(str(tmp_path)) is None


def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set the caller chose the place:
    the helper reports it and configures nothing."""
    import jax

    from horovod_tpu.core.platform import setup_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert setup_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before
    # scopes are metadata: a fetched executable must be this program's
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    """Unset, the cache is <checkout>/.jax_bench_cache — the same path on
    every call and from every entry point, never a temp name."""
    import jax

    from horovod_tpu.core.platform import setup_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        want = os.path.join(_ROOT, ".jax_bench_cache")
        assert setup_compile_cache() == want
        assert setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peak_tflops_is_a_table_not_a_guess(monkeypatch):
    """The MFU denominator comes from a table keyed by device_kind: the
    v5e's published peak, nothing on CPU, and an error — never a default,
    never an env override — for a kind that is not in the table."""
    import types

    import pytest

    bench = _load_bench()
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    other = types.SimpleNamespace(platform="tpu", device_kind="TPU v9000")
    assert bench._peak_tflops(v5e) == 197.0
    assert bench._peak_tflops(cpu) is None
    monkeypatch.setenv("HOROVOD_BENCH_PEAK_TFLOPS", "123")
    with pytest.raises(ValueError, match="TPU v9000"):
        bench._peak_tflops(other)


def test_native_core_rebuild_is_keyed_on_source_digest(tmp_path,
                                                       monkeypatch):
    """A build/libhtpu_core.so carried along with a copy of the tree has
    arbitrary mtimes: only a stored digest equal to the digest of the
    sources beside it makes the library current, and a forced make (-B)
    rebuilds it otherwise."""
    import importlib.util
    import shutil

    src_dir = os.path.join(_ROOT, "horovod_tpu", "cc")
    cc_dir = tmp_path / "cc"
    shutil.copytree(src_dir, cc_dir, ignore=shutil.ignore_patterns(
        "build", "__pycache__"))
    spec = importlib.util.spec_from_file_location(
        "_cc_under_test", str(cc_dir / "__init__.py"))
    cc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cc)

    assert cc._needs_build()  # no library yet
    (cc_dir / "build").mkdir()
    lib = cc_dir / "build" / "libhtpu_core.so"
    lib.write_bytes(b"stale")
    future = os.path.getmtime(lib) + 3600
    os.utime(lib, (future, future))  # newer than every source
    assert cc._needs_build()  # no digest beside it: not trusted

    calls = []

    def fake_make(argv, **kwargs):
        calls.append(argv)

    monkeypatch.setattr(cc.subprocess, "run", fake_make)
    cc._build_locked()
    assert calls == [["make", "-B", "-C", str(cc_dir)]]
    assert not cc._needs_build()  # digest now recorded

    with open(cc_dir / "negotiator.cc", "a") as fh:
        fh.write("// edited\n")
    os.utime(lib, (future, future))
    assert cc._needs_build()  # library is newer, sources differ: rebuild
