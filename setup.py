"""Packaging + native-core build for horovod_tpu.

Rebuild of the reference's feature-probe build (``setup.py:84-141,477-592``)
for the TPU stack. The reference compiles its C++ common core into every
framework extension after probing the toolchain (C++ flags, AVX/F16C, MPI,
CUDA, NCCL, DDL) and honoring an env-var build matrix
(``HOROVOD_WITH[OUT]_*``, ``HOROVOD_GPU_ALLREDUCE``, ...). Here the data
plane is XLA — there is no MPI/CUDA/NCCL to probe — so the native surface
is the controller core (negotiator, GP/Bayesian autotuner, timeline
writer) built as one shared library, with:

* compiler flag probing (newest usable -std=, best -O level) in the spirit
  of ``get_cpp_flags`` (``setup.py:84-115``);
* an env-var matrix: ``HOROVOD_TPU_WITHOUT_NATIVE=1`` skips the native
  build (pure-Python fallbacks take over), ``HOROVOD_TPU_WITH_NATIVE=1``
  makes a native build failure fatal instead of a warning — the
  ``HOROVOD_WITH[OUT]_*`` semantics of ``setup.py:477-592``; ``CXX``
  overrides the compiler like ``HOROVOD_MPICXX_SHOW`` overrides mpicxx.

The library also self-builds lazily at import time (``horovod_tpu/cc``),
so setup.py is the packaging path, not the only path.
"""

import os
import subprocess
import sys
import tempfile
import textwrap

from setuptools import Command, setup
from setuptools.command.build_py import build_py

_ROOT = os.path.dirname(os.path.abspath(__file__))
_CC_DIR = os.path.join(_ROOT, "horovod_tpu", "cc")


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in ("", "0", "false")


def _compiler() -> str:
    return os.environ.get("CXX", "g++")


def probe_cxx_flags(cxx: str) -> list:
    """Pick the best supported flag set by compiling a probe program,
    mirroring the reference's test-compile loop (``setup.py:84-115``)."""
    probe = textwrap.dedent("""
        #include <memory>
        #include <thread>
        int main() { auto p = std::make_unique<int>(1); return *p - 1; }
    """)
    candidates = [
        ["-std=c++17", "-O3", "-fPIC", "-pthread"],
        ["-std=c++14", "-O2", "-fPIC", "-pthread"],
        ["-std=c++11", "-O2", "-fPIC", "-pthread"],
    ]
    with tempfile.TemporaryDirectory() as tmp:
        src = os.path.join(tmp, "probe.cc")
        with open(src, "w", encoding="utf-8") as fh:
            fh.write(probe)
        for flags in candidates:
            out = os.path.join(tmp, "probe.out")
            result = subprocess.run(
                [cxx, *flags, src, "-o", out],
                capture_output=True, text=True)
            if result.returncode == 0:
                return flags
    raise RuntimeError(
        f"{cxx} cannot compile C++11 or newer; set CXX to a working "
        f"compiler or HOROVOD_TPU_WITHOUT_NATIVE=1 to skip the native core.")


def _native_sources():
    """The Makefile's SRCS line is the single source of truth — a second
    hardcoded list here once shipped a library missing a translation unit."""
    with open(os.path.join(_CC_DIR, "Makefile"), encoding="utf-8") as fh:
        for line in fh:
            if line.startswith("SRCS"):
                return line.split(":=", 1)[1].split()
    raise RuntimeError("cc/Makefile has no SRCS line")


def build_native_core(out_dir: str) -> str:
    """Compile the native controller core into ``out_dir`` and return the
    library path."""
    cxx = _compiler()
    flags = probe_cxx_flags(cxx)
    os.makedirs(out_dir, exist_ok=True)
    lib = os.path.join(out_dir, "libhtpu_core.so")
    sources = [os.path.join(_CC_DIR, s) for s in _native_sources()]
    cmd = [cxx, *flags, "-Wall", "-Wextra", "-shared", "-o", lib, *sources]
    result = subprocess.run(cmd, capture_output=True, text=True)
    if result.returncode != 0:
        raise RuntimeError(
            f"native core build failed:\n$ {' '.join(cmd)}\n{result.stderr}")
    # the loader rebuilds unless this digest matches the sources it finds
    with open(lib + ".sources.sha256", "w", encoding="utf-8") as fh:
        fh.write(_cc_binding()._sources_digest())
    return lib


def _cc_binding():
    """``horovod_tpu/cc/__init__.py`` loaded by path (stdlib-only at import;
    the package itself pulls in jax, which a build host need not have)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_htpu_cc_binding", os.path.join(_CC_DIR, "__init__.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class BuildNative(Command):
    """``python setup.py build_native`` — standalone native-core build."""

    description = "build the native controller core (libhtpu_core.so)"
    user_options = []

    def initialize_options(self):  # noqa: D102
        pass

    def finalize_options(self):  # noqa: D102
        pass

    def run(self):  # noqa: D102
        if _env_flag("HOROVOD_TPU_WITHOUT_NATIVE"):
            print("HOROVOD_TPU_WITHOUT_NATIVE=1: skipping native core")
            return
        try:
            lib = build_native_core(os.path.join(_CC_DIR, "build"))
            print(f"built {lib}")
        except Exception as exc:  # noqa: BLE001
            if _env_flag("HOROVOD_TPU_WITH_NATIVE"):
                raise
            print(f"WARNING: native core unavailable, pure-Python fallbacks "
                  f"will be used: {exc}", file=sys.stderr)


class BuildPyWithNative(build_py):
    """Package build hook: compile the native core and ship it inside the
    package (the role of the reference's per-framework extension builders,
    ``setup.py:595-849``)."""

    def run(self):  # noqa: D102
        super().run()
        if _env_flag("HOROVOD_TPU_WITHOUT_NATIVE"):
            return
        target = os.path.join(self.build_lib, "horovod_tpu", "cc", "build")
        try:
            build_native_core(target)
        except Exception as exc:  # noqa: BLE001
            if _env_flag("HOROVOD_TPU_WITH_NATIVE"):
                raise
            print(f"WARNING: native core unavailable, pure-Python fallbacks "
                  f"will be used: {exc}", file=sys.stderr)


if __name__ == "__main__":
    setup(
        cmdclass={"build_native": BuildNative, "build_py": BuildPyWithNative},
    )
