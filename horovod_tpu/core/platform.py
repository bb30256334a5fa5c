"""Where JAX runs and where it keeps compiled programs.

``pin_cpu_platform`` is the "no cluster needed" fixture: the reference's
test fixture is single-process MPI (a self-initialized world of size 1,
SURVEY §4); ours is N virtual XLA CPU devices in one process.
``setup_compile_cache`` is the one place the persistent compilation cache
is configured for every entry point that compiles for the chip.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pin_cpu_platform(n_devices: int = 8) -> None:
    """Pin JAX to ``n_devices`` virtual CPU devices, verifying the result.

    Sets ``JAX_PLATFORMS=cpu`` and
    ``--xla_force_host_platform_device_count`` for this process and its
    children. Must be called before any JAX backend query
    (``jax.devices()``, ``jax.process_index()``, array creation, ...); safe
    to call after ``import jax`` itself. If a backend already spun up, the
    settings are a silent no-op in JAX — so this function queries the
    devices it just pinned and raises rather than letting the caller
    proceed on the wrong platform with the wrong device count.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        flags = " ".join(
            flag if f.startswith("--xla_force_host_platform_device_count")
            else f for f in flags.split())
    else:
        flags = f"{flags} {flag}".strip()
    os.environ["XLA_FLAGS"] = flags

    import jax

    # the env var is only read when jax is first imported
    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) < n_devices:
        raise RuntimeError(
            f"pin_cpu_platform({n_devices}) failed: JAX reports "
            f"{len(devices)} {devices[0].platform!r} device(s). A backend "
            f"was already initialized before the pin ran — call "
            f"pin_cpu_platform before any jax.devices()/array operation.")


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the caller chose the place
    and JAX reads it itself: the place is left alone. Otherwise the
    cache is ``<checkout>/.jax_bench_cache`` — one fixed path, so that
    every entry point run from this checkout finds what an earlier one
    compiled.

    Either way the cache's key takes in the programs' metadata. JAX leaves
    it out by default, and a fetched executable then carries the
    ``op_name``s of whichever program filled the entry: the phase scopes
    (docs/tracing.md, "Scopes in a compiled step") of a step that another
    commit compiled, or none. The metadata holds source locations, so an
    entry is shared only by runs that build the program through the same
    call stack: the same entry point of the same checkout."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(_REPO_ROOT, ".jax_bench_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
