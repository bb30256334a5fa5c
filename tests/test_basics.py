"""Basics: init/rank/size lifecycle (reference: ``test/test_torch.py:59-71``
rank/size ground truth; ``horovod/common/__init__.py`` error semantics)."""

import pytest

import horovod_tpu as hvd


def test_uninitialized_raises():
    hvd.shutdown()
    with pytest.raises(ValueError):
        hvd.rank()
    with pytest.raises(ValueError):
        hvd.size()


def test_init_rank_size(hvd):
    assert hvd.rank() == 0
    assert hvd.size() == 1
    assert hvd.local_rank() == 0
    assert hvd.local_size() == 1
    assert hvd.cross_rank() == 0
    assert hvd.cross_size() == 1
    assert hvd.local_device_count() == 8  # virtual CPU mesh from conftest
    assert hvd.num_devices() == 8


def test_init_idempotent(hvd):
    hvd.init()
    hvd.init()
    assert hvd.is_initialized()
    assert hvd.rank() == 0


def test_shutdown_and_reinit(hvd):
    hvd.shutdown()
    assert not hvd.is_initialized()
    with pytest.raises(ValueError):
        hvd.rank()
    hvd.init()
    assert hvd.rank() == 0


def test_mpi_threads_supported(hvd):
    # No MPI in this build, by design (SURVEY §2.10).
    assert hvd.mpi_threads_supported() is False


def test_init_subset_validation():
    """Subset worlds (reference ``common/__init__.py:58-84``): rank lists
    are validated against the launcher world; an mpi4py communicator object
    is rejected (no MPI here); a rank list may also be spelled ``comm=``
    as the reference allows. Multi-member subsets are exercised in
    tests/test_multiprocess.py::test_mp_subset_world."""
    hvd.shutdown()
    with pytest.raises(ValueError):
        hvd.init(ranks=[0, 1])  # world of 1: rank 1 does not exist
    with pytest.raises(ValueError):
        hvd.init(ranks=[0, 0])  # duplicates
    with pytest.raises(ValueError):
        hvd.init(ranks=[])  # empty communicator is a typo, not full world
    with pytest.raises(ValueError):
        hvd.init(comm=object())  # an actual MPI communicator: unsupported

    # the self-subset of a single-process world is legal, via either
    # spelling
    hvd.init(ranks=[0])
    assert hvd.rank() == 0 and hvd.size() == 1
    hvd.shutdown()
    hvd.init(comm=[0])
    assert hvd.rank() == 0 and hvd.size() == 1
    hvd.shutdown()


def test_pin_cpu_platform_is_two_settings():
    """conftest pinned this process: JAX_PLATFORMS=cpu plus the virtual
    device count — both in the environment, so children inherit them."""
    import os

    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in \
        os.environ["XLA_FLAGS"]


def test_launcher_world_does_not_invent_a_device_count(monkeypatch):
    """A launcher-described rank whose JAX cannot start is an error, not
    a world with one device per rank."""
    import jax

    from horovod_tpu.core import topology

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setenv("HOROVOD_RANK", "0")
    monkeypatch.setenv("HOROVOD_SIZE", "2")
    monkeypatch.setattr(jax, "local_device_count", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        topology.discover()
    # the host plane never asks JAX at all
    monkeypatch.setenv("HOROVOD_DATA_PLANE", "host")
    assert topology.discover().local_device_count == 1
