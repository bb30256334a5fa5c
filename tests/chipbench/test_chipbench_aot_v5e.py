"""Every cell's step compiles for the v5e at the cell's real shapes and
fits one chip's 16 GB — the rehearsal that picks per-chip batches before
any chip call. The chip is described, not attached: nothing runs, so this
gives no time and no result.

All such compiles live in this one file (only one process at a time may
hold the TPU compiler), the topology is described inside a fixture, never
at import. The language model's step takes about a minute to compile here,
so those two are marked ``slow``; ResNet-50's (about 40 s) runs in tier 1.
"""

import pytest

from chipbench import aot
from chipbench import cell as cells


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name=aot.TOPOLOGY)
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler away
        pytest.skip(f"no {aot.TOPOLOGY} topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip; keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", [
    "resnet50_1chip",
    pytest.param("gpt2m_1chip", marks=pytest.mark.slow),
    pytest.param("gpt2m_4chip", marks=pytest.mark.slow)])
def test_step_compiles_for_v5e_and_fits_the_chip(topo, no_compile_cache,
                                                 name):
    from chipbench.run import allreduce_group_sizes

    cell = cells.Spec().cell(name)
    compiled = aot.compile_cell(cell, topo.devices)
    held = aot.device_bytes(compiled)
    hbm = cells.peaks_of("TPU v5 lite")["hbm_bytes"]
    # room for the batch pool and the seeded copy that ``correct`` makes
    assert held["total"] < 0.85 * hbm, held
    # a cell that leaves three quarters of the chip empty is too small
    assert held["total"] > 0.25 * hbm, held
    hlo = compiled.as_text()
    work = cell.family.kernel_work(cell.config, cell.traffic,
                                   cell.per_chip_batch)
    calls = hlo.count('custom_call_target="tpu_custom_call"')
    assert calls == sum(k["calls"] for k in work.values())
    sizes = allreduce_group_sizes(hlo)
    if cell.chips > 1:
        assert sizes and all(s in (0, cell.chips) for s in sizes), sizes
    else:
        assert all(s in (0, 1) for s in sizes), sizes
