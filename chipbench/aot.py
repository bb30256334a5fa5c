"""Compile a cell's step at its real shapes for a TPU that is described,
not attached — the rehearsal that picks per-chip batches before any chip
call (``on-chip-measurement`` guide, section 2.3).

    JAX_PLATFORMS=cpu python3 -m chipbench.aot --workload gpt2m_1chip

prints what the compiler says the program holds on each device. Nothing
runs, so this gives no time and no result; a compile that passes is not a
chip run. The tests call ``compile_cell`` with devices from a fixture.
"""

from __future__ import annotations

import contextlib
import functools
import os

TOPOLOGY = "v5e:2x2"


@contextlib.contextmanager
def mosaic_kernels():
    """The program picks the Pallas interpreter whenever the *attached*
    backend is the CPU; compiling for a described TPU has to take the
    Mosaic path, so the kernel's switch is set here, not in the program."""
    from horovod_tpu.ops import pallas_attention

    real = pallas_attention.flash_attention
    pallas_attention.flash_attention = functools.partial(real,
                                                         interpret=False)
    try:
        yield
    finally:
        pallas_attention.flash_attention = real


def compile_cell(cell, devices):
    """The cell's compiled step for ``devices`` (``cell.chips`` of them,
    described or real), from shapes alone."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    family, config, traffic = cell.family, cell.config, cell.traffic
    mesh = Mesh(np.asarray(devices[:cell.chips]), ("data",))
    replicated = NamedSharding(mesh, P())

    def placed(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    key = jax.ShapeDtypeStruct((2,), np.uint32)
    model_state = jax.eval_shape(
        functools.partial(family.init_model_state, config), key)
    batch = jax.eval_shape(
        functools.partial(family.make_pool, config, traffic), key)[0]
    opt = hvd.DistributedOptimizer(family.optimizer(config),
                                   axis_name="data")
    opt_state = jax.eval_shape(opt.init, model_state[0])
    state = family.assemble(placed(model_state, replicated),
                            placed(opt_state, replicated))
    batch = tuple(placed(x, NamedSharding(mesh, spec))
                  for x, spec in zip(batch, family.data_spec("data")))
    with mosaic_kernels():
        return family.make_step(family.build(config), opt, mesh).lower(
            *state, *batch).compile()


def device_bytes(compiled) -> dict:
    """What the compiled program holds on one device, from the compiler's
    own account: arguments and outputs (less what they share by donation),
    temporaries, and the code."""
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes
             + m.generated_code_size_in_bytes)
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "aliased": m.alias_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "code": m.generated_code_size_in_bytes, "total": total}


def main() -> None:
    import argparse

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    from chipbench import cell as cells

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--benchmark", default=None)
    args = parser.parse_args()
    cell = cells.Spec(args.benchmark).cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)
    compiled = compile_cell(cell, topo.devices)
    held = device_bytes(compiled)
    print(f"{cell.name} compiled for {TOPOLOGY} ({cell.chips} chip(s)): "
          + ", ".join(f"{k} {v / 1e9:.3f} GB" for k, v in held.items()))
    print(f"Mosaic custom calls: "
          f"{compiled.as_text().count('tpu_custom_call')}")


if __name__ == "__main__":
    main()
