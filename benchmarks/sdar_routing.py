"""Where the rows of a block-diffusion cell go, on the chip and at the
cell's size.

    python3 benchmarks/sdar_routing.py --workload sdar_moe_8k_1chip \
        --seeds 7,8 --out chiprun_out/sdar_routing.json

For every seed and every batch of its pool, on the seeded weights, what the
benchmark's runs do not print: the gauges of the noise (``horovod_bd_masked_
share``, ``horovod_bd_mean_weight``: ``obs.bd``), the routing gauges layer
by layer (``horovod_moe_held_assignment_share``, ``horovod_moe_expert_load_
max_over_mean``: ``obs.moe``) and of the loop that multiplies the held
experts' rows (``models.experts.held_expert_sum``) the slices it ran and
how full they were (``horovod_moe_slices_run``, ``horovod_moe_slot_fill``)
and the share of its slots summed by token in ``moe_rows_add``
(``horovod_moe_sum_kernel_share``, 1.0 on the cell):
a batch that routes more rows here runs more slices, and its step takes as
much longer than its neighbours'.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", default="sdar_moe_8k_1chip")
    parser.add_argument("--benchmark", default=None)
    parser.add_argument("--seeds", default="7")
    parser.add_argument("--out", default=None)
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)

    from chipbench import cell as cells
    from chipbench import run

    cell = cells.Spec(args.benchmark).cell(args.workload)
    run.take_devices(cell, args.rehearse_cpu)

    import jax

    from horovod_tpu import obs

    family, config, traffic = cell.family, cell.config, cell.traffic
    model = family.build(config)
    stats = jax.jit(lambda p, clean, noisy, weights: model.apply(
        {"params": p}, clean, noisy, weights=weights,
        mutable=["moe_stats", "bd_stats"])[1])
    rows = []
    for seed in (int(s) for s in args.seeds.split(",") if s):
        keys = cells.seed_keys(seed, 2)
        (params,) = jax.jit(
            lambda key: family.init_model_state(config, key))(keys[0])
        pool = jax.jit(
            lambda key: family.make_pool(config, traffic, key))(keys[1])
        for i, batch in enumerate(pool):
            sown = stats(params, *batch)
            row = {"seed": seed, "batch": i, **obs.bd.publish(sown["bd_stats"])}
            routed = obs.moe.publish(sown["moe_stats"])
            for name in ("held_share", "load_max_over_mean", "slices_run",
                         "slot_fill", "sum_kernel_share"):
                row[name] = [v[name] for _, v in sorted(routed.items())]
            rows.append(row)
            print(json.dumps(row), flush=True)
        del params, pool
    print(f"slices a layer ran: {min(min(r['slices_run']) for r in rows)} to "
          f"{max(max(r['slices_run']) for r in rows)}; the emptiest were "
          f"{min(min(r['slot_fill']) for r in rows):.3f} full")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump({"cell": cell.name, "rows": rows}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
