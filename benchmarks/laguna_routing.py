"""Where the tokens of an expert cell of an LM family (``laguna``,
``smallthinker``) go, on the chip and at the cell's size.

    python3 benchmarks/laguna_routing.py --workload laguna_xs2_8k_1chip \
        --seed 7 --steps 80 --out chiprun_out/routing.json

Two readings that the benchmark's runs do not print:

* how many tokens take another top-k set of experts in the configuration's
  compute type than in float32, layer by layer, on the seeded weights (the
  router's own products are float32 in both; its input is not) — the
  discontinuity that ``correct``'s limits have to live with;
* the share of assignments that go to the experts held here
  (``horovod_moe_held_assignment_share``), the busiest expert's load
  (``horovod_moe_expert_load_max_over_mean``) and the slices the held
  experts' loop ran, how full they were and whether their sums by token
  went through the kernel (``horovod_moe_slices_run``,
  ``horovod_moe_slot_fill``, ``horovod_moe_sum_kernel_share``), and the
  share of exact zeros behind the gate
  (``horovod_moe_gate_zero_share``), as the
  cell's own trainer steps through its pool, with each step's time
  beside them: a chip's share of the experts
  is the only part of the routed sum the loss sees, so training moves the
  router, and a step costs the rows routed here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", default="laguna_xs2_8k_1chip")
    parser.add_argument("--benchmark", default=None)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--steps", type=int, default=80)
    parser.add_argument("--every", type=int, default=8)
    parser.add_argument("--out", default=None)
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)

    from chipbench import cell as cells
    from chipbench import run

    cell = cells.Spec(args.benchmark).cell(args.workload)
    devices, _ = run.take_devices(cell, args.rehearse_cpu)

    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    family, config, traffic = cell.family, cell.config, cell.traffic
    keys = cells.seed_keys(args.seed, 2)
    model = family.build(config)
    k = model.experts_per_token
    report = {"cell": cell.name, "seed": args.seed}

    def router_logits(m):
        def fn(params, tokens):
            _, state = m.apply(
                {"params": params}, tokens, capture_intermediates=(
                    lambda mdl, _: mdl.name == "router"))
            return jax.tree_util.tree_leaves(state["intermediates"])
        return jax.jit(fn)

    (params,) = jax.jit(lambda key: family.init_model_state(config, key))(
        keys[0])
    tokens = jax.jit(lambda key: family.make_pool(config, traffic, key))(
        keys[1])[0][0][:1]
    chosen = [[jnp.sort(jax.lax.top_k(x, k)[1], axis=-1) for x in
               router_logits(model.clone(dtype=dtype, remat=False))(
                   params, tokens)]
              for dtype in (jnp.float32, model.dtype)]
    report["tokens_with_another_top_k"] = [
        float(jnp.mean(jnp.any(a != b, axis=-1))) for a, b in zip(*chosen)]
    print(json.dumps({"tokens_with_another_top_k":
                      report["tokens_with_another_top_k"]}), flush=True)
    del params, chosen

    stats = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["moe_stats"])[1]["moe_stats"])
    hvd.init()
    try:
        mesh = hvd.parallel.data_parallel_mesh(devices)
        trainer = run.Trainer(cell, mesh, args.seed)
        rows = []
        for step in range(args.steps):
            row = {"step": step}
            if step % args.every == 0:
                batch = trainer.pool[step % len(trainer.pool)][0]
                routed = hvd.obs.moe.publish(stats(trainer.state[0], batch))
                for name in ("held_share", "load_max_over_mean",
                             "slices_run", "slot_fill", "sum_kernel_share",
                             "gate_zero_share"):
                    row[name] = [v[name] for _, v in sorted(routed.items())]
            t = time.perf_counter()
            row["loss"] = float(trainer.step())
            row["step_ms"] = 1e3 * (time.perf_counter() - t)
            rows.append(row)
            print(json.dumps(row), flush=True)
        report["steps"] = rows
    finally:
        hvd.shutdown()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
