#!/usr/bin/env python
"""One expert layer alone: device time of a forward + backward call of
``models.experts.ExpertLayer`` under ``jax.checkpoint``, at a cell's shape.

    chiprun -- python benchmarks/expert_layer_bench.py --case laguna \\
        --case sdar --case kimi --tree .pr_trees/parent --tree .

Each ``--case`` is a cell's expert layer — ``laguna`` (16,384 rows of 2048,
32 of 256 experts 512 wide held, a shared expert), ``sdar`` (16 of 128
experts 768 wide, a softmax router, no shared expert), ``kimi`` (2304 wide,
8 of 256 experts of 1024) — with an optional ``,slice=<slots>``: the slots
the loop of ``held_expert_sum`` takes at a time in place of the layer's own
rule (what one slice costs, and what an iteration's fixed cost is; a tree
from before that loop ignores it), and an optional ``,keep=0``: the
checkpoint's default policy in place of the one that saves the layer's
``KEPT_NAMES``, so that the recomputed half selects and sorts again (what
keeping the routing is worth; a tree from before the names keeps nothing
either way). The
router is seeded and the rows are normal, so about ``held / num_experts``
of the assignments come here, as on the cells' seeded weights. As a block
runs it: the layer's output recomputed in the backward pass under the
policy the decoders give an expert half, gradients for the rows and every
parameter.

``layer_ms`` is the whole device program a call, ``kernels_ms`` the three
``expert_matmul_*`` calls in it, both read from a profiler trace of
``--iters`` calls, ``ops_ms`` its twelve longest operations as a cell's
``breakdown.device_ops`` names them (a loop is one of them, and spans its
body's), ``sums_ms`` the operations outside the loops' containers whose name
holds ``moe_rows_add``, the token-sized float32 type (the scatter-adds the
call replaced) or the assignment-sized one (the router weights' scalar
scatter-add), ``layout_ms`` the layer's bookkeeping: every operation the
compiled text puts under ``hvd.moe`` but the two loops and their bodies
(the kernels among them) and the shared expert — the router's product and
scoring, the selection, the slot layout, their transposes, the sum with
the shared expert; ``layout_reruns`` is ``obs.moe.record_layout_program``'s
count on that text (null on a tree without it); ``slices_run``,
``slot_fill`` and ``sum_kernel_share`` are
what the layer sows (null on a tree that does not). Each ``--tree``
measures that checkout's ``horovod_tpu`` (a ``git archive`` of the parent
beside this one): give the option more than once to compare in one chip
call.

``--sum-only`` times, in place of the layer, what a slice's end costs: its
rows added by token into the carried float32 sum, once as the loop's
forward pass does it (bfloat16 rows times a float32 weight a row) and once
as its backward pass does (float32 rows), by ``ops.grouped_matmul.
moe_rows_add`` on the sum carried as ``[N, d / 128, 128]`` (``kernel_ms`` a
call, ``kernel_us_a_row``; null on a tree without the call) and by the
scatter-add ``sum.at[token].add(rows * scale, mode="drop")`` it replaced
(``scatter_ms``, ``scatter_us_a_row``: its whole device program, the
product by the weights included; the kernel's line is the call's own
operation), from a trace of ``--iters`` calls that hand the sum on, after
the largest difference between the two results and the elements that
differ (0 and 0 on the v5e: the kernel adds in the scatter-add's order, and
the chip rounds the product before the sum as XLA's own fusion does). A
slice's tokens are drawn as the layer lays them out: distinct within a
tile, anew for every tile, each tile's last slots empty.

One process, on the device it measures: exits non-zero without a TPU
unless ``--rehearse-cpu`` asks for a CPU run at 512 rows (the Pallas
interpreter: a check of the script, never a time). Prints one JSON line
per case and tree, stamped with ``platform`` / ``device_kind``.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import os
import re
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = ("expert_matmul_fwd", "expert_matmul_bwd_dx", "expert_matmul_bwd_dw")
CELLS = {
    "laguna": dict(rows=16384, d=2048, num_experts=256, experts_per_token=8,
                   held=32, width=512, shared_width=512, scaling=2.5,
                   scoring="sigmoid"),
    "sdar": dict(rows=16384, d=2048, num_experts=128, experts_per_token=8,
                 held=16, width=768, shared_width=0, scaling=1.0,
                 scoring="softmax"),
    "kimi": dict(rows=16384, d=2304, num_experts=256, experts_per_token=8,
                 held=8, width=1024, shared_width=1024, scaling=2.446,
                 scoring="sigmoid"),
    "smallthinker": dict(rows=16384, d=2560, num_experts=64,
                         experts_per_token=6, held=16, width=768,
                         shared_width=0, scaling=1.0, scoring="softmax",
                         gate="relu"),
}


def parse_case(text: str) -> dict:
    name, *options = text.split(",")
    if name not in CELLS:
        raise ValueError(f"case {text!r}: one of {sorted(CELLS)}")
    case = {"case": name, **CELLS[name], "slice": None, "keep": 1}
    for option in options:
        key, _, value = option.partition("=")
        if key not in ("slice", "keep"):
            raise ValueError(f"case {text!r}: unknown option {key!r}")
        case[key] = int(value)
    return case


def slices_of(size: int):
    """``models.experts.slice_slots`` answering ``size`` whatever the shapes
    (in tiles of the kernel's, or of 8 slots below one)."""
    def rule(capacity, held, num_experts):
        from horovod_tpu.ops.grouped_matmul import ROW_TILE

        tile = ROW_TILE if size >= ROW_TILE else 8
        return max(size // tile, 1) * tile, tile
    return rule


def device_lines(trace_dir: str):
    """The lines of a trace's device plane; ``None`` from the CPU backend,
    which traces none."""
    from chipbench import trace_reduce

    return next((lines for name, lines in
                 trace_reduce.load(trace_dir).items()
                 if trace_reduce.DEVICE_PLANE.match(name)), None)


def programs_ms(device, iters: int) -> float:
    """ms a call of the device programs in a trace of ``iters`` calls."""
    from chipbench import trace_reduce

    return 1e-6 * sum(e.dur_ns for e in device.get(
        trace_reduce.MODULES_LINE, [])) / iters


def expert_module():
    """The tree's ``models.experts``: ``models.laguna`` in a tree from
    before the expert layer had a module of its own."""
    try:
        from horovod_tpu.models import experts
    except ImportError:
        from horovod_tpu.models import laguna as experts
    return experts


def measure(case: dict, iters: int, trace_root: str) -> dict:
    """One case's line, under the case's slice where it names one."""
    experts = expert_module()
    own_rule = getattr(experts, "slice_slots", None)
    if case["slice"] and own_rule:
        experts.slice_slots = slices_of(case["slice"])
    try:
        return _measure(experts, case, iters, trace_root)
    finally:
        if own_rule:
            experts.slice_slots = own_rule


def _measure(experts, case: dict, iters: int, trace_root: str) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import scopes, trace_reduce
    from horovod_tpu import obs

    layer = experts.ExpertLayer(
        num_experts=case["num_experts"],
        experts_per_token=case["experts_per_token"],
        experts_held=(0, case["held"]), width=case["width"],
        shared_width=case["shared_width"], scaling=case["scaling"],
        scoring=case["scoring"],
        # a tree from before the gate was a field knows silu alone
        **({"gate": case["gate"]} if "gate" in case else {}))
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x, cot = (jax.random.normal(key, (1, case["rows"], case["d"]),
                                jnp.bfloat16) for key in keys[:2])
    params = jax.jit(layer.init)(keys[2], x)["params"]

    # what a decoder's recomputed expert half keeps of the layer
    kept = getattr(experts, "KEPT_NAMES", ()) if case["keep"] else ()

    @jax.jit
    def call(params, x):
        run = jax.checkpoint(
            lambda p, x: layer.apply({"params": p}, x),
            policy=jax.checkpoint_policies.save_only_these_names(*kept)
            if kept else None)
        return jax.value_and_grad(lambda p, x: jnp.vdot(
            run(p, x).astype(jnp.float32), cot), argnums=(0, 1))(params, x)

    hlo = call.lower(params, x).compile().as_text()
    for _ in range(3):
        jax.block_until_ready(call(params, x))
    trace_dir = tempfile.mkdtemp(dir=trace_root)
    with jax.profiler.trace(trace_dir):
        for _ in range(iters):
            result = call(params, x)
        jax.block_until_ready(result)

    sown = obs.moe.publish({"layer": jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["moe_stats"])[1]["moe_stats"])(params, x)})
    record = getattr(obs.moe, "record_layout_program", None)
    line = {**case, "iters": iters, "kept": list(kept),
            "layout_reruns": record and record(case["case"], hlo)[3],
            **{name: sown["layer"].get(name) for name in (
                "held_share", "slices_run", "slot_fill",
                "sum_kernel_share", "gate_zero_share")}}
    device = device_lines(trace_dir)
    if device is None:
        return line
    seconds, groups = collections.Counter(), collections.Counter()
    known, layout = scopes.instructions(hlo), 0.0
    for event in device.get(trace_reduce.OPS_LINE, []):
        name = trace_reduce.parse_op(event.name)[0]
        seconds[re.sub(r"\.\d+$", "", name)] += event.dur_ns * 1e-9
        groups[trace_reduce.group_of(event.name)] += event.dur_ns * 1e-9
        scope = known.get(name, (None, ""))[1]
        if "hvd.moe" in scope and "hvd.moe.experts/while" not in scope \
                and "/shared/" not in scope:
            layout += event.dur_ns * 1e-9
    line["layout_ms"] = 1e3 * layout / iters
    for name in KERNELS:
        line[f"{name}_ms"] = 1e3 * seconds[name] / iters
    line["kernels_ms"] = 1e3 * sum(seconds[name] for name in KERNELS) / iters
    # the longest operations as a cell's breakdown names them (name, opcode
    # and output type), loops (which span their bodies) too
    line["ops_ms"] = [[name, round(1e3 * total / iters, 3)]
                      for name, total in groups.most_common(12)]
    # the sums by token outside the loops' own rows: the kernel, the
    # scatter-add it replaced, and the router weights' scalar scatter-add
    line["sums_ms"] = {
        key: round(1e3 * sum(total for name, total in groups.items() if
                             key in name and not name.startswith("while"))
                   / iters, 3)
        for key in ("moe_rows_add", f"f32[{case['rows']},{case['d']}]",
                    f"f32[{case['rows'] * case['experts_per_token']}]")}
    line["layer_ms"] = programs_ms(device, iters)
    return line


def measure_sum(case: dict, iters: int, trace_root: str) -> dict:
    """One case's ``--sum-only`` line: a slice's sum by token alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import trace_reduce
    from horovod_tpu.ops import grouped_matmul

    n, d = case["rows"], case["d"]
    size, tile = slices_of(case["slice"])(0, 0, 0) if case["slice"] else \
        expert_module().slice_slots(
            n * case["experts_per_token"], case["held"], case["num_experts"])
    rng = np.random.default_rng(0)
    token = np.stack([rng.permutation(n)[:tile] for _ in range(size // tile)])
    token[:, tile - tile // 16:] = n
    token = jnp.asarray(token.reshape(-1), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    total = jax.random.normal(keys[0], (n, d), jnp.float32)
    scale = jax.random.uniform(keys[1], (size, 1), jnp.float32, 0.1, 1.0)
    kernel = getattr(grouped_matmul, "moe_rows_add", None)

    def scatter(total, rows, scale):
        rows = rows.astype(jnp.float32)
        return total.at[token].add(rows if scale is None else rows * scale,
                                   mode="drop")

    def device_ms(call, total, *operands, op=None):
        """ms a call of the device programs of ``iters`` calls, or of their
        operations named ``op`` alone (a program handed ``[N, 18, 128]``
        from outside a loop also copies it to the kernel's layout and
        back, which no loop does)."""
        call = jax.jit(call, donate_argnums=0)
        for _ in range(3):
            total = call(total, *operands)
        jax.block_until_ready(total)
        trace_dir = tempfile.mkdtemp(dir=trace_root)
        with jax.profiler.trace(trace_dir):
            for _ in range(iters):
                total = call(total, *operands)
            jax.block_until_ready(total)
        device = device_lines(trace_dir)
        if device is None:
            return None
        if op is None:
            return programs_ms(device, iters)
        return 1e-6 * sum(
            e.dur_ns for e in device.get(trace_reduce.OPS_LINE, [])
            if trace_reduce.parse_op(e.name)[0].startswith(op)) / iters

    line = {**case, "iters": iters, "slice": size, "tile": tile}
    for form, dtype, scale in (("fwd", jnp.bfloat16, scale),
                               ("bwd", jnp.float32, None)):
        rows = jax.random.normal(keys[2], (size, d), dtype)
        want = jax.jit(scatter)(total, rows, scale)
        ms = {"scatter": device_ms(scatter, total + 0, rows, scale)}
        if kernel is not None:
            add = lambda total, rows, scale: kernel(  # noqa: E731
                total, rows, token, scale, jnp.int32(size // tile),
                row_tile=tile)
            cut = (n, d // 128, 128)
            apart = jnp.abs(jax.jit(add)(total.reshape(cut), rows, scale)
                            .reshape(n, d) - want)
            line[f"{form}_max_abs_diff"] = float(jnp.max(apart))
            line[f"{form}_elements_apart"] = int(jnp.sum(apart != 0))
            ms["kernel"] = device_ms(add, total.reshape(cut) + 0, rows, scale,
                                     op="moe_rows_add")
        for name, value in ms.items():
            line[f"{form}_{name}_ms"] = value
            line[f"{form}_{name}_us_a_row"] = \
                None if value is None else 1e3 * value / size
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    parser.add_argument("--case", action="append", required=True,
                        type=parse_case)
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--tree", action="append",
                        help="a checkout whose horovod_tpu is measured")
    parser.add_argument("--sum-only", action="store_true",
                        help="a slice's sum by token alone, kernel and "
                             "scatter-add")
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)
    sys.path.insert(0, _ROOT)
    if args.rehearse_cpu:
        os.environ["HOROVOD_BENCH_PLATFORM"] = "cpu"
        args.case = [{**case, "rows": min(case["rows"], 512)}
                     for case in args.case]

    from bench import _bench_device, _device_stamp

    device = _bench_device()
    trace_root = os.path.join(_ROOT, ".chipbench_trace")
    os.makedirs(trace_root, exist_ok=True)
    for tree in args.tree or [_ROOT]:
        # this tree's package in place of the last one's
        for name in [m for m in sys.modules
                     if m.split(".")[0] == "horovod_tpu"]:
            del sys.modules[name]
        sys.path.insert(0, os.path.abspath(tree))
        importlib.invalidate_caches()
        for case in args.case:
            line = (measure_sum if args.sum_only else measure)(
                case, args.iters, trace_root)
            print(json.dumps({
                **line, "tree": os.path.relpath(tree, _ROOT),
                **_device_stamp(device, 1)}), flush=True)
        sys.path.pop(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
