#!/usr/bin/env python
"""The flash-attention kernels alone: device time of ``flash_fwd``,
``flash_bwd_dq`` and ``flash_bwd_dkv`` (``flash_win_*`` under a window) for
one forward + backward call.

    chiprun -- python benchmarks/flash_kernel_bench.py \\
        --case 8,1024,12,64,noncausal --case 1,8192,8,128,causal \\
        --case gpt2m --case laguna_full --case laguna_window

Each ``--case`` is ``batch,seq,heads,head_dim,causal|noncausal`` with
optional ``,block=<n>`` (a ``block_q`` / ``block_k`` bound for
``hvd.flash_attention``; none by default), ``,dtype=float32``,
``,seq_k=<n>``, ``,q_offset=<n>``, ``,kv_heads=<n>`` (grouped heads) and
``,window=<n>``; or the name of a benchmark cell's call (``CELLS``: a
layer's attention at the cell's batch a chip), with further options after
it. ``,strip=<rows>`` measures other strips of the masked tiles than the
module's rule (``ops.pallas_attention._STRIP``; ``0`` runs them whole) —
how the rule was chosen, and how to check it again.
The times are read from a profiler trace of ``--iters`` calls, by the
kernels' names (docs/tracing.md), so the XLA work round them (layout
copies, delta, the statistics' broadcasts) is reported apart, as
``call_ms`` less the kernels. ``--tree`` measures another checkout's
``horovod_tpu`` with this script — how a kernel PR compares itself with
its parent in one chip call.

One process, on the device it measures: exits non-zero without a TPU
unless ``HOROVOD_BENCH_PLATFORM=cpu`` asks for a CPU run (the Pallas
interpreter: a check of the script, never a time). Prints one JSON line
per case, stamped with ``platform`` / ``device_kind``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
# one layer's attention as the benchmark's cells call it: gpt2m_1chip (and
# a chip of gpt2m_4chip), laguna_xs2_8k_1chip's full and sliding layers,
# smallthinker_16k_1chip's full and window layers (a group of 7)
CELLS = {
    "gpt2m": "4,1024,16,64,causal",
    "laguna_full": "2,8192,48,128,causal,kv_heads=8",
    "laguna_window": "2,8192,64,128,causal,kv_heads=8,window=512",
    "smallthinker_full": "1,16384,28,128,causal,kv_heads=4",
    "smallthinker_window": "1,16384,28,128,causal,kv_heads=4,window=4096",
}


def parse_case(text: str) -> dict:
    name, _, more = text.partition(",")
    if name in CELLS:
        text = CELLS[name] + ("," + more if more else "")
    batch, seq, heads, head_dim, causal, *options = text.split(",")
    if causal not in ("causal", "noncausal"):
        raise ValueError(f"case {text!r}: causal or noncausal, not {causal!r}")
    case = {"shape": (int(batch), int(seq), int(heads), int(head_dim)),
            "causal": causal == "causal", "block": None, "dtype": "bfloat16",
            "seq_k": int(seq), "q_offset": 0, "kv_heads": int(heads),
            "window": None, "strip": None}
    for option in options:
        key, _, value = option.partition("=")
        if key not in case or key in ("shape", "causal"):
            raise ValueError(f"case {text!r}: unknown option {key!r}")
        case[key] = value if key == "dtype" else int(value)
    return case


@contextlib.contextmanager
def strip_of(rows):
    """The module's rule replaced by strips of ``rows`` (a case's
    ``strip=``) for one measurement. The rule is read when a call is
    traced, so the traces made under another are forgotten on the way in
    and out."""
    if rows is None:
        yield
        return
    import jax

    from horovod_tpu.ops import pallas_attention

    rule = pallas_attention._STRIP
    pallas_attention._STRIP = rows
    jax.clear_caches()
    try:
        yield
    finally:
        pallas_attention._STRIP = rule
        jax.clear_caches()


def measure(case: dict, iters: int, trace_root: str) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import trace_reduce
    from horovod_tpu.ops.pallas_attention import flash_attention

    batch, seq, heads, head_dim = case["shape"]
    dtype = jnp.dtype(case["dtype"])
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, cot = (jax.random.normal(key, case["shape"], dtype)
              for key in keys[:2])
    k, v = (jax.random.normal(
        key, (batch, case["seq_k"], case["kv_heads"], head_dim), dtype)
        for key in keys[2:])

    # a checkout from before PR 25 bounds its blocks at 512 by default and
    # takes no ``None``; one from before PR 26 has no window
    options = {} if case["block"] is None else {
        "block_q": case["block"], "block_k": case["block"]}
    if case["window"] is not None:
        options["window"] = case["window"]

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=case["causal"],
                              q_offset=case["q_offset"], **options)
        return jnp.vdot(out.astype(jnp.float32), cot.astype(jnp.float32))

    call = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    for _ in range(3):
        jax.block_until_ready(call(q, k, v))
    trace_dir = tempfile.mkdtemp(dir=trace_root)
    with jax.profiler.trace(trace_dir):
        for _ in range(iters):
            result = call(q, k, v)
        jax.block_until_ready(result)

    line = {**case, "iters": iters}
    device = next((lines for name, lines in
                   trace_reduce.load(trace_dir).items()
                   if trace_reduce.DEVICE_PLANE.match(name)), None)
    if device is None:  # the CPU backend traces no device plane
        return line
    seconds = dict.fromkeys(KERNELS, 0.0)
    for event in device.get(trace_reduce.OPS_LINE, []):
        stem = re.sub(r"\.\d+$", "", trace_reduce.parse_op(event.name)[0])
        stem = stem.replace("flash_win_", "flash_")
        if stem in seconds:
            seconds[stem] += event.dur_ns * 1e-9
    for name, total in seconds.items():
        line[f"{name}_ms"] = 1e3 * total / iters
    line["kernels_ms"] = 1e3 * sum(seconds.values()) / iters
    modules = device.get(trace_reduce.MODULES_LINE, [])
    line["call_ms"] = 1e-6 * sum(e.dur_ns for e in modules) / iters
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    parser.add_argument("--case", action="append", required=True,
                        type=parse_case)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--tree", default=_ROOT,
                        help="the checkout whose horovod_tpu is measured")
    args = parser.parse_args(argv)
    sys.path[:0] = [os.path.abspath(args.tree), _ROOT]

    from bench import _bench_device, _device_stamp

    device = _bench_device()
    trace_root = os.path.join(_ROOT, ".chipbench_trace")
    os.makedirs(trace_root, exist_ok=True)
    for case in args.case:
        with strip_of(case["strip"]):
            line = measure(case, args.iters, trace_root)
        print(json.dumps({
            **line, "tree": os.path.relpath(args.tree, _ROOT),
            **_device_stamp(device, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
