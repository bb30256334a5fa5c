#!/usr/bin/env python
"""The delta rule's kernels alone: device time of ``kda_fwd`` and
``kda_bwd`` for one forward + backward call of ``hvd.ops.kda.kda``.

    chiprun -- python benchmarks/kda_kernel_bench.py --case kimi \\
        --tree .parent --tree .

Each ``--case`` is ``batch,seq,heads,head_dim`` with optional
``,dtype=float32``, ``,chunk=<n>``, ``,d_v=<n>`` (values of their own
width), ``,decay=head`` (one decay a head: the kernels are then ``gdn_fwd``
/ ``gdn_bwd``) and ``,beta=<largest>``, or ``kimi``: a KDA layer's call on
``kimi_linear_16k_1chip`` (one sequence of 16,384 tokens, 32 heads of
128), or ``olmo``: a delta-rule layer's on ``olmo_hybrid_8k_1chip`` (8,192
tokens, 15 heads with keys 96 and values 192 wide, a decay a head, beta up
to 2). The kernels are handed q, k, v, g as ``[B, T, H * d]``, heads side by
side, as ``models.kimi_linear.KDAMixer`` hands them (``ops.kda.kda_fed``):
the measured program is then the two kernels and nothing round them, and
its per-call times are the cell's. A ``--tree`` from before PR 36 knows only
the four-axis form of ``ops.kda.kda``, whose reshapes are copies on the
TPU, and is measured through it (``layout`` says which). The times are read
from a profiler trace of ``--iters`` calls, by the kernels' names
(docs/tracing.md); ``call_ms`` is the whole device program, so what XLA
does round the kernels — layout copies; in a checkout from before PR 31
the loops that prepared the kernels' operands — is ``call_ms`` less
``kernels_ms``. ``check_*`` are the largest errors of the values and
of each gradient against ``kda_recurrent`` in float32 over the first
``--check`` tokens, as shares of the largest magnitude: the Mosaic
lowering checked on the chip, which the interpreter's tests cannot. Each
``--tree`` measures that checkout's ``horovod_tpu`` (a ``git archive`` of
the parent beside this one) in a process of its own state: give the
option more than once to compare in one chip call.

One process, on the device it measures: exits non-zero without a TPU
unless ``HOROVOD_BENCH_PLATFORM=cpu`` asks for a CPU run (the Pallas
interpreter: a check of the script, never a time). Prints one JSON line
per case and tree, stamped with ``platform`` / ``device_kind``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = ("kda_fwd", "kda_bwd", "gdn_fwd", "gdn_bwd")
CELLS = {"kimi": "1,16384,32,128",
         "olmo": "1,8192,15,96,d_v=192,decay=head,beta=2"}


def parse_case(text: str) -> dict:
    name, _, more = text.partition(",")
    if name in CELLS:
        text = CELLS[name] + ("," + more if more else "")
    batch, seq, heads, head_dim, *options = text.split(",")
    case = {"shape": (int(batch), int(seq), int(heads), int(head_dim)),
            "dtype": "bfloat16", "chunk": 64, "d_v": int(head_dim),
            "decay": "channel", "beta": 1.0}
    for option in options:
        key, _, value = option.partition("=")
        if key not in ("dtype", "chunk", "d_v", "decay", "beta"):
            raise ValueError(f"case {text!r}: unknown option {key!r}")
        case[key] = value if key in ("dtype", "decay") else \
            float(value) if key == "beta" else int(value)
    if case["decay"] not in ("channel", "head"):
        raise ValueError(f"case {text!r}: decay is channel or head")
    return case


def operands(case, dtype):
    """Unit q and k, normal v (``d_v`` wide), a decay of about 0.8 a token —
    a channel or a head — and a write strength of ``beta`` times a sigmoid:
    what a delta-rule layer feeds its kernels at the start of training."""
    import jax
    import jax.numpy as jnp

    shape = case["shape"]
    values = (*shape[:3], case["d_v"])
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k = (jax.random.normal(key, shape) for key in keys[:2])
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v, cot = (jax.random.normal(key, values) for key in keys[2:4])
    g = -0.3 * jax.nn.softplus(jax.random.normal(
        keys[4], shape[:3] if case["decay"] == "head" else shape))
    beta = case["beta"] * jax.nn.sigmoid(
        jax.random.normal(keys[5], shape[:3]))
    return tuple(x.astype(dtype) for x in (q, k, v)), g, beta, cot


def takes_heads_side_by_side(ops_kda) -> bool:
    """Whether that checkout's ``kda_fed`` takes ``[B, T, H * d]`` (since
    PR 36) and not ``[B, T, H, d]``: asked of it by shape, nothing runs."""
    import jax

    flat = jax.ShapeDtypeStruct((1, 16, 2 * 16), "float32")
    try:
        jax.eval_shape(lambda *a: ops_kda.kda_fed(ops_kda._as_given, *a),
                       flat, flat, flat, flat,
                       jax.ShapeDtypeStruct((1, 16, 2), "float32"))
    except (ValueError, TypeError):
        return False
    return True


def measure(case: dict, iters: int, check: int, trace_root: str) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import trace_reduce
    from horovod_tpu.ops import kda as ops_kda

    kda, kda_recurrent = ops_kda.kda, ops_kda.kda_recurrent
    (q, k, v), g, beta, cot = operands(case, jnp.dtype(case["dtype"]))

    def loss(fn, cot, *args):
        return jnp.vdot(fn(*args)[0].astype(jnp.float32), cot)

    chunked = lambda *a: kda(*a, chunk=case["chunk"])  # noqa: E731
    args = (q, k, v, g, beta)
    flat = takes_heads_side_by_side(ops_kda)
    if flat:
        as_timed = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731
        rule = lambda *a: ops_kda.kda_fed(  # noqa: E731
            ops_kda._as_given, *a, chunk=case["chunk"])
    else:
        as_timed, rule = (lambda x: x), chunked
    call = jax.jit(jax.value_and_grad(
        lambda *a: loss(rule, as_timed(cot), *a), argnums=(0, 1, 2, 3, 4)))
    timed = (*map(as_timed, args[:3]),
             g if case["decay"] == "head" else as_timed(g), beta)
    for _ in range(3):
        jax.block_until_ready(call(*timed))
    trace_dir = tempfile.mkdtemp(dir=trace_root)
    with jax.profiler.trace(trace_dir):
        for _ in range(iters):
            result = call(*timed)
        jax.block_until_ready(result)

    line = {**case, "iters": iters,
            "layout": "heads_side_by_side" if flat else "by_head"}
    if check:
        head = tuple(x[:, :check] for x in args)
        got, want = (jax.jit(jax.value_and_grad(
            lambda *a, fn=fn: loss(fn, cot[:, :check], *a),
            argnums=(0, 1, 2, 3, 4)))(*head)
            for fn in (chunked, kda_recurrent))
        for name, a, b in zip(("q", "k", "v", "g", "beta"), got[1], want[1]):
            a, b = (x.astype(jnp.float32) for x in (a, b))
            line[f"check_d{name}"] = float(
                jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        line["check_loss"] = float(abs(got[0] - want[0]) / abs(want[0]))
    device = next((lines for name, lines in
                   trace_reduce.load(trace_dir).items()
                   if trace_reduce.DEVICE_PLANE.match(name)), None)
    if device is None:  # the CPU backend traces no device plane
        return line
    seconds = dict.fromkeys(KERNELS, 0.0)
    for event in device.get(trace_reduce.OPS_LINE, []):
        stem = re.sub(r"\.\d+$", "", trace_reduce.parse_op(event.name)[0])
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
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--check", type=int, default=512,
                        help="tokens compared with kda_recurrent; 0: none")
    parser.add_argument("--tree", action="append",
                        help="a checkout whose horovod_tpu is measured")
    args = parser.parse_args(argv)
    sys.path.insert(0, _ROOT)

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
            line = measure(case, args.iters, args.check, trace_root)
            print(json.dumps({
                **line, "tree": os.path.relpath(tree, _ROOT),
                **_device_stamp(device, 1)}), flush=True)
        sys.path.pop(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
