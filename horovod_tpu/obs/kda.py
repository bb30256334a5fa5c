"""Gauges of the delta-rule layers' recurrence (docs/kimi-linear.md).

``models.kimi_linear.KDAMixer`` sows, into the flax collection
``kda_stats``, the mean of its per-channel decay over the batch
(``mean_decay``) and the largest magnitude in its state at the sequence's
end (``state_max``): the two numbers that say whether the recurrence
forgets or blows up. A training step does not carry the collection; a
caller who wants the numbers applies the model with
``mutable=["kda_stats"]`` and hands the collection to :func:`publish`.

And four gauges of a *compiled* step's text, set by
:func:`record_scan_program` (whoever holds the compiled step calls it, as
with ``obs.compiles.record_exchange_collectives``): whether the recurrence
engaged its kernels. ``ops.kda`` forms a chunk's operands inside
``kda_fwd`` and ``kda_bwd``; a lowering that fell back to XLA loops under
``hvd.kda.scan`` would show here as loops, and as calls that are missing.
And whether the layout held: the kernels read q, k, v, g as ``[B, T, H *
d]`` and the layer keeps them so from its projections on; a tensor that is
taken to ``[B, T, H, d]`` on the way is copied whole on the TPU, and shows
here as a relayout. And whether a recomputed block kept its mixer kernel's
outputs (``models.kimi_linear``): a forward kernel the backward pass runs
again shows here as a call beyond the one a layer needs.
"""

from __future__ import annotations

import collections
import math
import re

from .registry import registry as _metrics

_DECAY = _metrics().gauge(
    "horovod_kda_mean_decay",
    "Mean per-channel decay alpha of a delta-rule layer over the newest "
    "batch (1.0: nothing is forgotten; near 0: the state is wiped a token)",
    labels=("layer",))
_STATE = _metrics().gauge(
    "horovod_kda_state_abs_max",
    "Largest magnitude in a delta-rule layer's state at the end of the "
    "newest batch's sequences",
    labels=("layer",))
_SCAN_LOOPS = _metrics().gauge(
    "horovod_kda_scan_loops",
    "while instructions under the scope hvd.kda.scan in a compiled step's "
    "text (0 where the delta rule's kernels form their own operands)",
    labels=("program",))
_KERNEL_CALLS = _metrics().gauge(
    "horovod_kda_kernel_calls",
    "Mosaic custom calls of one of the delta rule's kernels (kda_fwd, "
    "kda_bwd) in a compiled step's text",
    labels=("program", "kernel"))

_RELAYOUTS = _metrics().gauge(
    "horovod_kda_relayouts",
    "copy, reshape and transpose instructions of a compiled step's entry "
    "computation that move a tensor as large as the delta rule's q between "
    "heads side by side and heads on an axis of their own (0 where the "
    "layer keeps [B, T, H * d] from its projections to the kernels and back)",
    labels=("program",))

_RERUNS = _metrics().gauge(
    "horovod_remat_forward_reruns",
    "Calls of the mixers' forward kernels (kda_fwd, flash_mla_fwd) in a "
    "compiled step's text beyond one a layer, a layer being one call of the "
    "kernel's backward (0 where every recomputed block keeps its mixer "
    "kernel's outputs for its backward pass)",
    labels=("program",))

# a mixer's forward kernel and the backward kernel that runs once a layer
_MIXER_KERNELS = {"kda_fwd": "kda_bwd", "flash_mla_fwd": "flash_mla_bwd_dq"}
_KERNEL_CALL = re.compile(
    r"%((?:kda|flash_mla)_\w+?)(?:\.\d+)? = .*\bcustom-call\(")
_OPERAND_SHAPES = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")
_INSTRUCTION = re.compile(
    r"\s+(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* (copy|reshape|transpose)\(")


def _dims(text: str) -> tuple:
    return tuple(int(n) for n in text.split(",") if n)


def _relayouts(entry_lines, q_shape, beta_shape) -> int:
    """The instructions of :func:`record_scan_program`'s third count, given
    the kernels' q ``[B, T, H * d]`` and beta ``[B, H / group, T, group]``
    as a ``kda_*`` call states them."""
    heads = beta_shape[1] * beta_shape[3]
    by_head = (heads, q_shape[-1] // heads)
    count = 0
    for line in entry_lines:
        m = _INSTRUCTION.match(line)
        if m is None or math.prod(_dims(m.group(1))) < math.prod(q_shape):
            continue
        if "op_name=" in line:
            count += "hvd.kda" in line
        else:   # the compiler's own copies carry no metadata
            count += _dims(m.group(1))[-2:] == by_head
    return count


def record_scan_program(program: str, hlo_text: str) -> tuple:
    """``(loops, {kernel: calls}, relayouts, reruns)`` of a compiled step's
    text (``compiled.as_text()``), set on the four gauges under ``program``:
    the ``while`` instructions whose ``op_name`` holds ``hvd.kda.scan``;
    the ``tpu_custom_call``s named ``kda_*`` by kernel; the entry
    computation's ``copy``, ``reshape`` and ``transpose`` instructions (a
    ``bitcast`` moves nothing) whose result holds at least as many elements
    as the kernels' q and whose ``op_name`` holds ``hvd.kda`` or, where it
    has none (the compiler's own copies), whose shape ends in ``[H, d]``;
    and the calls of ``kda_fwd`` and ``flash_mla_fwd`` beyond one a layer
    (as many as ``kda_bwd`` and ``flash_mla_bwd_dq`` have calls). On
    ``kimi_linear_16k_1chip`` that is 0, ``{"kda_fwd": 4, "kda_bwd": 4}``,
    0 and 0: every recomputed block keeps its mixer kernel's outputs. While
    a recomputed block ran its forward kernel again it was 8 ``kda_fwd``
    and 5 reruns (PR 38); before the kernels formed their operands 12
    loops, and while the layer held its tensors ``[B, T, H, d]`` 100
    relayouts, 47 GB moved a step (PERF.md §6, PR 31 and PR 36)."""
    loops = 0
    calls = collections.Counter()
    shapes = None
    for line in hlo_text.splitlines():
        if "hvd.kda.scan" in line and " while(" in line:
            loops += 1
        elif 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_CALL.search(line)
            if m is not None:
                calls[m.group(1)] += 1
                given = _OPERAND_SHAPES.search(line)
                if shapes is None and given is not None \
                        and m.group(1).startswith("kda_"):
                    shapes = [_dims(dims) for dims in re.findall(
                        r"\w+\[([\d,]*)\]", given.group(1))]
    entry = hlo_text.partition("\nENTRY ")[2].partition("\n}")[0]
    relayouts = 0 if shapes is None else _relayouts(
        entry.splitlines(), shapes[0], shapes[4])
    reruns = sum(calls[forward] - calls[backward]
                 for forward, backward in _MIXER_KERNELS.items())
    kda_calls = {kernel: n for kernel, n in calls.items()
                 if kernel.startswith("kda_")}
    _SCAN_LOOPS.labels(program=program).set(loops)
    for kernel in {"kda_fwd", "kda_bwd", *kda_calls}:
        _KERNEL_CALLS.labels(program=program, kernel=kernel).set(
            calls[kernel])
    _RELAYOUTS.labels(program=program).set(relayouts)
    _RERUNS.labels(program=program).set(reruns)
    return loops, kda_calls, relayouts, reruns


def publish(kda_stats) -> dict:
    """Set the gauges from a ``kda_stats`` collection and return what was
    set, ``{layer: {"mean_decay": .., "state_abs_max": ..}}``; a layer is
    the path of its module, ``block_2/kda``."""
    from flax.traverse_util import flatten_dict

    sown = {}
    for (*module, name), values in flatten_dict(dict(kda_stats)).items():
        # ``sow`` keeps a tuple of what was sown: the newest is the last
        sown.setdefault("/".join(module), {})[name] = float(values[-1])
    out = {}
    for layer, stats in sorted(sown.items()):
        if not {"mean_decay", "state_max"} <= set(stats):
            continue
        out[layer] = {"mean_decay": stats["mean_decay"],
                      "state_abs_max": stats["state_max"]}
        _DECAY.labels(layer=layer).set(stats["mean_decay"])
        _STATE.labels(layer=layer).set(stats["state_max"])
    return out
