"""Gauges of the delta-rule layers' recurrence (docs/kimi-linear.md), under
a decay a channel (``kda``: Kimi-Linear) and a decay a head (``gdn``:
Olmo-Hybrid).

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
``hvd.kda.scan`` (``hvd.gdn.scan``) would show here as loops, and as calls
that are missing (the kernels are ``gdn_fwd`` / ``gdn_bwd`` under a decay a
head).
And whether the layout held: the kernels read q, k, v, g as ``[B, T, H *
d]`` and the layer keeps them so from its projections on; a tensor that is
taken to ``[B, T, H, d]`` on the way is copied whole on the TPU, and shows
here as a relayout. And whether a recomputed block kept its mixer kernel's
outputs (``models.parts.keep_policy``): a forward kernel the backward pass runs
again shows here as a call beyond the one a layer needs.

``models.olmo_hybrid.GatedDeltaMixer`` sows ``gdn_stats`` — the same two
numbers and ``beta_above_one``, the share of writes stronger than 1, under
which ``I - beta k k^T`` has a negative eigenvalue — for :func:`publish_gdn`.
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
_GDN_DECAY = _metrics().gauge(
    "horovod_gdn_mean_decay",
    "Mean per-head decay alpha of a gated-delta-rule layer over the newest "
    "batch (1.0: nothing is forgotten; near 0: the state is wiped a token)",
    labels=("layer",))
_GDN_STATE = _metrics().gauge(
    "horovod_gdn_state_abs_max",
    "Largest magnitude in a gated-delta-rule layer's state at the end of "
    "the newest batch's sequences",
    labels=("layer",))
_GDN_STRONG = _metrics().gauge(
    "horovod_gdn_beta_above_one_share",
    "Share of a gated-delta-rule layer's writes whose strength beta is "
    "above 1 in the newest batch: the negative-eigenvalue regime of "
    "I - beta k k^T (0 where beta stops at 1)",
    labels=("layer",))
_SCAN_LOOPS = _metrics().gauge(
    "horovod_kda_scan_loops",
    "while instructions under the scope hvd.kda.scan or hvd.gdn.scan in a "
    "compiled step's text (0 where the delta rule's kernels form their own "
    "operands)",
    labels=("program",))
_KERNEL_CALLS = _metrics().gauge(
    "horovod_kda_kernel_calls",
    "Mosaic custom calls of one of the delta rule's kernels (kda_fwd, "
    "kda_bwd; gdn_fwd, gdn_bwd under a decay a head) in a compiled step's "
    "text",
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
    "Calls of the mixers' forward kernels (kda_fwd, gdn_fwd, flash_mla_fwd, "
    "flash_fwd, flash_win_fwd, flash_bd_fwd) in a compiled step's text beyond one a layer, "
    "a layer being one call of the kernel's backward (0 where every "
    "recomputed block keeps its mixer kernel's outputs for its backward pass)",
    labels=("program",))

# a mixer's forward kernel and the backward kernel that runs once a layer
_MIXER_KERNELS = {"kda_fwd": "kda_bwd", "gdn_fwd": "gdn_bwd",
                  "flash_mla_fwd": "flash_mla_bwd_dq",
                  "flash_fwd": "flash_bwd_dq",
                  "flash_bd_fwd": "flash_bd_bwd_dq",
                  "flash_win_fwd": "flash_win_bwd_dq"}
_RULE_KERNELS = ("kda_", "gdn_")
_RULE_SCOPES = ("hvd.kda", "hvd.gdn")
_KERNEL_CALL = re.compile(
    r"%((?:kda|gdn|flash)_\w+?)(?:\.\d+)? = .*\bcustom-call\(")
_OPERAND_SHAPES = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")
_INSTRUCTION = re.compile(
    r"\s+(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* (copy|reshape|transpose)\(")


def _dims(text: str) -> tuple:
    return tuple(int(n) for n in text.split(",") if n)


def _relayouts(entry_lines, q_shape, v_shape, beta_shape) -> int:
    """The instructions of :func:`record_scan_program`'s third count, given
    the kernels' q and v ``[B, T, H * d]`` and beta ``[B, H' / group, T,
    group]`` as a ``kda_*`` or ``gdn_*`` call states them (``H'`` the heads
    in whole groups: H is the last group's every count that divides)."""
    whole, group = beta_shape[1] * beta_shape[3], beta_shape[3]
    by_head = {(heads, shape[-1] // heads)
               for heads in range(whole - group + 1, whole + 1)
               for shape in (q_shape, v_shape) if shape[-1] % heads == 0}
    count = 0
    for line in entry_lines:
        m = _INSTRUCTION.match(line)
        if m is None or math.prod(_dims(m.group(1))) < math.prod(q_shape):
            continue
        if "op_name=" in line:
            count += any(scope in line for scope in _RULE_SCOPES)
        else:   # the compiler's own copies carry no metadata
            count += _dims(m.group(1))[-2:] in by_head
    return count


def record_scan_program(program: str, hlo_text: str) -> tuple:
    """``(loops, {kernel: calls}, relayouts, reruns)`` of a compiled step's
    text (``compiled.as_text()``), set on the four gauges under ``program``:
    the ``while`` instructions whose ``op_name`` holds ``hvd.kda.scan`` or
    ``hvd.gdn.scan``; the ``tpu_custom_call``s named ``kda_*`` or ``gdn_*``
    by kernel; the entry computation's ``copy``, ``reshape`` and
    ``transpose`` instructions (a ``bitcast`` moves nothing) whose result
    holds at least as many elements as the kernels' q and whose ``op_name``
    holds ``hvd.kda`` or ``hvd.gdn`` or, where it has none (the compiler's
    own copies), whose shape ends in ``[H, d]``; and the calls of a mixer's
    forward kernel beyond one a layer (``_MIXER_KERNELS``: as many as its
    backward kernel has calls; 3 on ``laguna_xs2_8k_1chip``, whose sliding
    layers run ``flash_win_fwd`` again, 5 before PR 41). On
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
        if " while(" in line and any(
                scope + ".scan" in line for scope in _RULE_SCOPES):
            loops += 1
        elif 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_CALL.search(line)
            if m is not None:
                calls[m.group(1)] += 1
                given = _OPERAND_SHAPES.search(line)
                if shapes is None and given is not None \
                        and m.group(1).startswith(_RULE_KERNELS):
                    shapes = [_dims(dims) for dims in re.findall(
                        r"\w+\[([\d,]*)\]", given.group(1))]
    entry = hlo_text.partition("\nENTRY ")[2].partition("\n}")[0]
    relayouts = 0 if shapes is None else _relayouts(
        entry.splitlines(), shapes[0], shapes[2], shapes[4])
    reruns = sum(calls[forward] - calls[backward]
                 for forward, backward in _MIXER_KERNELS.items())
    kda_calls = {kernel: n for kernel, n in calls.items()
                 if kernel.startswith(_RULE_KERNELS)}
    _SCAN_LOOPS.labels(program=program).set(loops)
    for kernel in {"kda_fwd", "kda_bwd", *kda_calls}:
        _KERNEL_CALLS.labels(program=program, kernel=kernel).set(
            calls[kernel])
    _RELAYOUTS.labels(program=program).set(relayouts)
    _RERUNS.labels(program=program).set(reruns)
    return loops, kda_calls, relayouts, reruns


def _newest(stats) -> dict:
    """``{layer: {name: the newest value sown}}`` of a ``*_stats``
    collection; a layer is the path of its module, ``block_2/kda``."""
    from flax.traverse_util import flatten_dict

    sown = {}
    for (*module, name), values in flatten_dict(dict(stats)).items():
        # ``sow`` keeps a tuple of what was sown: the newest is the last
        sown.setdefault("/".join(module), {})[name] = float(values[-1])
    return dict(sorted(sown.items()))


def publish_gdn(gdn_stats) -> dict:
    """Set the three ``horovod_gdn_*`` gauges from a ``gdn_stats``
    collection and return what was set, ``{layer: {"mean_decay": ..,
    "state_abs_max": .., "beta_above_one_share": ..}}``."""
    out = {}
    for layer, stats in _newest(gdn_stats).items():
        if not {"mean_decay", "state_max", "beta_above_one"} <= set(stats):
            continue
        out[layer] = {"mean_decay": stats["mean_decay"],
                      "state_abs_max": stats["state_max"],
                      "beta_above_one_share": stats["beta_above_one"]}
        _GDN_DECAY.labels(layer=layer).set(stats["mean_decay"])
        _GDN_STATE.labels(layer=layer).set(stats["state_max"])
        _GDN_STRONG.labels(layer=layer).set(stats["beta_above_one"])
    return out


def publish(kda_stats) -> dict:
    """Set the gauges from a ``kda_stats`` collection and return what was
    set, ``{layer: {"mean_decay": .., "state_abs_max": ..}}``; a layer is
    the path of its module, ``block_2/kda``."""
    out = {}
    for layer, stats in _newest(kda_stats).items():
        if not {"mean_decay", "state_max"} <= set(stats):
            continue
        out[layer] = {"mean_decay": stats["mean_decay"],
                      "state_abs_max": stats["state_max"]}
        _DECAY.labels(layer=layer).set(stats["mean_decay"])
        _STATE.labels(layer=layer).set(stats["state_max"])
    return out
