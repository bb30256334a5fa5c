"""Gauges of the expert layer's routing (docs/laguna.md).

``models.experts.ExpertLayer`` sows, into the flax collection ``moe_stats``,
how many assignments each expert got (``assignments``), how many went to
experts this chip does not hold (``absent``) and, of the loop that
multiplies the held experts' rows, the ``slices`` it ran, the ``slots`` in
use, the slots it ``ran`` over and those whose sum by token went through
``ops.grouped_matmul.moe_rows_add`` (``summed``) and the share of the gated
hidden rows' elements that are exactly zero (``gate_zero_share``, a pass of
its own that only a caller of the collection pays for). A training step does
not carry the collection; a caller who wants the numbers applies the model
with ``mutable=["moe_stats"]`` and hands the collection to :func:`publish`.

And one gauge of a *compiled* step's text, set by
:func:`record_layout_program` (whoever holds the compiled step calls it, as
with ``obs.kda.record_scan_program``): whether a recomputed layer kept its
routing and slot layout (``models.experts.KEPT_NAMES``, saved by
``models.parts.keep_policy("models.experts")``). One that does not selects
and sorts again in its backward pass, and shows here as sorts beyond the
ones a layer needs.
"""

from __future__ import annotations

import re

from .registry import registry as _metrics

_LOAD = _metrics().gauge(
    "horovod_moe_expert_load_max_over_mean",
    "Assignments of the busiest expert over the mean over all experts, by "
    "expert layer (1.0: the router spreads tokens evenly)",
    labels=("layer",))
_HELD = _metrics().gauge(
    "horovod_moe_held_assignment_share",
    "Share of a layer's token-to-expert assignments that went to experts "
    "this chip holds (held / num_experts under an even router)",
    labels=("layer",))
_SLICES = _metrics().gauge(
    "horovod_moe_slices_run",
    "Slices of slots the held experts' loop ran, by expert layer: the "
    "slots in use over a slice's, rounded up (0: no row was routed here)",
    labels=("layer",))
_FILL = _metrics().gauge(
    "horovod_moe_slot_fill",
    "Slots in use (the rows routed here, each expert's padded to whole "
    "kernel tiles) over the slots the loop ran over, by expert layer (1.0 "
    "where it ran none)",
    labels=("layer",))
_SUMMED = _metrics().gauge(
    "horovod_moe_sum_kernel_share",
    "Slots whose rows were added to their tokens' sum by the moe_rows_add "
    "kernel over the slots the loop ran over, by expert layer (0.0 where "
    "the width is no multiple of 128 and XLA's scatter-add runs; 1.0 where "
    "the loop ran none)",
    labels=("layer",))
_ZEROS = _metrics().gauge(
    "horovod_moe_gate_zero_share",
    "Share of the elements of the held experts' gated hidden rows, gate(x "
    "w1) * (x w3) over the rows routed here, that are exactly zero, by "
    "expert layer (about a half under a relu gate on seeded weights, what "
    "'sparse ReGLU' means on this chip; 0 under silu)",
    labels=("layer",))
_RERUNS = _metrics().gauge(
    "horovod_moe_layout_reruns",
    "Times a compiled step's text selects a layer's experts (top_k, under "
    "hvd.moe.route) or sorts its assignments into slots (under "
    "hvd.moe.experts) beyond once a layer, a layer being one backward loop "
    "over the held experts' slices (0 where every recomputed expert layer "
    "keeps its routing and slot layout for its backward pass)",
    labels=("program",))

_SORT = re.compile(r' (?:sort|topk)\(|custom_call_target="TopK"')


def record_layout_program(program: str, hlo_text: str) -> tuple:
    """``(selections, sorts, layers, reruns)`` of a compiled step's text
    (``compiled.as_text()``), the last set on the gauge under ``program``:
    the ``sort`` instructions (``topk``, or a ``TopK`` call, where the
    backend has one) whose ``op_name`` holds ``hvd.moe.route``, those under
    ``hvd.moe`` otherwise (``models.experts.slot_layout``'s one), the
    backward loops over the slices (a ``while`` named ``hvd.moe.experts/
    while`` under a ``transpose``), and the larger of the first two beyond
    the third. On the four expert cells that is 0 — 4, 6, 4 and 4 layers
    (``laguna_xs2_8k_1chip``, ``sdar_moe_8k_1chip``,
    ``kimi_linear_16k_1chip``, ``smallthinker_16k_1chip``), each selected
    and sorted once; as many reruns as layers while a recomputed half kept
    nothing of its routing (before PR 47)."""
    selections = sorts = layers = 0
    for line in hlo_text.splitlines():
        if "hvd.moe" not in line:
            continue
        if _SORT.search(line):
            selections += "hvd.moe.route" in line
            sorts += "hvd.moe.route" not in line
        elif " while(" in line and "transpose(" in line \
                and 'hvd.moe.experts/while"' in line:
            layers += 1
    reruns = max(selections, sorts) - layers if layers else 0
    _RERUNS.labels(program=program).set(reruns)
    return selections, sorts, layers, reruns


def publish(moe_stats) -> dict:
    """Set the gauges from a ``moe_stats`` collection and return what was
    set, ``{layer: {"load_max_over_mean": .., "held_share": ..,
    "slices_run": .., "slot_fill": .., "sum_kernel_share": ..,
    "gate_zero_share": ..}}`` (the last where the layer sowed it); a layer is
    the path of its module, ``block_3/moe``."""
    import numpy as np
    from flax.traverse_util import flatten_dict

    sown = {}
    for (*module, name), values in flatten_dict(dict(moe_stats)).items():
        # ``sow`` keeps a tuple of what was sown: the newest is the last
        sown.setdefault("/".join(module), {})[name] = np.asarray(values[-1])
    out = {}
    for layer, stats in sorted(sown.items()):
        if not {"assignments", "absent", "slices", "slots", "ran",
                "summed"} <= set(stats):
            continue
        counts = stats["assignments"].astype(np.float64)
        total = counts.sum()
        if not total:
            continue
        ran = int(stats["ran"])
        out[layer] = {
            "load_max_over_mean": float(counts.max() / counts.mean()),
            "held_share": float(1.0 - stats["absent"] / total),
            "slices_run": int(stats["slices"]),
            "slot_fill": float(stats["slots"] / ran) if ran else 1.0,
            "sum_kernel_share": float(stats["summed"] / ran) if ran else 1.0}
        for gauge, name in ((_LOAD, "load_max_over_mean"),
                            (_HELD, "held_share"), (_SLICES, "slices_run"),
                            (_FILL, "slot_fill"),
                            (_SUMMED, "sum_kernel_share")):
            gauge.labels(layer=layer).set(out[layer][name])
        if "gate_zero_share" in stats:
            out[layer]["gate_zero_share"] = float(stats["gate_zero_share"])
            _ZEROS.labels(layer=layer).set(out[layer]["gate_zero_share"])
    return out
