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
"""

from __future__ import annotations

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
