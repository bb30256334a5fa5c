"""Gauges of the delta-rule layers' recurrence (docs/kimi-linear.md).

``models.kimi_linear.KDAMixer`` sows, into the flax collection
``kda_stats``, the mean of its per-channel decay over the batch
(``mean_decay``) and the largest magnitude in its state at the sequence's
end (``state_max``): the two numbers that say whether the recurrence
forgets or blows up. A training step does not carry the collection; a
caller who wants the numbers applies the model with
``mutable=["kda_stats"]`` and hands the collection to :func:`publish`.

And two gauges of a *compiled* step's text, set by
:func:`record_scan_program` (whoever holds the compiled step calls it, as
with ``obs.compiles.record_exchange_collectives``): whether the recurrence
engaged its kernels. ``ops.kda`` forms a chunk's operands inside
``kda_fwd`` and ``kda_bwd``; a lowering that fell back to XLA loops under
``hvd.kda.scan`` would show here as loops, and as calls that are missing.
"""

from __future__ import annotations

import collections
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

_KERNEL_CALL = re.compile(r"%(kda_\w+?)(?:\.\d+)? = .*\bcustom-call\(")


def record_scan_program(program: str, hlo_text: str) -> tuple:
    """``(loops, {kernel: calls})`` of a compiled step's text
    (``compiled.as_text()``), set on the two gauges under ``program``:
    the ``while`` instructions whose ``op_name`` holds ``hvd.kda.scan``,
    and the ``tpu_custom_call``s named ``kda_*`` by kernel. On
    ``kimi_linear_16k_1chip`` that is 0 and ``{"kda_fwd": 8, "kda_bwd":
    4}`` (four layers, the forward run again where a block is
    recomputed); before the kernels formed their operands it was 12
    loops (PERF.md §6, PR 31)."""
    loops = 0
    calls = collections.Counter()
    for line in hlo_text.splitlines():
        if "hvd.kda.scan" in line and " while(" in line:
            loops += 1
        elif 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_CALL.search(line)
            if m is not None:
                calls[m.group(1)] += 1
    _SCAN_LOOPS.labels(program=program).set(loops)
    for kernel in {"kda_fwd", "kda_bwd", *calls}:
        _KERNEL_CALLS.labels(program=program, kernel=kernel).set(
            calls[kernel])
    return loops, dict(calls)


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
