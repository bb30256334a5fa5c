"""Gauges of the delta-rule layers' recurrence (docs/kimi-linear.md).

``models.kimi_linear.KDAMixer`` sows, into the flax collection
``kda_stats``, the mean of its per-channel decay over the batch
(``mean_decay``) and the largest magnitude in its state at the sequence's
end (``state_max``): the two numbers that say whether the recurrence
forgets or blows up. A training step does not carry the collection; a
caller who wants the numbers applies the model with
``mutable=["kda_stats"]`` and hands the collection to :func:`publish`.
"""

from __future__ import annotations

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
