"""Gauges of a block-diffusion step's noise (docs/sdar.md).

``models.sdar.SdarMoeLM`` sows, into the flax collection ``bd_stats``, the
share of the newest batch's rows that carry a loss weight (``masked_share``:
the positions the noise masked, about half under the linear schedule) and
the mean weight over them (``mean_weight``: ``1 / rate``, large where a
block drew a small rate). A training step does not carry the collection; a
caller who wants the numbers applies the model with ``mutable=["bd_stats"]``
and hands the collection to :func:`publish`.
"""

from __future__ import annotations

from .registry import registry as _metrics

_SHARE = _metrics().gauge(
    "horovod_bd_masked_share",
    "Share of the newest block-diffusion batch's rows that were masked and "
    "so carry a loss weight (0.5 under the linear schedule)")
_WEIGHT = _metrics().gauge(
    "horovod_bd_mean_weight",
    "Mean loss weight 1 / rate over the masked rows of the newest "
    "block-diffusion batch")


def publish(bd_stats) -> dict:
    """Set the two gauges from a ``bd_stats`` collection and return what
    was set, ``{"masked_share": .., "mean_weight": ..}`` (empty where the
    collection holds neither)."""
    # ``sow`` keeps a tuple of what was sown: the newest is the last
    out = {name: float(bd_stats[name][-1])
           for name in ("masked_share", "mean_weight") if name in bd_stats}
    if len(out) == 2:
        _SHARE.set(out["masked_share"])
        _WEIGHT.set(out["mean_weight"])
    return out
