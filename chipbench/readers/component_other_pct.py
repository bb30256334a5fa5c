"""``component_other_pct``: share of the forward and backward phases' device
time spent in operations under none of the seven owners (``hvd.embed``,
``hvd.norm``, ``hvd.mixer``, ``hvd.mlp``, ``hvd.head``, ``hvd.moe``) — the
component attribution's own check, as ``unscoped_pct`` is the phases'
(``chipbench/components.py``), first device. With the phases' and the
components' metrics of the same line it also gives the time under
``hvd.moe`` where no ``moe_*`` metric lists the cell: ``(forward_ms +
backward_ms) * (1 - this / 100) - embed - norm - mixer - mlp - head``.
On ``resnet50_1chip`` it reads about 98: the convolutions have no owner and
the BatchNorms are fused into them (``norm_ms``)."""

from chipbench import components


def read(run):
    found = components.seconds(run)
    if found is None or not found["total"]:
        return None
    return 100.0 * found["other"] / found["total"]
