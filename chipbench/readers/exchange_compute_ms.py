"""``exchange_compute_ms``: device time per step of the operations under
``hvd.exchange`` or ``hvd.sync_stats`` that are not collectives — the
averaging divide, casts, a codec; the collectives themselves are
``allreduce_ms`` (``chipbench/scopes.py``), first device."""

from chipbench import scopes


def read(run):
    return scopes.phase_ms(run, "exchange_compute")
