"""``optimizer_ms``: device time per step of the operations traced under
``hvd.optimizer`` (the inner ``optimizer.update`` of
``DistributedOptimizer``) or ``hvd.apply_updates``; XLA fuses the two
into the same loops, so they are one number (``chipbench/scopes.py``),
first device."""

from chipbench import scopes


def read(run):
    return scopes.phase_ms(run, "optimizer")
