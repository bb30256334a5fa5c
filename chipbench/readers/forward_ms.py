"""``forward_ms``: device time per step of the operations traced under
``hvd.loss`` and not transposed — the forward pass, its flash kernel
included (``chipbench/scopes.py``), first device."""

from chipbench import scopes


def read(run):
    return scopes.phase_ms(run, "forward")
