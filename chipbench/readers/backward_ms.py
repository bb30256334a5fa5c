"""``backward_ms``: device time per step of the operations traced under
``transpose(jvp(hvd.loss))`` — the backward pass, its two flash kernels
included, the collectives among them left out (``chipbench/scopes.py``),
first device."""

from chipbench import scopes


def read(run):
    return scopes.phase_ms(run, "backward")
