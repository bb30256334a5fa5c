"""``embed_ms``: device time per step of the forward and backward operations
traced under the models' scope ``hvd.embed`` — the token (and position)
embedding and the scatter-add of its gradient
(``chipbench/components.py``), first device."""

from chipbench import components


def read(run):
    return components.component_ms(run, "embed")
