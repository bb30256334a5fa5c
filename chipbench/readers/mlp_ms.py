"""``mlp_ms``: device time per step of the forward and backward operations
traced under the models' scope ``hvd.mlp`` — a block's dense MLP and its
residual add; the expert layer and its shared expert are ``hvd.moe``'s
(``chipbench/components.py``), first device."""

from chipbench import components


def read(run):
    return components.component_ms(run, "mlp")
