"""``head_ms``: device time per step of the forward and backward operations
traced under the models' scope ``hvd.head`` — ``lm_head`` and the whole of
``lm_loss``; ResNet's pool and classifier (``chipbench/components.py``),
first device. The final norm's statistic and scale are fused into these
operations where the compiler put them there (``norm_ms``)."""

from chipbench import components


def read(run):
    return components.component_ms(run, "head")
