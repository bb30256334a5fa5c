"""``norm_ms``: device time per step of the forward and backward operations
whose root was traced under the models' scope ``hvd.norm`` — the block-level
norms (``ln_attn``, ``ln_mlp``, ``ln_final``; every BatchNorm of ResNet), the
mixers' own norms apart (``chipbench/components.py``), first device.

**Not what the norms cost.** On the TPU the compiler leaves a norm no
operation of its own: its statistic becomes an epilogue of the fusion that
produces its input (a matmul's, a convolution's) and its scale-and-shift a
prologue of the one that consumes its output, and a fusion counts where
its root's ``op_name`` puts it. So this reads only the few fusions *rooted*
in a norm — 0.03 ms of a 94 ms step on ``gpt2m_1chip``, 0.78 of 47 on
``resnet50_1chip``, 8–9 of 740–850 on the Laguna and Kimi-Linear cells (my
chip runs, PR 37) — and the rest of the norms' work is inside
``mixer_proj_ms``, ``mlp_ms`` and ``head_ms`` (on ResNet under no owner:
``component_other_pct``). A reading near 0 says the norms are fused away,
not that they are free; price a norm from a kernel-level trace or from its
bytes, not from this number (PERF.md §5)."""

from chipbench import components


def read(run):
    return components.component_ms(run, "norm")
