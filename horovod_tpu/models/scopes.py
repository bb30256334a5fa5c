"""The component scopes of the models: whose a device operation is.

``jax.named_scope`` names that the blocks set at their call sites, so that
every operation of the forward and the backward pass of ``TransformerLM``,
``LagunaLM``, ``KimiLinearLM`` and ``ResNet`` carries one owner in its
``op_name`` (docs/tracing.md, "Scopes in a compiled step"). A scope is
metadata of the compiled program and no code that runs: always on, and a
refactor keeps the names, because readers find device time by them
(``chipbench/components.py``).

Set where a block calls its part and not inside the part, the six never
nest in one another; ``hvd.moe`` (``experts.ExpertLayer``) is the seventh
owner, and the shared expert's ``GatedMLP`` stays its.
"""

EMBED = "hvd.embed"     # tok_embed, and pos_embed with the add
NORM = "hvd.norm"       # a block-level norm: ln_attn, ln_mlp, ln_final;
#                         every BatchNorm of ResNet
MIXER = "hvd.mixer"     # the block's whole mixer call and its residual add:
#                         encloses hvd.kda*, hvd.mla*, rotary, the gate, the
#                         kernels and the mixer's own norms
MIXER_PROJ = "hvd.mixer.proj"   # inside the mixer, its input and output
#                                 projections only
MLP = "hvd.mlp"         # the block's dense MLP and its residual add
HEAD = "hvd.head"       # lm_head and the whole of lm_loss; ResNet's pool
#                         and classifier
