"""``moe_experts_ms``: the part of ``moe_ms`` under the expert layer's scope
``hvd.moe.experts`` — the sort of the assignments, the row gather, the
grouped products (``expert_matmul_ms`` is their kernels alone), the
weighting, the scatter-add back by token and the shared expert, forward,
recomputed and backward. First device, per step."""


def read(run):
    return run["cell"].spec.reader("moe_ms").scope_ms(run, "hvd.moe.experts")
