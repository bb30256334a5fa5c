"""``st_moe_experts_ms``: the part of ``st_moe_ms`` under
``hvd.moe.experts`` — the sort of the assignments, the row gather, the
grouped products (``st_expert_matmul_ms`` is their kernels alone), the ReLU
gate, the weighting and the sum back by token, forward, recomputed and
backward, the loops' bodies counted once. First device, per step."""


def read(run):
    return run["cell"].spec.reader("st_moe_ms").scope_ms(
        run, "hvd.moe.experts")
