"""``st_expert_matmul_ms``: device time per step of the held ReLU-gated
experts' grouped matrix products, forward, recomputed and backward — the
Mosaic custom calls ``expert_matmul_fwd``, ``expert_matmul_bwd_dx`` and
``expert_matmul_bwd_dw``, first device."""


def read(run):
    return run["cell"].spec.reader("expert_matmul_ms").read(run)
