"""``expert_matmul_ms``: device time per step of the held experts' grouped
matrix products, forward, recomputed and backward — the Mosaic custom calls
the program names ``expert_matmul_fwd``, ``expert_matmul_bwd_dx`` and
``expert_matmul_bwd_dw``, first device."""

KERNELS = ("expert_matmul_fwd", "expert_matmul_bwd_dx",
           "expert_matmul_bwd_dw")


def read(run):
    return run["cell"].spec.reader("flash_win_ms").kernels_ms(run, KERNELS)
