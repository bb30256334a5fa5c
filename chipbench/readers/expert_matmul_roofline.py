"""``expert_matmul_roofline``: the least time the chip could take for the
grouped products' FLOPs and bytes at the *expected* number of rows routed
to held experts (``kernel_work``'s ``expert_matmul``) over
``expert_matmul_ms``, in percent."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").roofline(
        run, spec.reader("expert_matmul_ms").read(run), "expert_matmul")
