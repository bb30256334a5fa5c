"""``st_expert_matmul_roofline``: the least time the chip could take for the
grouped products' FLOPs and bytes at the *expected* rows routed to held
experts (``kernel_work``'s ``expert_matmul``) over ``st_expert_matmul_ms``,
in percent."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").roofline(
        run, spec.reader("st_expert_matmul_ms").read(run), "expert_matmul")
