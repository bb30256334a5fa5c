"""``kda_roofline``: the least time the chip could take for the delta
rule's needed FLOPs and bytes (``kernel_work``'s ``kda``: the recurrence's
own products and its operands once each way, whatever implements it) over
``kda_ms``, in percent."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").roofline(
        run, spec.reader("kda_ms").read(run), "kda")
