"""``gdn_roofline``: the least time the chip could take for the gated delta
rule's needed FLOPs and bytes (``kernel_work``'s ``gdn``: the recurrence's
own products and its operands once each way, whatever implements it) over
``gdn_ms``, in percent."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").roofline(
        run, spec.reader("gdn_ms").read(run), "gdn")
