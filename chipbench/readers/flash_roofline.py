"""``flash_roofline``: the least time the chip could take for the flash
kernels' needed FLOPs and bytes (the larger of FLOPs over peak FLOP/s and
bytes over peak HBM bytes/s; shape functions in the family file) over the
time they took (the ``flash_ms`` reader's), in percent."""


def bound(work, peaks):
    """``(least seconds, which peak bounds)`` for ``work``."""
    by_flops = work["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), \
        "flops" if by_flops >= by_bytes else "bytes"


def read(run):
    seconds = run["cell"].spec.reader("flash_ms").seconds_per_step(run)
    if seconds is None:
        return None
    least, _ = bound(run["kernel_work"]["flash"], run["peaks"])
    return 100.0 * least / seconds
