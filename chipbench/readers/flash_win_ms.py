"""``flash_win_ms``: device time per step of the Mosaic custom calls the
program names ``flash_win_fwd``, ``flash_win_bwd_dq`` and
``flash_win_bwd_dkv`` (the flash-attention kernels of the sliding layers,
the forward's recomputation included), first device."""

from chipbench import scopes

WINDOW = ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv")


def kernels_ms(run, names):
    """Milliseconds per step of the kernels ``names`` together; ``None``
    where the trace holds none of them."""
    found = [ms for ms in (scopes.kernel_ms(run, n) for n in names)
             if ms is not None]
    return sum(found) if found else None


def roofline(run, ms, work: str):
    """The least time the chip could take for ``kernel_work``'s entry
    ``work`` (the larger of FLOPs over peak FLOP/s and bytes over peak HBM
    bytes/s) over the ``ms`` its kernels took, in percent."""
    if ms is None or work not in run["kernel_work"]:
        return None
    least, _ = run["cell"].spec.reader("flash_roofline").bound(
        run["kernel_work"][work], run["peaks"])
    return 100.0 * least / (1e-3 * ms)


def read(run):
    return kernels_ms(run, WINDOW)
