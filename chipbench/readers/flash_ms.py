"""``flash_ms``: device time per step of the flash-attention kernels
(forward, dQ and dK/dV of every layer together), first device.

The three Pallas calls carry no names of their own yet (no ``name=`` on
the ``pallas_call``s: all show as ``%flash_attention.N``), so they are
read together, as the step's Mosaic custom calls."""


def seconds_per_step(run):
    if run["trace"] is None or "flash" not in run["kernel_work"]:
        return None
    d = run["trace"]["devices"][0]
    if not d["steps"] or not d["mosaic_s"]:
        return None
    return d["mosaic_s"] / d["steps"]


def read(run):
    seconds = seconds_per_step(run)
    return None if seconds is None else 1e3 * seconds
