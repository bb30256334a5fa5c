"""``device_idle_pct``: share of the traced steady window in which no
operation ran on the device, averaged over the chips used."""

import statistics


def read(run):
    if run["trace"] is None:
        return None
    return 100.0 * statistics.fmean(
        1.0 - d["busy_s"] / d["window_s"] for d in run["trace"]["devices"])
