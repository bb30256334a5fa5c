"""``dispatch_ms``: median host time for one step call to return, over
every step of the window."""

import statistics


def read(run):
    return 1e3 * statistics.median(run["window"].dispatch_s)
