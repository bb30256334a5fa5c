"""``unscoped_pct``: share of the device-busy time of the traced steady
steps spent in operations under none of the program's scopes, or whose
instruction the compiled HLO text does not hold — the attribution's own
check (``chipbench/scopes.py``), first device."""

from chipbench import scopes


def read(run):
    seconds = scopes.phase_seconds(run)
    if seconds is None or not seconds["busy"]:
        return None
    return 100.0 * seconds["unscoped"] / seconds["busy"]
