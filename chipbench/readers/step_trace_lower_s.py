"""``step_trace_lower_s``: seconds the program's compile ledger holds for
tracing the step program to a jaxpr and lowering it to StableHLO, before
the window opened — the part of ``compile_s`` that is the program's own
Python, whatever the cache holds (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(run):
    return scopes.step_compile_seconds(run, ("trace", "lower"))
