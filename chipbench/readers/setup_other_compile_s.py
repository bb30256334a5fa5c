"""``setup_other_compile_s``: seconds the program's compile ledger holds, in
the stages ``trace``, ``lower`` and ``backend_compile`` (a fetch from the
persistent cache is inside the last) and before the window opened, for
every program other than the step — seeded weights, ``opt.init``,
``broadcast_parameters``, the norms read for ``correct``. With
``step_trace_lower_s`` and ``cache_retrieval_s`` it closes the compile side
of ``setup_s``. An event that ended inside one of the step's own (a jitted
function the model calls, traced while the step was) has its seconds in the
step's and is left out; ``None`` where the program has no ledger or the
ledger nothing before the window."""

from chipbench import scopes

STAGES = ("trace", "lower", "backend_compile")


def read(run):
    try:
        from horovod_tpu.obs import compile_events
    except ImportError:
        return None
    events = [e for e in compile_events() if e.at < run["window"].opened_at]
    if not events:
        return None
    of_step = [(e.at - e.seconds, e.at) for e in events
               if scopes.STEP_PROGRAM in e.fun_name]
    return sum(e.seconds for e in events
               if e.stage in STAGES and scopes.STEP_PROGRAM not in e.fun_name
               and not any(a < e.at <= b for a, b in of_step))
