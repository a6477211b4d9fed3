"""Scheduler (core/a2ws.py WorkerPool): host microseconds of task-boundary
work per task run, from the program's counters a2ws.boundary_ns and
a2ws.tasks (repro.core.spans.COUNTERS): the info update, the policy and
any steal, get_task, the task record and communicate; not the task, not
the idle wait.  The survey driver runs its pools in the window alone (the
warm-up calls the shot directly), so the process's sums are the window's
surveys.  None where the program keeps no such counters."""


def read(ctx):
    if "surveys" not in ctx:
        return None
    try:
        from repro.core.spans import COUNTERS
    except ImportError:
        return None
    sums = COUNTERS.snapshot()
    if not sums.get("a2ws.tasks"):
        return None
    return sums["a2ws.boundary_ns"] / sums["a2ws.tasks"] / 1e3
