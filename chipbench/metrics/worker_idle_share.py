"""Scheduler (core/a2ws.py WorkerPool): worker-seconds inside the window's
completed surveys with no shot running, over workers x survey time, in %.
Read from the pool's task records (start, end, worker)."""


def read(ctx):
    surveys = ctx.get("surveys")
    if not surveys:
        return None
    idle = total = 0.0
    for i, sv in enumerate(surveys):
        dur = sv["end"] - sv["start"]
        busy = sum(x["end"] - x["start"] for x in ctx["records"] if x["survey"] == i)
        idle += sv["workers"] * dur - busy
        total += sv["workers"] * dur
    return 100.0 * idle / total
