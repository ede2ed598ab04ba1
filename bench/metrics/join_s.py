"""Seconds per join over the window: the window's length, from the first
join's start to the last one's end, over the joins completed in it. A join
runs from the host array to the exact host pair set."""


def read(run):
    ops = run.records.get("ops")
    return run.window_s / len(ops) if ops else None
