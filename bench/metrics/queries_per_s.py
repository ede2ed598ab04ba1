"""Queries answered over the whole window, per second: every query of every
batch, over the window from the first batch's submission to the last
batch's pairs on the host."""


def read(run):
    ops = run.records.get("ops")
    if not ops or run.window_s <= 0:
        return None
    return sum(op["attempted"] for op in ops) / run.window_s
