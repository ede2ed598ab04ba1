"""Seconds per join of the sampling phase (node fits, goodness of fit,
Gibbs pivots), as ``JoinResult.sample_time_s`` gives it; the phase ends in
host reads of its results."""


def read(run):
    ops = run.records.get("ops")
    return sum(op["sample_s"] for op in ops) / len(ops) if ops else None
