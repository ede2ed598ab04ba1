"""Seconds per join of the reduce phase, as ``JoinResult.verify_time_s``
gives it: host wall time from the start of the reduce phase to the host
pair set. It includes the wait for the map kernel, whose result the reduce
phase reads first."""


def read(run):
    ops = run.records.get("ops")
    return sum(op["verify_s"] for op in ops) / len(ops) if ops else None
