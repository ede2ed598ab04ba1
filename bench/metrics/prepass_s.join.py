"""Seconds per join in the reduce loop's pivot pre-pass (``verify.prepass``
spans): each tile's bound dispatch, its whole-mask readback and the host
count of its survivors."""
from bench import program_spans


def read(run):
    return program_spans.seconds_per_op(run, "bench.join", "verify.prepass")
