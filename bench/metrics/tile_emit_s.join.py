"""Seconds per join in the reduce loop's host emission (``verify.emit``
spans): each tile's ``np.nonzero`` over its mask, or the slice of its pair
buffer, and the gather of the pairs' row ids."""
from bench import program_spans


def read(run):
    return program_spans.seconds_per_op(run, "bench.join", "verify.emit")
