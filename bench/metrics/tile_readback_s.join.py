"""Seconds per join in the reduce loop's tile readbacks (``verify.readback``
spans): the host waits for each tile's kernel and copies its mask or pair
buffer."""
from bench import program_spans


def read(run):
    return program_spans.seconds_per_op(run, "bench.join", "verify.readback")
