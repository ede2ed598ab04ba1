"""Seconds per join of the map phase, as the program's ``spjoin.map`` span
gives it: anchors and partition plan, the map kernel, and the host copies
of its cells and membership, so it includes the kernel's wait."""
from bench import program_spans


def read(run):
    return program_spans.seconds_per_op(run, "bench.join", "spjoin.map")
