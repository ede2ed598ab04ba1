"""Seconds per join of the sampling phase (the stats stage, its readback
and the Gibbs chain): the ``dist.sample`` spans of
``distributed.distributed_join`` in the traced joins."""
from bench import dist_spans


def read(run):
    return dist_spans.seconds_per_join(run, "dist.sample")
