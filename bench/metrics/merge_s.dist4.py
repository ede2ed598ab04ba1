"""Seconds per join of the host merge (the slots' pairs concatenated,
ordered and made unique): the ``dist.merge`` spans of
``distributed.distributed_join`` in the traced joins."""
from bench import dist_spans


def read(run):
    return dist_spans.seconds_per_join(run, "dist.merge")
