"""Seconds per join of the plan (anchors and partition, the two counting
passes, tighten, placement and capacities): the ``dist.plan`` spans of
``distributed.distributed_join`` in the traced joins."""
from bench import dist_spans


def read(run):
    return dist_spans.seconds_per_join(run, "dist.plan")
