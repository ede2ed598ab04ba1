"""Seconds per join of the verify stage and its readback: dispatch, the
``all_to_all`` exchange and the verify and compaction on the devices, up
to the wait for their counts (``dist.stage``), then the pair buffers and
counts to the host (``dist.readback``), in the traced joins."""
from bench import dist_spans


def read(run):
    return dist_spans.seconds_per_join(run, "dist.stage", "dist.readback")
