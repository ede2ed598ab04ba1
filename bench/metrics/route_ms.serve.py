"""Milliseconds per query batch of the host routing pass (``serve.route``
spans): the map pass over the batch and the count that sizes the stage's
W capacity."""
from bench import program_spans


def read(run):
    s = program_spans.seconds_per_op(run, "bench.query_batch", "serve.route")
    return None if s is None else 1e3 * s
