"""Milliseconds per query batch of the serve stage's readback
(``serve.readback`` spans): the wait for the stage and the copies of its
overflow flags, its dense hit masks and the W ids to the host."""
from bench import program_spans


def read(run):
    s = program_spans.seconds_per_op(run, "bench.query_batch", "serve.readback")
    return None if s is None else 1e3 * s
