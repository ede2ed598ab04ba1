"""Milliseconds per query batch of the host unpack (``serve.unpack``
spans): ``np.nonzero`` over the masks, the gather of row ids, and
``np.unique`` of the pairs."""
from bench import program_spans


def read(run):
    s = program_spans.seconds_per_op(run, "bench.query_batch", "serve.unpack")
    return None if s is None else 1e3 * s
