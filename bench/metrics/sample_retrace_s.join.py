"""Seconds per join that the sampling phase (``spjoin.sample`` spans) spends
in JAX's tracing, lowering, and compiling or loading of programs: the
union of those host events (``program_spans.RETRACE``) inside the span."""
from bench import program_spans


def read(run):
    got = program_spans.per_op(run, "bench.join")
    if got is None:
        return None
    spans, joins = got
    samples = spans.named("spjoin.sample", joins)
    if not samples:
        return None
    retrace = program_spans.RETRACE.search
    return spans.covered_s(lambda name: retrace(name) is not None, samples) / len(joins)
