"""Share of the valid pairs of the reduce loop's tiles that survive the
pivot filter: Σ ``n_cand`` / Σ ``n_valid`` over the ``verify.tile`` spans
that carry ``n_cand`` (the pre-pass survivors, or the compact path's
in-band count). Lower is better: the less survives, the more the pivot
bound prunes before the exact kernel; at 1.0 the pre-pass prunes nothing."""
from bench import program_spans


def read(run):
    got = program_spans.per_op(run, "bench.join")
    if got is None:
        return None
    spans, joins = got
    tiles = [sp.counts for sp in spans.named("verify.tile", joins) if "n_cand" in sp.counts]
    valid = sum(c["n_valid"] for c in tiles)
    return sum(c["n_cand"] for c in tiles) / valid if valid else None
