"""Device seconds per join of the verify distance kernels
(``pairdist_filtered_blocked``, ``pairdist_blocked``), in the traced joins."""

KERNELS = ("pairdist_filtered_blocked", "pairdist_blocked")


def read(run):
    red = run.reduction
    joins = red.spans_named("bench.join") if red else []
    if not joins:
        return None
    t = red.device_s(lambda op, program: op in KERNELS, joins)
    return t / len(joins) if t > 0 else None
