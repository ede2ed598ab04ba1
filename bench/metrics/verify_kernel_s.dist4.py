"""Device seconds per join of the verify distance kernels
(``pairdist_filtered_blocked``, ``pairdist_blocked``), in the traced joins,
the mean over the device planes."""

KERNELS = ("pairdist_filtered_blocked", "pairdist_blocked")


def read(run):
    red = run.reduction
    joins = red.spans_named("bench.join") if red else []
    if not joins or not red.ops:
        return None
    t = red.device_s(lambda op, program: op in KERNELS, joins)
    return t / len(joins) / len(red.ops) if t > 0 else None
