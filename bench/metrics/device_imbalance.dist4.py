"""How unevenly the verify stage loads the devices: the busiest device's
busy time inside the ``dist.stage`` spans of the traced joins (the union
of its operation intervals there), over the mean of the devices'. 1.0 is
an even load."""
from bench import dist_spans
from bench.trace import _union


def read(run):
    got = dist_spans.per_join(run)
    if got is None:
        return None
    spans, joins = got
    stages = [(sp.start, sp.end) for sp in spans.named("dist.stage", joins)]
    if not stages:
        return None
    busy = []
    for plane in run.reduction.ops:
        clipped = [(max(s, a), min(e, b)) for _, _, s, e in plane for a, b in stages
                   if e > a and s < b]
        busy.append(sum(e - s for s, e in _union(clipped)))
    mean = sum(busy) / len(busy)
    return max(busy) / mean if mean > 0 else None
