"""Share, in %, of the map phase's roofline: the least time the chip needs
for the map phase's work (``work.map_phase``: the rows read once; anchor
distances, cells and membership written once), over the device time of the
map kernel in the traced joins (``map_assign_blocked``: once for
``ops.map_assign`` and, after ``tighten``, once more for
``ops.assign_membership``). The work is the same however many passes the
program splits the phase into."""
from bench import work

KERNEL = "map_assign_blocked"


def read(run):
    red, j = run.reduction, run.cell.config["join"]
    joins = red.spans_named("bench.join") if red else []
    if not joins:
        return None
    t = red.device_s(lambda op, program: op == KERNEL, joins)
    if t <= 0:
        return None
    ops, nbytes = work.map_phase(run.records["rows"], run.records["dims"], j["n_dims"], j["p"])
    least, _ = work.least_time_s(ops, nbytes, run.peak)
    return 100.0 * least * len(joins) / t
