"""Device milliseconds per query batch of the serve stage program
(``core.distributed.make_stage_serve``), in the traced batches."""

PROGRAM = "jit_per_shard"  # the jitted shard_map that make_stage_serve returns


def read(run):
    red = run.reduction
    batches = red.spans_named("bench.query_batch") if red else []
    if not batches:
        return None
    t = red.device_s(lambda op, program: program.startswith(PROGRAM), batches)
    return 1e3 * t / len(batches) if t > 0 else None
