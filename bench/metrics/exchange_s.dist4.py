"""Device seconds per join of the exchange: the ``all_to_all`` operations
of the join's verify stage, in the traced joins, the mean over the device
planes. An operation's event carries its instruction's name, which JAX
gives the collective as ``all_to_all`` (``%all_to_all.16 = ...
all-to-all(...)`` in the stage compiled for a v5e 2x2); an instruction
that XLA names itself takes the opcode, ``all-to-all``."""

NAMES = ("all_to_all", "all-to-all")


def read(run):
    red = run.reduction
    joins = red.spans_named("bench.join") if red else []
    if not joins or not red.ops:
        return None
    t = red.device_s(lambda op, program: op.startswith(NAMES), joins)
    return t / len(joins) / len(red.ops) if t > 0 else None
