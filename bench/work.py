"""Work that a phase needs, counted from its shapes, independent of how
many passes or kernels the program splits it into."""
from __future__ import annotations

WORD = 4  # bytes of a float32 or int32
MEMBER_BITS = 32  # partitions packed per membership word


def map_phase(n: int, m: int, anchors: int, p: int) -> tuple[float, float]:
    """(operations, bytes) of the map phase over ``n`` rows of ``m``
    float32 features, ``anchors`` pivot anchors and ``p`` partitions.

    Bytes: the rows and anchors read once, the kernel and whole boxes
    (lo and hi of each, p × anchors) read once, and the anchor distances
    (the mapped coordinates), the kernel cell and the packed whole
    membership of every row written once.
    Operations: the squared distance of every row to every anchor as a dot
    product (2·m per pair), and one lower and one upper comparison per row,
    box and coordinate for the kernel and for the whole boxes."""
    read = (n * m + anchors * m + 4 * p * anchors) * WORD
    written = (n * anchors + n + n * -(-p // MEMBER_BITS)) * WORD
    ops = 2.0 * n * anchors * m + 2 * 2.0 * n * p * anchors
    return ops, float(read + written)


def least_time_s(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time for the work, and which bound sets it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
