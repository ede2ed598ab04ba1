"""The plain reference: exact L2 range search over integer rows, in NumPy.

It imports nothing of the program and takes nothing the program made. The
rows hold small integers (0/1 bits here, whose squared L2 distance is
their Hamming distance), so every product and every sum of squares or of
products is an integer below 2**24: float32 holds them all exactly, and a
float32 matrix product gives the exact squared distance whatever order it
sums in. ``check_integer_rows`` refuses rows for which that does not hold.
A squared distance is compared with ``threshold`` = K + ½, so no pair lies
within rounding of it.

``control=True`` computes the same answers with the arithmetic carried in
bfloat16, the nearest precision below the float32 the configuration
states: the squared norms, the dot products and their combination are
each rounded to bfloat16, as a matrix product with bfloat16 output gives
them. It stands in the program's place to show that the comparison fails.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

COLUMN_BLOCK = 65536
PAIR_BLOCK = 1 << 20


def check_integer_rows(x: np.ndarray) -> None:
    if x.dtype != np.float32 or x.ndim != 2:
        raise ValueError(f"rows must be a 2-D float32 array, got {x.dtype} {x.shape}")
    if x.size and (x.min() < 0 or x.max() > 255 or not np.array_equal(x, np.rint(x))):
        raise ValueError("rows must hold integers in [0, 255] for the reference to be exact")
    top = float(x.max(initial=0.0))
    if 2 * top * top * x.shape[1] >= 1 << 24:  # ‖x‖² + ‖y‖² must stay below 2**24
        raise ValueError(f"{x.shape[1]} features up to {top:g} can overflow float32's exact integers")


def _bf16(a: np.ndarray) -> np.ndarray:
    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


def partners(
    queries: np.ndarray, data: np.ndarray, threshold: float, *, control: bool = False
) -> tuple[np.ndarray, np.ndarray]:
    """Every (query position, data row) with squared distance <= threshold,
    as two int64 arrays sorted by query position, then by row."""
    check_integer_rows(queries)
    check_integer_rows(data)
    qn = np.einsum("ij,ij->i", queries, queries)
    qs, rs = [], []
    for c0 in range(0, data.shape[0], COLUMN_BLOCK):
        blk = data[c0 : c0 + COLUMN_BLOCK]
        bn = np.einsum("ij,ij->i", blk, blk)
        d2 = queries @ blk.T
        if control:
            d2 = _bf16(_bf16(_bf16(qn)[:, None] + _bf16(bn)[None, :]) - _bf16(2.0 * d2))
        else:  # in place; every partial sum is an integer below 2**24 in magnitude
            d2 *= -2.0
            d2 += bn[None, :]
            d2 += qn[:, None]
        q, r = np.nonzero(d2 <= threshold)
        qs.append(q)
        rs.append(r + c0)
    q = np.concatenate(qs).astype(np.int64)
    r = np.concatenate(rs).astype(np.int64)
    order = np.lexsort((r, q))
    return q[order], r[order]


def pair_squared_distances(a: np.ndarray, b: np.ndarray, ia: np.ndarray, ib: np.ndarray) -> np.ndarray:
    """Exact Σ(a[ia] − b[ib])² in int64, blocked over the pairs."""
    check_integer_rows(a)
    check_integer_rows(b)
    a8 = a.astype(np.uint8)
    b8 = a8 if b is a else b.astype(np.uint8)
    out = np.empty(ia.shape[0], np.int64)
    for s in range(0, ia.shape[0], PAIR_BLOCK):
        d = a8[ia[s : s + PAIR_BLOCK]].astype(np.int32) - b8[ib[s : s + PAIR_BLOCK]]
        out[s : s + PAIR_BLOCK] = np.einsum("ij,ij->i", d, d, dtype=np.int64)
    return out
