"""Pallas TPU kernel: blocked all-pairs distance + fused threshold epilogue.

This is the verify-phase hot spot of SP-Join (paper reduce phase: every
kernel-partition row is checked against every whole-partition row) and also
the map-phase space mapping (objects x anchors). The same kernel serves both.

TPU adaptation of the paper's per-reducer verify loop (DESIGN.md par.2):

  * Grid (nv, nw, nm): V-tiles x W-tiles x feature-chunks. The feature axis is
    innermost so a VMEM accumulator carries partial distances across chunks —
    the (a, b, m) intermediate never exists, and for the masked variant the
    (a, b) float distance matrix never touches HBM either (only the int8 mask
    or per-row counts do, an 8x/32x HBM-write saving over materializing f32
    distances).
  * MXU path (l2 / cosine / dot): the cross term is a (bv, bm) x (bm, bw)
    ``dot_general`` per chunk — systolic-array work, bm = 128 aligned.
  * VPU path (l1 / linf): |x - y| reductions are elementwise. The feature
    block is lane-aligned like the MXU path (bm = 128, or the whole width
    when it is narrower — Mosaic tiles a block's last dim by 128 unless it
    spans the array), and the kernel walks it one feature at a time over
    (bv, bw) tiles, so no 3-d intermediate exists.
  * Fused epilogue on the last chunk: sqrt / 1-minus, then optional
    ``<= delta`` mask in int8.

Block sizes default to (128, 128, 128): MXU-aligned tiles; VMEM footprint
per step = x(64 KiB) + y(64 KiB) + acc(64 KiB) + out tile, far under the
~16 MiB/core budget, leaving room for double-buffered pipelining.

Correctness contract (validated against ``ref.py`` in tests/test_kernels.py):
inputs are zero-padded to block multiples by ``ops.py``; zero padding in the
feature dimension is exact for every supported metric (|0-0| contributes 0),
and padded rows/cols are sliced away after the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MXU_METRICS = ("l2", "cosine", "dot")
VPU_METRICS = ("l1", "linf")
METRICS = MXU_METRICS + VPU_METRICS

# Lane width of the in-kernel 3-d pivot-bound broadcast, and the multiple a
# narrow l1/linf feature axis is padded to: (128, 128, CHUNK) f32 is ~1 MiB.
CHUNK = 16


def _check_feature_block(m: int, bm: int) -> None:
    """Mosaic tiles a block's last dim by 128 unless it spans the array."""
    assert m % bm == 0 and (bm == m or bm % 128 == 0), (m, bm)


def _accumulate(acc_ref, xc, yc, metric: str) -> None:
    """One feature block's contribution to the (bv, bw) distance accumulator
    (shared by the plain, filtered, compact and map kernels)."""
    if metric in VPU_METRICS:
        # One feature at a time on (bv, bw) tiles: an x column broadcast
        # along lanes against the matching row of y transposed — 2-d VPU
        # work only, no 3-d broadcast whose narrow lane dim Mosaic pads.
        yt = yc.T  # (bm, bw)
        acc = acc_ref[...]
        for k in range(xc.shape[1]):
            diff = jnp.abs(xc[:, k : k + 1] - yt[k : k + 1, :])
            # max-accumulation: init 0 is correct because |.| >= 0.
            acc = acc + diff if metric == "l1" else jnp.maximum(acc, diff)
        acc_ref[...] = acc
    elif metric == "l2":
        cross = jax.lax.dot_general(
            xc, yc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] += (
            (xc * xc).sum(1)[:, None] + (yc * yc).sum(1)[None, :] - 2.0 * cross
        )
    elif metric in ("cosine", "dot"):
        # cosine: ops.py pre-normalizes rows, so the dot accumulates cos-sim.
        acc_ref[...] += jax.lax.dot_general(
            xc, yc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:  # pragma: no cover — guarded by ops.py
        raise ValueError(metric)


def _pivot_bound(px_ref, py_ref):
    """(bv, bw) L-inf lower bound max_p |px_i[p] - py_j[p]| over the full
    mapped coordinates, in ``CHUNK`` slices (the pivot axis is small and
    never grid-chunked)."""
    pxc = px_ref[...].astype(jnp.float32)
    pyc = py_ref[...].astype(jnp.float32)
    bound = jnp.zeros((pxc.shape[0], pyc.shape[0]), jnp.float32)
    for c in range(0, pxc.shape[1], CHUNK):
        bound = jnp.maximum(
            bound, jnp.abs(pxc[:, None, c : c + CHUNK] - pyc[None, :, c : c + CHUNK]).max(-1)
        )
    return bound


def _finalize(acc, metric: str):
    if metric == "l2":
        return jnp.sqrt(jnp.maximum(acc, 0.0))
    if metric == "cosine":
        return 1.0 - acc
    return acc


def _kernel(
    x_ref,  # (bv, bm) VMEM
    y_ref,  # (bw, bm) VMEM
    out_ref,  # (bv, bw) VMEM — f32 distances or int8 mask
    acc_ref,  # (bv, bw) f32 VMEM scratch, persists across the nm grid axis
    *,
    metric: str,
    delta: float | None,
    nm: int,
):
    im = pl.program_id(2)

    @pl.when(im == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(acc_ref, x_ref[...].astype(jnp.float32),
                y_ref[...].astype(jnp.float32), metric)

    @pl.when(im == nm - 1)
    def _epilogue():
        acc = _finalize(acc_ref[...], metric)
        if delta is None:
            out_ref[...] = acc
        else:
            out_ref[...] = (acc <= delta).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("metric", "delta", "bv", "bw", "bm", "interpret"),
)
def pairdist_blocked(
    x: jnp.ndarray,  # (a, m) — a, m already padded to block multiples
    y: jnp.ndarray,  # (b, m)
    *,
    metric: str = "l2",
    delta: float | None = None,
    bv: int = 128,
    bw: int = 128,
    bm: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw blocked call. Use ``ops.pairdist`` / ``ops.pairdist_mask`` which
    handle padding, normalization and backend dispatch."""
    a, m = x.shape
    b, _ = y.shape
    bm = min(bm or 128, m)
    assert a % bv == 0 and b % bw == 0, (x.shape, y.shape, bv, bw)
    _check_feature_block(m, bm)
    nm = m // bm
    out_dtype = jnp.float32 if delta is None else jnp.int8

    grid = (a // bv, b // bw, nm)
    return pl.pallas_call(
        functools.partial(_kernel, metric=metric, delta=delta, nm=nm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bv, bm), lambda i, j, k: (i, k)),
            pl.BlockSpec((bw, bm), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bv, bw), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a, b), out_dtype),
        scratch_shapes=[pltpu.VMEM((bv, bw), jnp.float32)],
        interpret=interpret,
        name="pairdist_blocked",
    )(x, y)


# ---------------------------------------------------------------------------
# Fused pivot-filter + pairdist (the verify engine's prune="pivot" hot path)
# ---------------------------------------------------------------------------


def _filtered_kernel(
    x_ref,  # (bv, bm) VMEM — payload feature chunk
    y_ref,  # (bw, bm) VMEM
    px_ref,  # (bv, bp) VMEM — FULL mapped coordinates (anchor distances)
    py_ref,  # (bw, bp) VMEM
    out_ref,  # (bv, bw) int8 mask, or code with ``candidates``
    acc_ref,  # (bv, bw) f32 scratch — distance accumulator
    bound_ref,  # (bv, bw) f32 scratch — L-inf pivot lower bound
    *,
    metric: str,
    delta: float,
    delta_bound: float,
    nm: int,
    candidates: bool,
):
    im = pl.program_id(2)

    @pl.when(im == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # Computed once per (i, j) tile.
        bound_ref[...] = _pivot_bound(px_ref, py_ref)

    # Whole-tile skip: when the lower bound already exceeds delta for EVERY
    # pair in this (bv, bw) tile, the exact-distance accumulation (the MXU /
    # VPU hot loop) is skipped outright — this is where pruning buys compute,
    # not just a masked epilogue. acc stays at its zero init; the epilogue's
    # bound conjunct forces the mask to all-False regardless.
    @pl.when((bound_ref[...] <= delta_bound).any())
    def _live():
        _accumulate(acc_ref, x_ref[...].astype(jnp.float32),
                    y_ref[...].astype(jnp.float32), metric)

    @pl.when(im == nm - 1)
    def _epilogue():
        live = bound_ref[...] <= delta_bound
        hit = (_finalize(acc_ref[...], metric) <= delta) & live
        if candidates:
            out_ref[...] = (jnp.where(hit, 1, 0) + jnp.where(live, 2, 0)).astype(out_ref.dtype)
        else:
            out_ref[...] = hit.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "delta", "delta_bound", "candidates", "bv", "bw", "bm", "interpret",
    ),
)
def pairdist_filtered_blocked(
    x: jnp.ndarray,  # (a, m) — a, m already padded to block multiples
    y: jnp.ndarray,  # (b, m)
    px: jnp.ndarray,  # (a, bp) — mapped coords, bp padded to a CHUNK multiple
    py: jnp.ndarray,  # (b, bp)
    *,
    metric: str,
    delta: float,
    delta_bound: float,
    candidates: bool = False,
    bv: int = 128,
    bw: int = 128,
    bm: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw blocked fused filter+pairdist call. Use ``ops.pairdist_mask_filtered``
    which handles padding, normalization and backend dispatch.

    Semantics (validated against ``ref.pairdist_mask_filtered``): int8 mask,
    1 where D(x_i, y_j) <= delta AND max_p |px_i[p] - py_j[p]| <= delta_bound.
    ``candidates=True`` adds bit 1 where the bound alone holds (the pivot-
    filter survivors ``ops.verify_compact`` counts). Zero padding is exact on
    both the feature and the pivot axis (|0-0| = 0 contributes nothing to
    sum or max).
    """
    a, m = x.shape
    b, _ = y.shape
    bp = px.shape[1]
    bm = min(bm or 128, m)
    assert a % bv == 0 and b % bw == 0, (x.shape, y.shape, bv, bw)
    _check_feature_block(m, bm)
    assert px.shape == (a, bp) and py.shape == (b, bp) and bp % CHUNK == 0, (
        px.shape, py.shape, CHUNK,
    )
    nm = m // bm

    grid = (a // bv, b // bw, nm)
    return pl.pallas_call(
        functools.partial(
            _filtered_kernel, metric=metric, delta=delta,
            delta_bound=delta_bound, nm=nm, candidates=candidates,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bv, bm), lambda i, j, k: (i, k)),
            pl.BlockSpec((bw, bm), lambda i, j, k: (j, k)),
            pl.BlockSpec((bv, bp), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bw, bp), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bv, bw), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a, b), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((bv, bw), jnp.float32),
            pltpu.VMEM((bv, bw), jnp.float32),
        ],
        interpret=interpret,
        name="pairdist_filtered_blocked",
    )(x, y, px, py)
