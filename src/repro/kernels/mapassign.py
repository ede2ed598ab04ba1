"""Pallas TPU kernel: fused map phase (space map + kernel assign + membership).

The map phase of SP-Join (paper §5.2, Lemma 4) takes every object o to its
pivot-space coordinates oⁿ = (D(a_1,o) … D(a_n,o)), finds the unique KERNEL
cell whose half-open box contains oⁿ, and computes the WHOLE-partition
membership mask over the δ-expanded (closed) boxes. Done naively that is a
pairdist pass plus TWO (N, p, n) containment broadcasts and an (N, p) bool
mask — all round-tripping HBM, per shard, twice per join (counting pass +
verify pass).

This kernel fuses all three into one streamed pass:

  * Grid (n_tiles,): per row block the (bn, n) coordinate tile is computed
    in VMEM from the row block and the (small, fully resident) anchor set —
    the same feature-chunked MXU/VPU accumulation as ``pairdist.py``
    (``_accumulate``/``_finalize`` are shared verbatim) — and written out
    once. The (small, fully resident) box tables are then walked in static
    blocks of bp partitions; the (bn, bp, n) containment broadcasts live and
    die in VMEM.
  * KERNEL cell id: boxes are half-open [lo, hi) and tile ℝⁿ, so at most one
    matches; a running "first containing box" value reproduces the jnp
    path's argmax-of-bool semantics exactly (first match wins, no match → 0).
  * WHOLE membership is packed 32 partitions per word in-register (int32
    arithmetic, which Mosaic reduces; bitcast to the uint32 words outside),
    so the per-shard mask costs N·⌈p/32⌉ words of HBM instead of N·p bools.

HBM traffic: N·(n + 1 + ⌈p/32⌉) words written, zero (N, p, n) or (N, p)
intermediates — vs 2·N·p·n + N·p bool bytes for the two-pass jnp path.

Correctness contract (validated in tests/test_map_phase.py against
``ref.map_assign``): callers (``ops.py``) pre-pad rows/features/partitions;
padded feature columns are zero (exact for every metric after cosine
pre-normalization), padded anchor DIMENSIONS carry (-BIG, BIG) box edges so
they never veto containment, and padded PARTITIONS carry lo = +BIG so they
never match. Half-open vs closed edges (kernel: ``< hi``; whole: ``<= hi``)
are the correctness hazard and are kept bit-exact with the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pairdist import _accumulate, _check_feature_block, _finalize
from repro.kernels.ref import BIG, MEMBER_WORD as WORD  # single-owner constants


def _kernel(
    x_ref,  # (bn, m) VMEM — payload rows (or mapped coords when metric None)
    a_ref,  # (na, m) VMEM — all anchors (tiny; fully resident)
    klo_ref,  # (pp, na) VMEM — kernel box lows, every partition
    khi_ref,  # (pp, na)
    wlo_ref,  # (pp, na) — whole (δ-expanded) box lows
    whi_ref,  # (pp, na)
    xm_ref,  # (bn, na) f32 OUT — mapped coordinates
    cell_ref,  # (bn, 1) int32 OUT — kernel cell id
    bits_ref,  # (bn, pp // WORD) int32 OUT — packed whole membership
    *,
    metric: str | None,
    bm: int,
    npb: int,
    bp: int,
    want_cells: bool,
    want_member: bool,
):
    # Fused pairdist tile: row block × ALL anchors, feature-chunked with the
    # verify kernel's accumulation (xm_ref doubles as the accumulator).
    if metric is None:
        xm_ref[...] = x_ref[...].astype(jnp.float32)
    else:
        xm_ref[...] = jnp.zeros_like(xm_ref)
        for c0 in range(0, x_ref.shape[1], bm):
            _accumulate(
                xm_ref,
                x_ref[:, c0 : c0 + bm].astype(jnp.float32),
                a_ref[:, c0 : c0 + bm].astype(jnp.float32),
                metric,
            )
        xm_ref[...] = _finalize(xm_ref[...], metric)
    xm = xm_ref[...]  # (bn, na)

    # Partitions are walked in static blocks of bp so the (bn, bp, na)
    # containment broadcasts stay small and never leave VMEM. Kernel boxes
    # are half-open, whole boxes closed. A skipped side (want_cells /
    # want_member False) costs nothing and its output is zero-filled.
    cell = jnp.full((xm.shape[0], 1), -1, jnp.int32)  # first containing box
    shift = jax.lax.broadcasted_iota(jnp.int32, (1, WORD), 1)
    for j in range(npb):
        rows = slice(j * bp, (j + 1) * bp)
        if want_cells:
            in_k = (
                (xm[:, None, :] >= klo_ref[rows, :][None])
                & (xm[:, None, :] < khi_ref[rows, :][None])
            ).all(-1)  # (bn, bp)
            # First containing box within this block; first block to match
            # wins — exactly argmax-of-bool over the full p axis (all-False
            # rows → 0).
            col = jax.lax.broadcasted_iota(jnp.int32, in_k.shape, 1)
            local = jnp.min(jnp.where(in_k, col, bp), axis=1, keepdims=True)
            cell = jnp.where((cell < 0) & (local < bp), j * bp + local, cell)
        if want_member:
            in_w = (
                (xm[:, None, :] >= wlo_ref[rows, :][None])
                & (xm[:, None, :] <= whi_ref[rows, :][None])
            ).all(-1)
            # Pack WORD partitions per int32 word (disjoint bits, so the
            # wrapping sum is the bitwise or; bit 31 lands on the sign).
            for w in range(bp // WORD):
                sel = in_w[:, w * WORD : (w + 1) * WORD].astype(jnp.int32)
                word = j * (bp // WORD) + w
                bits_ref[:, word : word + 1] = (sel << shift).sum(-1, keepdims=True)
    cell_ref[...] = jnp.maximum(cell, 0)
    if not want_member:
        bits_ref[...] = jnp.zeros_like(bits_ref)


@functools.partial(
    jax.jit,
    static_argnames=("metric", "bn", "bp", "bm", "interpret", "want_cells", "want_member"),
)
def map_assign_blocked(
    x: jnp.ndarray,  # (n, m) — n, m pre-padded to block multiples
    anchors: jnp.ndarray,  # (na, m) — na pre-padded; ignored when metric None
    kernel_lo: jnp.ndarray,  # (pp, na) — pp pre-padded to a bp multiple
    kernel_hi: jnp.ndarray,
    whole_lo: jnp.ndarray,
    whole_hi: jnp.ndarray,
    *,
    metric: str | None,
    bn: int = 128,
    bp: int = 128,
    bm: int | None = None,
    interpret: bool = False,
    want_cells: bool = True,
    want_member: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Raw blocked call — use ``ops.map_assign`` / ``ops.assign_membership``,
    which handle padding, normalization and backend dispatch.

    ``metric=None`` skips the space map: ``x`` then IS the (n, na) mapped
    coordinate matrix (assign-only mode). ``want_cells`` / ``want_member``
    skip the respective containment sweep (the skipped output is
    zero-filled) — what ``tighten``-style callers use to avoid paying for a
    side they recompute anyway. Returns (xm, cells, bits) with xm (n, na)
    f32, cells (n, 1) int32, bits (n, pp // WORD) uint32.
    """
    n, m = x.shape
    na = kernel_lo.shape[1]
    pp = kernel_lo.shape[0]
    bm = min(bm or 128, m)
    assert n % bn == 0 and pp % bp == 0 and bp % WORD == 0, (
        x.shape, kernel_lo.shape, bn, bp,
    )
    if metric is not None:
        _check_feature_block(m, bm)
    assert anchors.shape == (na, m) or metric is None, (anchors.shape, na, m)

    # One grid step per row block; every output block spans its array's
    # full last dim, which is what Mosaic's (8, 128) tiling accepts for
    # narrow outputs.
    xm, cells, bits = pl.pallas_call(
        functools.partial(
            _kernel, metric=metric, bm=bm, npb=pp // bp, bp=bp,
            want_cells=want_cells, want_member=want_member,
        ),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, m), lambda i: (i, 0)),
            pl.BlockSpec((na, m), lambda i: (0, 0)),
            pl.BlockSpec((pp, na), lambda i: (0, 0)),
            pl.BlockSpec((pp, na), lambda i: (0, 0)),
            pl.BlockSpec((pp, na), lambda i: (0, 0)),
            pl.BlockSpec((pp, na), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, na), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, pp // WORD), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, na), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, pp // WORD), jnp.int32),
        ],
        interpret=interpret,
        name="map_assign_blocked",
    )(x, anchors, kernel_lo, kernel_hi, whole_lo, whole_hi)
    return xm, cells, jax.lax.bitcast_convert_type(bits, jnp.uint32)
