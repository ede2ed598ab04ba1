"""Pallas TPU kernel: fused per-dimension histogram (the GoF cell counts).

The sampling-phase stats pass needs, per shard, the observed count nu_j of
every (dimension, cell) pair under the CDF transform u = F(x) in [0,1)
(paper Eq. 9 with equal-probability cells). Done naively this is a one-hot of
shape (n, m, t) — n x t times the input size in HBM traffic. The kernel fuses
binning + accumulation so only the (m, t) count matrix is ever written.

Grid (m_tiles, n_tiles), n innermost: the output tile accumulates in place
across n-chunks (sequential innermost grid on TPU). It is kept transposed,
(t, bmm), so each cell's counts are one sublane reduction written to one
row, with the feature axis on lanes; the wrapper transposes the small (t, m)
result. Cells are compared, not gathered — gather-free, VPU-only.

Weights (the padding/validity mask of static-shape distributed buffers) ride
along as a second input so masked counts need no second pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(u_ref, w_ref, out_ref, *, t: int):
    i_n = pl.program_id(1)

    @pl.when(i_n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...].astype(jnp.float32)  # (bn, bmm)
    w = w_ref[...].astype(jnp.float32)  # (bn, 1)
    cell = jnp.clip((u * t).astype(jnp.int32), 0, t - 1)  # (bn, bmm)
    # One row of the transposed (t, bmm) count block per cell: a compare
    # and a sublane reduction, no (bn, bmm, t) one-hot.
    for c in range(t):
        out_ref[c : c + 1, :] += jnp.where(cell == c, w, 0.0).sum(0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("t", "bn", "bmm", "interpret"))
def histogram_blocked(
    u: jnp.ndarray,  # (n, m) in [0, 1) — ragged shapes padded internally
    weights: jnp.ndarray,  # (n, 1) validity mask (0 for padding rows)
    *,
    t: int,
    bn: int = 256,
    bmm: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns the (m, t) count matrix. Ragged n/m are handled here: padding
    rows ride the existing weights column with weight 0 (no contribution) and
    padding dimensions land in extra output columns that are sliced off — so
    callers never pre-pad. ``bmm`` must be a multiple of 128 (Mosaic's lane
    tiling); narrower inputs take one block of the full width."""
    n, m = u.shape
    if n == 0 or m == 0:
        return jnp.zeros((m, t), jnp.float32)
    assert bmm % 128 == 0, bmm
    bn = min(bn, -(-n // 8) * 8)
    bmm = min(bmm, m)
    pad_n = (-n) % bn
    pad_m = (-m) % bmm
    if pad_n or pad_m:
        u = jnp.pad(u, ((0, pad_n), (0, pad_m)))
        weights = jnp.pad(weights, ((0, pad_n), (0, 0)))
    np_, mp = u.shape
    out = pl.pallas_call(
        functools.partial(_kernel, t=t),
        grid=(mp // bmm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bn, bmm), lambda j, i: (i, j)),
            pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t, bmm), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((t, mp), jnp.float32),
        interpret=interpret,
        name="histogram_blocked",
    )(u, weights)
    return out[:, :m].T
