"""Fused reduce phase with on-device pair compaction.

ONE jitted call per tile bucket does the pivot-filter pre-mask, the exact
pairwise distance, the ``<= delta`` test, padding validity + the min-cell
de-dup rule, and a prefix-sum compaction that gathers surviving
``(v_id, w_id)`` pairs into a fixed-capacity output buffer. What leaves the
device is output-sensitive — O(capacity) ids plus two counters — instead of
the O(tile_v · tile_w) hit mask the host would otherwise round-trip through
``np.asarray`` / ``np.nonzero`` per tile.

The work is split between a Pallas kernel and XLA, both on the device and
inside the same jitted call:

  * The distance kernels of ``pairdist.py`` write one int8 per pair. Pruned,
    ``pairdist_filtered_blocked(candidates=True)`` sets bit 0 for a hit that
    survives the pivot bound and bit 1 for a pair the bound keeps, and skips
    the MXU/VPU accumulation of a block whose every pair the bound prunes —
    the on-accelerator analogue of the streaming engine's tile skip.
    Unpruned, ``pairdist_blocked`` writes the hit mask.
  * XLA then applies validity and the min-cell de-dup (``ref.emit_mask``),
    compacts the hits with ``ref.compact_mask`` (flat prefix sum +
    searchsorted) and counts the candidates. Mosaic has no lowering for
    ``cumsum`` or for a value-level scatter, so the rank and the gather
    cannot live in the kernel; the int8 matrix is the one O(tile)
    intermediate, and it stays in HBM.

Emission order is therefore row-major, the same as ``ref.verify_compact``;
a count above ``capacity`` is the overflow sentinel the engine retries on.

Correctness contract (validated against ``ref.verify_compact`` in
tests/test_reduce_fused.py): rows are zero-padded to block multiples by
``ops.py`` with id/wcell padding = -1, so padded rows fail the validity
mask and can never be emitted; zero feature/pivot padding is exact for
every metric (|0-0| contributes nothing to sum or max).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.pairdist import pairdist_blocked, pairdist_filtered_blocked


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "delta", "delta_bound", "capacity", "cross",
        "bv", "bw", "bm", "interpret",
    ),
)
def verify_compact_blocked(
    x: jnp.ndarray,  # (a, m) — a, m already padded to block multiples
    y: jnp.ndarray,  # (b, m)
    vids: jnp.ndarray,  # (a, 1) int32, padding = -1
    wids: jnp.ndarray,  # (b, 1) int32, padding = -1
    wcells: jnp.ndarray,  # (b, 1) int32, padding = -1
    cell_id: jnp.ndarray,  # (1, 1) int32 — traced, NOT static (no recompiles
    #   per cell: the engine sweeps thousands of cells through one executable)
    px: jnp.ndarray | None = None,  # (a, bp) mapped coords, bp % CHUNK == 0
    py: jnp.ndarray | None = None,  # (b, bp)
    *,
    metric: str,
    delta: float,
    capacity: int,
    delta_bound: float | None = None,
    cross: bool = False,
    bv: int = 128,
    bw: int = 128,
    bm: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Raw blocked fused verify+compact call. Use ``ops.verify_compact``
    which handles padding, normalization and backend dispatch.

    Returns ``(pairs (capacity, 2) int32, counts (1, 2) int32)`` with
    ``counts[0, 0]`` the TRUE hit total (> capacity == overflow; the buffer
    then holds the first ``capacity`` hits) and ``counts[0, 1]`` the
    pivot-filter candidate count (valid pair count when unpruned) —
    semantics and row-major order of ``ref.verify_compact``.
    """
    a, b = x.shape[0], y.shape[0]
    assert vids.shape == (a, 1) and wids.shape == (b, 1) and wcells.shape == (b, 1)
    blocks = dict(bv=bv, bw=bw, bm=bm, interpret=interpret)
    vid, wid = vids[:, 0], wids[:, 0]
    valid = (vid[:, None] >= 0) & (wid[None, :] >= 0)
    if px is not None:
        code = pairdist_filtered_blocked(
            x, y, px, py, metric=metric, delta=float(delta),
            delta_bound=float(delta_bound), candidates=True, **blocks,
        ).astype(jnp.int32)
        hit = (code & 1) == 1
        n_cand = (((code >> 1) == 1) & valid).sum()
    else:
        hit = pairdist_blocked(x, y, metric=metric, delta=float(delta), **blocks) == 1
        n_cand = valid.sum()
    hit = hit & ref.emit_mask(vid, wid, wcells[:, 0], cell_id[0, 0], cross)
    pairs, count = ref.compact_mask(hit, vid, wid, capacity)
    return pairs, jnp.stack([count, n_cand.astype(jnp.int32)]).reshape(1, 2)
