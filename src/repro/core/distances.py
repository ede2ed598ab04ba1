"""Metric-space distance functions (paper Def. 1 / Def. 2).

Every metric is exposed in two forms:
  dist(x, y)        — single-pair distance, x/y: (m,)
  pairwise(X, Y)    — all-pairs matrix, X: (a, m), Y: (b, m) -> (a, b)

``pairwise`` here is the *reference* (pure jnp) implementation; the Pallas
verify kernel in ``repro.kernels`` computes the same quantity blocked/fused and
is validated against this module.

Supported metrics:
  l1        Σ|x−y|              (paper's running example, Example 1)
  l2        √Σ(x−y)²            (EUCLIDEAN; evaluated on Netflix/SIFT)
  linf      max|x−y|
  cosine    1 − x·y/(‖x‖‖y‖)    (pseudo-metric; common for embeddings — the
                                 semantic-dedup use case. Triangle inequality
                                 holds for the induced angular distance; we use
                                 the angular form when exactness matters.)
  angular   arccos(cos_sim)/π   (a true metric on the unit sphere)
  jaccard_minhash
            1 − mean(sig_x == sig_y) over MinHash signatures (unbiased
            estimator of Jaccard distance; §6.2 string/set support via
            ``repro.data.vectorize``)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray


def _l1_pairwise(x: Array, y: Array) -> Array:
    # (a, 1, m) - (1, b, m) -> (a, b). O(a·b·m) VPU work.
    return jnp.abs(x[:, None, :] - y[None, :, :]).sum(-1)


def _l2_pairwise(x: Array, y: Array) -> Array:
    # MXU-friendly form: ‖x‖² + ‖y‖² − 2 x·yᵀ. Clamped for fp error.
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    sq = (x * x).sum(-1)[:, None] + (y * y).sum(-1)[None, :] - 2.0 * x @ y.T
    return jnp.sqrt(jnp.maximum(sq, 0.0))


def _linf_pairwise(x: Array, y: Array) -> Array:
    return jnp.abs(x[:, None, :] - y[None, :, :]).max(-1)


def _cosine_pairwise(x: Array, y: Array) -> Array:
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    yn = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), 1e-12)
    return 1.0 - xn @ yn.T


def _angular_pairwise(x: Array, y: Array) -> Array:
    cos = 1.0 - _cosine_pairwise(x, y)
    return jnp.arccos(jnp.clip(cos, -1.0, 1.0)) / jnp.pi


def _jaccard_minhash_pairwise(x: Array, y: Array) -> Array:
    # x, y are integer MinHash signatures; distance = 1 − estimated Jaccard sim.
    eq = (x[:, None, :] == y[None, :, :]).astype(jnp.float32)
    return 1.0 - eq.mean(-1)


@dataclasses.dataclass(frozen=True)
class Metric:
    """A metric-space distance (Def. 1): the function plus metadata.

    ``mxu_friendly`` marks metrics whose pairwise form reduces to a matmul
    (the Pallas kernel routes those through the MXU path).
    """

    name: str
    pairwise: Callable[[Array, Array], Array]
    mxu_friendly: bool = False
    true_metric: bool = True
    # Equality-based metrics (MinHash) are only meaningful on the data's
    # integer support: model-GENERATED pivots must be rounded onto it, or
    # every distance degenerates to 1.0 (floats never collide).
    discrete: bool = False

    def dist(self, x: Array, y: Array) -> Array:
        return self.pairwise(x[None, :], y[None, :])[0, 0]


METRICS: dict[str, Metric] = {
    "l1": Metric("l1", _l1_pairwise),
    "l2": Metric("l2", _l2_pairwise, mxu_friendly=True),
    "linf": Metric("linf", _linf_pairwise),
    "cosine": Metric("cosine", _cosine_pairwise, mxu_friendly=True, true_metric=False),
    "angular": Metric("angular", _angular_pairwise, mxu_friendly=True),
    "jaccard_minhash": Metric("jaccard_minhash", _jaccard_minhash_pairwise, discrete=True),
}


def get_metric(name: str) -> Metric:
    try:
        return METRICS[name]
    except KeyError:
        raise ValueError(f"unknown metric {name!r}; have {sorted(METRICS)}") from None


def pairwise(x: Array, y: Array, metric: str = "l1") -> Array:
    """All-pairs distance matrix (reference implementation)."""
    return get_metric(metric).pairwise(x, y)


def brute_force_join(x: Array, *args, **kwargs) -> Array:
    """Oracle join — ground truth for tests/benchmarks (quadratic).

    Two call forms, overloaded on whether the second argument is a set:

      brute_force_join(x, delta[, metric])
          self-join: boolean (n, n) matrix, True where D(o_i, o_j) ≤ δ, i < j.
      brute_force_join(r, s, delta[, metric])
          cross R×S join: boolean (n_r, n_s) matrix, True where
          D(r_i, s_j) ≤ δ — no triangular de-dup, (i, j) index different sets.
    """
    y = kwargs.pop("s", None)
    delta = kwargs.pop("delta", None)
    metric = kwargs.pop("metric", None)
    if kwargs:
        raise TypeError(f"unexpected keyword arguments {sorted(kwargs)}")
    pos = list(args)
    # Cross form iff the second positional is a set — always (n, m); scalars
    # (and anything else) route to delta, so a stray 0-d array can't misroute.
    if pos and jnp.ndim(pos[0]) == 2:
        if y is not None:
            raise TypeError("brute_force_join got multiple values for s")
        y = pos.pop(0)
    if pos:
        if delta is not None:
            raise TypeError("brute_force_join got multiple values for delta")
        delta = pos.pop(0)
    if pos:
        if metric is not None:
            raise TypeError("brute_force_join got multiple values for metric")
        metric = pos.pop(0)
    if pos:
        raise TypeError("too many positional arguments")
    if delta is None:
        raise TypeError("brute_force_join requires a delta threshold")
    metric = metric or "l1"
    if y is None:
        d = pairwise(x, x, metric)
        n = x.shape[0]
        iu = jnp.triu_indices(n, k=1)
        mask = jnp.zeros((n, n), bool).at[iu].set(True)
        return (d <= delta) & mask
    if x.shape[0] == 0 or y.shape[0] == 0:
        return jnp.zeros((x.shape[0], y.shape[0]), bool)
    return pairwise(x, y, metric) <= delta


def _oracle_distance(x: Array, y: Array, metric: str) -> Array:
    """Block distances for the oracle. L2 is √Σ(x−y)², summed over the
    leading (feature) axis of the transposed blocks — not the kernels' dot
    expansion, so the oracle shares no rounding with the code it checks."""
    if metric == "l2":
        diff = x.astype(jnp.float32).T[:, :, None] - y.astype(jnp.float32).T[:, None, :]
        return jnp.sqrt((diff * diff).sum(0))
    return pairwise(x, y, metric)


def _first_hits(hit: Array, rows: Array, cols: Array, cap: int) -> tuple[Array, Array]:
    """The first ``cap`` hits of a bool block in row-major order as (cap, 2)
    (row, col) ids padded with -1, and the true hit count. The slot of the
    k-th hit is found by row (a search over the row-count prefix sum), then
    by 128-column chunk within that row, then by column within the chunk,
    each the first place where a running count passes the rank — a path
    that shares no code with the engine's compaction, and whose prefix
    sums are all short."""

    def first_past(counts, rank):  # (cap, k) counts: index, and rank left there
        end = jnp.cumsum(counts, axis=1)
        i = jnp.argmax(end > rank[:, None], axis=1)
        before = jnp.take_along_axis(end - counts, i[:, None], axis=1)[:, 0]
        return i, rank - before

    b = hit.shape[1]
    chunk = 128 if b % 128 == 0 else b
    row_cnt = hit.sum(1, dtype=jnp.int32)
    row_end = jnp.cumsum(row_cnt)
    q = jnp.arange(cap, dtype=jnp.int32)
    r = jnp.minimum(jnp.searchsorted(row_end, q, side="right"), hit.shape[0] - 1)
    chunks = hit[r].reshape(cap, b // chunk, chunk)
    c, rank = first_past(chunks.sum(2, dtype=jnp.int32), q - (row_end[r] - row_cnt[r]))
    inner = jnp.take_along_axis(chunks, c[:, None, None], axis=1)[:, 0]
    j, _ = first_past(inner.astype(jnp.int32), rank)
    pairs = jnp.stack([rows[r], cols[c * chunk + j]], 1).astype(jnp.int32)
    return jnp.where((q < row_end[-1])[:, None], pairs, -1), row_end[-1]


@functools.partial(jax.jit, static_argnames=("metric", "block", "cap", "self_join"))
def _oracle_row_block(x, y, r0, n_x, n_y, delta, *, metric, block, cap, self_join):
    """Hits of rows [r0, r0 + block) of ``x`` against every column block of
    ``y``: per column block, (cap, 2) global (row, col) pairs and the true
    hit count. Self-join blocks wholly below the diagonal are skipped."""
    xb = jax.lax.dynamic_slice_in_dim(x, r0, block)
    rows = r0 + jnp.arange(block)

    def col_block(c0):
        def hits(_):
            yb = jax.lax.dynamic_slice_in_dim(y, c0, block)
            cols = c0 + jnp.arange(block)
            hit = (_oracle_distance(xb, yb, metric) <= delta) & (
                (rows[:, None] < n_x) & (cols[None, :] < n_y)
            )
            if self_join:
                hit = hit & (cols[None, :] > rows[:, None])
            return _first_hits(hit, rows, cols, cap)

        if not self_join:
            return hits(None)
        return jax.lax.cond(
            c0 + block > r0, hits,
            lambda _: (jnp.full((cap, 2), -1, jnp.int32), jnp.zeros((), jnp.int32)),
            None,
        )

    return jax.lax.map(col_block, jnp.arange(0, y.shape[0], block))


def oracle_pairs(
    x: Array | np.ndarray,
    delta: float,
    metric: str = "l1",
    y: Array | np.ndarray | None = None,
    *,
    block: int = 4096,
) -> np.ndarray:
    """Ground-truth join pairs, computed on the device in row × column
    blocks, so its memory is O(block²) and it can check real sizes.

    ``y=None``: self-join pairs (i, j), i < j, with D(x_i, x_j) ≤ δ. With
    ``y``: cross pairs (i ∈ x, j ∈ y). Returns (n_pairs, 2) int64 in
    row-major order — the ``np.nonzero`` order of :func:`brute_force_join`'s
    dense matrix. Only the hits of a block come back to the host.
    """
    self_join = y is None
    x_np = np.asarray(x)
    y_np = x_np if self_join else np.asarray(y)
    n_x, n_y = x_np.shape[0], y_np.shape[0]
    if n_x == 0 or n_y == 0:
        return np.zeros((0, 2), np.int64)
    block = min(block, -(-max(n_x, n_y) // 8) * 8)

    def put(a):
        return jax.device_put(np.pad(a, ((0, (-a.shape[0]) % block), (0, 0))))

    x_dev = put(x_np)
    y_dev = x_dev if self_join else put(y_np)
    cap = max(block // 16, 8)
    out = []
    for r0 in range(0, n_x, block):
        while True:
            pairs, counts = jax.device_get(_oracle_row_block(
                x_dev, y_dev, r0, n_x, n_y, np.float32(delta),
                metric=metric, block=block, cap=cap, self_join=self_join,
            ))
            if counts.max() <= cap:
                break
            cap = 1 << (int(counts.max()) - 1).bit_length()  # overflow: exact retry
        out += [pr[:c] for pr, c in zip(pairs, counts) if c]
    if not out:
        return np.zeros((0, 2), np.int64)
    return np.unique(np.concatenate(out).astype(np.int64), axis=0)
