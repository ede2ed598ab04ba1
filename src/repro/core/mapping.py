"""Space mapping: metric space → ℝⁿ via anchor pivots (paper §5.2).

A set of n *dimensional pivots* A = {a_1..a_n} maps an object o to

    oⁿ = ( D(a_1, o), D(a_2, o), ..., D(a_n, o) )

Triangle inequality gives |oⁿ_x[i] − oⁿ_y[i]| ≤ D(o_x, o_y) for every i
(each coordinate is 1-Lipschitz), which is exactly what Lemma 4 needs: a pair
within δ in the *origin* space lands within an L∞ ball of radius δ in the
*target* space, so δ-expanded boxes are a correct (complete) filter.

Anchor selection: the paper samples A randomly from the pivots; we default to
a farthest-first traversal (greedy k-center) over the pivots, which spreads
anchors and strictly improves the filter's discrimination (beyond-paper
optimization, flagged in EXPERIMENTS.md §Perf); ``method="random"`` recovers
the paper's choice.

The map itself is the first compute hot-spot of the map phase — a (N × n)
pairwise-distance evaluation — and routes through the same Pallas kernel as
the verify phase on TPU.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distances

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class SpaceMap:
    """Frozen mapping: anchors (n, m) + metric name."""

    anchors: Array
    metric: str = "l1"

    @property
    def n_dims(self) -> int:
        return self.anchors.shape[0]

    def __call__(self, x: Array) -> Array:
        """(N, m) objects → (N, n) target-space coordinates."""
        return distances.pairwise(x, self.anchors, self.metric)


@functools.partial(jax.jit, static_argnames=("n",))
def _farthest_first(d: Array, first: Array, n: int) -> Array:
    """The ``n`` pivot indices of a farthest-first traversal over the
    (k, k) distances ``d`` from pivot ``first``; compiled once per shape,
    so repeated selections reuse the program."""

    def body(carry, _):
        chosen_mask, min_dist = carry
        # Next anchor: farthest pivot from the chosen set.
        nxt = jnp.argmax(jnp.where(chosen_mask, -jnp.inf, min_dist))
        chosen_mask = chosen_mask.at[nxt].set(True)
        min_dist = jnp.minimum(min_dist, d[nxt])
        return (chosen_mask, min_dist), nxt

    mask0 = jnp.zeros((d.shape[0],), bool).at[first].set(True)
    (_, _), rest = jax.lax.scan(body, (mask0, d[first]), None, length=n - 1)
    return jnp.concatenate([first[None], rest])


def select_anchors(
    key: jax.Array,
    pivots: Array,
    n: int,
    metric: str = "l1",
    method: str = "fft",
) -> SpaceMap:
    """Choose n anchors from the sampled pivots.

    method="fft"    — farthest-first traversal (greedy k-center, default)
    method="random" — uniform choice (the paper's A ⊂ S)

    Duplicate pivots (common for generative pivots on near-discrete data)
    cannot trap the traversal: while any unchosen row at positive distance
    from the chosen set remains, argmax never lands on a zero-distance twin
    of a chosen anchor. "Distinct" is counted in METRIC space, not by value:
    rows at distance 0 under a pseudo-metric (e.g. scaled copies under
    angular) collapse mapped dimensions just like value repeats do. Only
    when fewer than n metric-distinct rows exist does the traversal run dry
    — the residual anchors then fall back to method="random" fill over the
    pivots (every leftover row is a zero-distance twin of a chosen anchor
    anyway), instead of silently collapsing onto copies of row 0.
    """
    k = pivots.shape[0]
    if n > k:
        raise ValueError(f"need n={n} anchors from only k={k} pivots")
    if method == "random":
        idx = jax.random.choice(key, k, shape=(n,), replace=False)
        return SpaceMap(pivots[idx], metric)
    if method != "fft":
        raise ValueError(f"unknown anchor method {method!r}")

    d = distances.pairwise(pivots, pivots, metric)  # (k, k)
    # Metric-distinct count: a row is a twin if some earlier row sits at
    # ~zero distance (value duplicates give exactly 0; pseudo-metric
    # collisions give 0 up to fp noise — arccos is ill-conditioned near 1,
    # hence the absolute tolerance). Zero-distance is transitive under the
    # triangle inequality, so "no earlier twin" counts equivalence classes.
    d_np = np.asarray(d)
    twin = np.tril(d_np <= 1e-4, -1).any(1)
    piv_np = np.asarray(pivots)
    _, first, inv = np.unique(piv_np, axis=0, return_index=True, return_inverse=True)
    twin |= first[inv] < np.arange(k)  # value repeats (exact, any metric)
    n_distinct = int(k - twin.sum())
    n_fft = min(n, n_distinct)
    first = jax.random.randint(key, (), 0, k)
    idx = _farthest_first(d, first, n_fft)
    if n_fft < n:
        fill = jax.random.choice(
            jax.random.fold_in(key, 1), k, shape=(n - n_fft,), replace=False
        )
        idx = jnp.concatenate([idx, fill])
    return SpaceMap(pivots[idx], metric)


def map_shards(space_map: SpaceMap, shards: list[Array]) -> list[Array]:
    """Map a list of host shards (reference executor convenience)."""
    return [space_map(s) for s in shards]


def as_numpy(space_map: SpaceMap) -> "SpaceMap":
    return SpaceMap(np.asarray(space_map.anchors), space_map.metric)
