"""Partitioning invariants (Lemma 3/4) + end-to-end join exactness —
the system's central property, swept with hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import baselines, distances, mapping, partition, spjoin
from repro.data import dedup as dedup_lib


def _plan(rng, k=64, p=7, n=3, delta=1.5, strategy="iterative"):
    pivots = rng.normal(size=(k, 4)).astype(np.float32)
    smap = mapping.select_anchors(jax.random.PRNGKey(0), jnp.asarray(pivots), n, "l1")
    mapped = np.asarray(smap(jnp.asarray(pivots)))
    labels = partition.single_linkage_labels(
        np.asarray(distances.pairwise(jnp.asarray(pivots), jnp.asarray(pivots), "l1")), 8
    ) if strategy == "learning" else None
    return partition.build_partition(mapped, p, delta, strategy, labels), smap


def test_kernel_cells_tile_space(rng):
    """Lemma 3 (1): every point belongs to exactly ONE kernel cell."""
    plan, smap = _plan(rng)
    x = jnp.asarray(rng.normal(scale=3.0, size=(500, 4)), jnp.float32)
    xm = smap(x)
    inside = (np.asarray(xm)[:, None, :] >= np.asarray(plan.kernel_lo)[None]) & (
        np.asarray(xm)[:, None, :] < np.asarray(plan.kernel_hi)[None]
    )
    counts = inside.all(-1).sum(1)
    assert (counts == 1).all(), np.unique(counts)


def test_whole_contains_kernel(rng):
    plan, smap = _plan(rng)
    assert (np.asarray(plan.whole_lo) <= np.asarray(plan.kernel_lo)).all()
    assert (np.asarray(plan.whole_hi) >= np.asarray(plan.kernel_hi)).all()


def test_iterative_balances_kernel_sizes(rng):
    pivots = rng.normal(size=(512, 4)).astype(np.float32)
    smap = mapping.select_anchors(jax.random.PRNGKey(0), jnp.asarray(pivots), 4, "l1")
    mapped = np.asarray(smap(jnp.asarray(pivots)))
    plan = partition.build_partition(mapped, 8, 0.5, "iterative")
    cells = np.asarray(partition.assign_kernel(plan, jnp.asarray(mapped)))
    sizes = np.bincount(cells, minlength=8)
    assert sizes.max() <= 2 * sizes.min() + 8, sizes  # equi-depth splits


def test_fft_anchors_distinct_under_duplicate_pivots(rng):
    """Duplicate pivots (generative pivots on near-discrete data) must not
    collapse target-space dimensions: with enough distinct values the FFT
    anchors are all distinct; with fewer than n the distinct set is exhausted
    first and the residual falls back to random fill (no crash, no row-0
    collapse)."""
    base = rng.normal(size=(4, 3)).astype(np.float32)
    pivots = jnp.asarray(np.repeat(base, 8, axis=0))  # 32 rows, 4 distinct
    smap = mapping.select_anchors(jax.random.PRNGKey(0), pivots, 4, "l1")
    assert np.unique(np.asarray(smap.anchors), axis=0).shape[0] == 4
    smap6 = mapping.select_anchors(jax.random.PRNGKey(0), pivots, 6, "l1")
    a6 = np.asarray(smap6.anchors)
    assert a6.shape == (6, 3)
    assert np.unique(a6, axis=0).shape[0] == 4  # every distinct value chosen


def test_fft_anchors_pseudo_metric_zero_distance_twins(rng):
    """Scaled copies are value-distinct but angular-distance 0: the distinct
    count must be metric-aware, so the residual falls back to random fill
    instead of silently collapsing every mapped dimension."""
    v = rng.normal(size=(1, 3)).astype(np.float32)
    pivots = jnp.asarray(np.concatenate([v * c for c in (1.0, 2.0, 3.0, 4.0)]))
    smap = mapping.select_anchors(jax.random.PRNGKey(0), pivots, 3, "angular")
    assert np.asarray(smap.anchors).shape == (3, 3)  # no crash, full shape


def test_mapping_is_lipschitz(rng):
    """|o^n_x[i] - o^n_y[i]| <= D(x, y) — the Lemma 4 precondition."""
    x = jnp.asarray(rng.normal(size=(50, 6)), jnp.float32)
    piv = jnp.asarray(rng.normal(size=(16, 6)), jnp.float32)
    smap = mapping.select_anchors(jax.random.PRNGKey(0), piv, 5, "l1")
    xm = np.asarray(smap(x))
    d = np.asarray(distances.pairwise(x, x, "l1"))
    for i in range(10):
        for j in range(10):
            assert (np.abs(xm[i] - xm[j]) <= d[i, j] + 1e-4).all()


@pytest.mark.slow  # long property sweep (~30s): nightly tier; the fast tier
# covers the same invariant via tests/test_verify_engine.py parity tests
@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    metric=st.sampled_from(["l1", "l2", "linf"]),
    sampler=st.sampled_from(["random", "distribution", "generative"]),
    partitioner=st.sampled_from(["iterative", "learning"]),
    delta_q=st.floats(0.005, 0.05),
)
def test_join_equals_brute_force(seed, metric, sampler, partitioner, delta_q):
    """THE paper invariant: SP-Join output == brute-force join, for any
    sampler/partitioner/metric/threshold.

    δ sits in the middle of the gap between two consecutive pair distances
    nearest the ``delta_q`` quantile whose width is several times the f32
    rounding of the engine's distances (measured against f64), so no pair
    lies within rounding of δ: there the kernels' dot expansion and the
    oracle's Σ(x−y)² may round apart (ROADMAP B1)."""
    rng = np.random.default_rng(seed)
    data = np.concatenate([
        rng.normal(loc=c, scale=1.0, size=(120, 5)) for c in (0.0, 4.0, 9.0)
    ]).astype(np.float32)
    d = np.asarray(distances.pairwise(jnp.asarray(data), jnp.asarray(data), metric))
    iu = np.triu_indices(len(data), 1)
    d64 = data.astype(np.float64)
    diff = np.abs(d64[:, None, :] - d64[None, :, :])
    exact = {"l1": diff.sum(-1), "l2": np.sqrt((diff * diff).sum(-1)), "linf": diff.max(-1)}
    guard = 4.0 * float(np.abs(d - exact[metric])[iu].max()) + 1e-6
    ds = np.sort(d[iu])
    k = int(delta_q * (len(ds) - 1))
    wide = np.flatnonzero(np.diff(ds) > 2.0 * guard)
    assert wide.size, "no pair-distance gap wider than f32 rounding"
    g = wide[np.argmin(np.abs(wide - k))]
    delta = float((ds[g] + ds[g + 1]) / 2.0)
    cfg = spjoin.JoinConfig(
        delta=delta, metric=metric, sampler=sampler, partitioner=partitioner,
        k=96, p=6, n_dims=3, seed=seed,
    )
    res = spjoin.join(data, cfg)
    truth = spjoin.brute_force_pairs(data, delta, metric)
    assert np.array_equal(res.pairs, truth), (res.pairs.shape, truth.shape)


def test_join_on_minhash_metric(rng):
    sigs = rng.integers(0, 50, size=(150, 32)).astype(np.float32)
    cfg = spjoin.JoinConfig(delta=0.5, metric="jaccard_minhash", k=64, p=4, n_dims=3)
    res = spjoin.join(sigs, cfg)
    truth = spjoin.brute_force_pairs(sigs, 0.5, "jaccard_minhash")
    assert np.array_equal(res.pairs, truth)


@pytest.mark.parametrize("n,block", [(203, 32), (600, 256)])
@pytest.mark.parametrize("cross", [False, True])
@pytest.mark.parametrize("metric", ["l1", "l2", "linf", "jaccard_minhash"])
def test_blocked_oracle_matches_dense(rng, metric, cross, n, block):
    """The blocked device oracle equals the dense matrix oracle with blocks
    smaller than n: several row and column blocks, the self-join diagonal,
    rows spanning several 128-column chunks, and the overflow retry (far
    more hits per block than the first capacity). Integer data keeps both
    L2 forms exact."""
    x = rng.integers(0, 5, size=(n, 6)).astype(np.float32)
    y = rng.integers(0, 5, size=(77, 6)).astype(np.float32) if cross else None
    d = np.asarray(distances.pairwise(jnp.asarray(x), jnp.asarray(x), metric))
    delta = float(np.quantile(d, 0.2))
    dense = np.asarray(
        distances.brute_force_join(x, y, delta, metric)
        if cross
        else distances.brute_force_join(x, delta, metric)
    )
    got = distances.oracle_pairs(x, delta, metric, y, block=block)
    assert got.shape[0] > 32
    np.testing.assert_array_equal(got, np.argwhere(dense))


def test_tighten_preserves_exactness(rng):
    data = rng.normal(size=(300, 4)).astype(np.float32)
    for tighten in (False, True):
        cfg = spjoin.JoinConfig(delta=1.0, metric="l2", k=64, p=8, n_dims=3,
                                tighten=tighten)
        res = spjoin.join(data, cfg)
        truth = spjoin.brute_force_pairs(data, 1.0, "l2")
        assert np.array_equal(res.pairs, truth)


def test_tighten_reduces_verifications(rng):
    data = np.concatenate([
        rng.normal(loc=c, scale=0.5, size=(250, 4)) for c in (0, 6, 12, 18)
    ]).astype(np.float32)
    r_loose = spjoin.join(data, spjoin.JoinConfig(delta=1.0, metric="l1", k=128,
                                                  p=8, n_dims=4, tighten=False))
    r_tight = spjoin.join(data, spjoin.JoinConfig(delta=1.0, metric="l1", k=128,
                                                  p=8, n_dims=4, tighten=True))
    assert r_tight.n_verifications <= r_loose.n_verifications


def test_ball_join_baseline_exact(rng):
    data = rng.normal(size=(250, 5)).astype(np.float32)
    res = baselines.ball_join(data, 1.2, "l2", n_pivots=10)
    truth = spjoin.brute_force_pairs(data, 1.2, "l2")
    assert np.array_equal(res.pairs, truth)


def test_dedup_removes_near_duplicates(rng):
    base = rng.normal(size=(60, 8)).astype(np.float32)
    dups = base[:20] + rng.normal(scale=1e-3, size=(20, 8)).astype(np.float32)
    data = np.concatenate([base, dups])
    res = dedup_lib.dedup(data, delta=0.05, metric="l2")
    assert res.n_duplicates == 20, res.n_duplicates
    # representatives keep one copy of each duplicated row
    kept = data[res.keep_mask]
    assert kept.shape[0] == 60


def test_cost_model_lower_bound(rng):
    from repro.core import cost_model
    v = rng.integers(1, 100, size=16)
    w = v + rng.integers(0, 50, size=16)
    c = cost_model.partition_cost(v, w)
    assert c.inner >= cost_model.lower_bound_inner(int(v.sum()), 16) - 1e-6
    assert c.total == pytest.approx(c.inner + c.outer)
