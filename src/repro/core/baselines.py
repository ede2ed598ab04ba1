"""Baseline distributed-join strategies the paper compares against (§7.3).

The paper's four baselines are Spark systems; what distinguishes them
algorithmically is (a) random pivot sampling and (b) their partitioning rule.
We reproduce the *algorithmic cores* so Fig. 9's comparison is apples-to-
apples inside one executor:

  ball_join        MRSimJoin/ClusterJoin-style generalized-hyperplane (Voronoi)
                   partitioning with the 2-delta window replication rule.
                   KERNEL cell = nearest pivot; WHOLE membership of cell h =
                   D(o, p_h) <= D(o, p_nearest) + 2*delta  (complete by the
                   triangle inequality — proof in the module test).
  kpm_join         KPM (Chen et al. 2017): random sampling + KD-style
                   equi-depth space splitting. Exactly this framework's
                   Random + Iter arm — we expose a config alias rather than
                   duplicate code (spjoin.JoinConfig(sampler="random",
                   partitioner="iterative", anchor_method="random",
                   tighten=False)).

Both emit the same JoinResult as repro.core.spjoin.join, so every benchmark
metric (verifications, balance std, cost model) is directly comparable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model, distances, sampling, spjoin, tracing

Array = jnp.ndarray


def kpm_config(delta: float, metric: str = "l1", k: int = 1024, p: int = 16,
               n_dims: int = 8, seed: int = 0) -> spjoin.JoinConfig:
    """The KPM-like arm: random pivots + iterative equi-depth splits."""
    return spjoin.JoinConfig(
        delta=delta, metric=metric, sampler="random", partitioner="iterative",
        k=k, p=p, n_dims=n_dims, anchor_method="random", tighten=False, seed=seed,
    )


def ball_join(
    data: Array,
    delta: float,
    metric: str = "l1",
    n_pivots: int = 16,
    seed: int = 0,
    return_pairs: bool = True,
) -> spjoin.JoinResult:
    """MRSimJoin-style ball (generalized-hyperplane) partitioning join.

    Pivots are drawn uniformly (the baseline's sampling). Every object's
    KERNEL cell is its nearest pivot; it is replicated to every cell within
    the 2-delta window. Verification is per-cell V_h x W_h with the min-cell
    de-dup rule (same rule as spjoin.join, so results are identical sets).
    """
    key = jax.random.PRNGKey(seed)
    data = jnp.asarray(data)
    n = data.shape[0]

    with tracing.root("ball_join", rows=int(n)):
        with tracing.span("ball_join.sample") as t_sample:
            pivots = sampling.random_sample(key, data, min(n_pivots, n))

        with tracing.span("ball_join.map") as t_map:
            d = distances.pairwise(data, pivots, metric)  # (n, p)
            cells = jnp.argmin(d, axis=1).astype(jnp.int32)
            nearest = d.min(axis=1, keepdims=True)
            member = d <= nearest + 2.0 * delta  # (n, p) window rule
            cells_np = np.asarray(cells)  # the phase ends on the host
            member_np = np.asarray(member)

        with tracing.span("ball_join.reduce") as t_verify:
            p = member_np.shape[1]
            v_sizes = np.bincount(cells_np, minlength=p).astype(np.int64)
            w_sizes = member_np.sum(0).astype(np.int64)

            metric_fn = distances.get_metric(metric)
            n_verif = 0
            chunks: list[np.ndarray] = []
            for h in range(p):
                v_idx = np.flatnonzero(cells_np == h)
                w_idx = np.flatnonzero(member_np[:, h])
                if v_idx.size == 0 or w_idx.size == 0:
                    continue
                n_verif += int(v_idx.size) * int(w_idx.size)
                dm = np.asarray(metric_fn.pairwise(data[v_idx], data[w_idx]))
                hv, hw = np.nonzero(dm <= delta)
                gi, gj = v_idx[hv], w_idx[hw]
                cj = cells_np[gj]
                keep = ((cj == h) & (gi < gj)) | (cj > h)
                if return_pairs and keep.any():
                    chunks.append(np.stack([gi[keep], gj[keep]], axis=1))
            pairs = (
                np.unique(np.sort(np.concatenate(chunks), axis=1), axis=0)
                if chunks
                else np.zeros((0, 2), np.int64)
            )

    return spjoin.JoinResult(
        pairs=pairs.astype(np.int64),
        n_verifications=n_verif,
        cost=cost_model.partition_cost(v_sizes, w_sizes),
        node_confidences=np.zeros((0,)),
        sample_time_s=t_sample.seconds,
        map_time_s=t_map.seconds,
        verify_time_s=t_verify.seconds,
    )
