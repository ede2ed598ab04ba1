"""Ablation (EXPERIMENTS.md finding #2): Gibbs confidence normalization.

The paper's Eqs. 17-19 acceptance collapses when no exponential family fits
(all c_i ~ 0 — e.g. multimodal shards): the fixed-length chain yields few
distinct pivots and partition quality degrades. Max-normalizing the
confidences is scale-invariant for the unbiased C=1 branch; this ablation
quantifies what it buys on mixture data.

    PYTHONPATH=src python -m benchmarks.ablation_confnorm
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv
from repro.core import distances, expfam, gof, mapping, partition, sampling
from repro.data import synthetic


def run(n: int = 2000) -> None:
    csv = Csv("bench_ablation_confnorm.csv",
              ["normalize", "accept_rate", "distinct_pivots",
               "verifications", "max_cell"])
    data = synthetic.mixture(n, 8, n_clusters=5, skew=0.4, seed=0)
    shards = np.array_split(data, 4)
    stats = []
    for s in shards:
        params, res = gof.fit_best_family(jnp.asarray(s))
        stats.append(sampling.NodeStats(params.family, params,
                                        float(res.confidence), len(s)))

    model = sampling.GenerativeModel(
        families=tuple(s.family for s in stats),
        packed_params=jnp.stack([expfam.pack(s.params) for s in stats]),
        confidence=jnp.asarray([s.confidence for s in stats], jnp.float32),
        counts=jnp.asarray([s.count for s in stats], jnp.float32),
    )

    for normalize in (False, True):
        pivots, acc = sampling.gibbs_chain(
            jax.random.PRNGKey(0), model, k=256, normalize_confidence=normalize
        )
        distinct = len(np.unique(np.asarray(pivots).round(4), axis=0))

        # partition quality downstream of those pivots
        smap = mapping.select_anchors(jax.random.PRNGKey(1), pivots, 6, "l1")
        mapped = np.asarray(smap(pivots))
        labels = partition.single_linkage_labels(
            np.asarray(distances.pairwise(pivots, pivots, "l1")), 32)
        plan = partition.build_partition(mapped, 16, 3.0, "learning", labels)
        xm = smap(jnp.asarray(data))
        cells = np.asarray(partition.assign_kernel(plan, xm))
        member = np.asarray(partition.whole_membership(plan, xm))
        v = np.bincount(cells, minlength=16)
        w = member.sum(0)
        csv.row(normalize, round(float(acc), 3), distinct,
                int((v * w).sum()), int((v * w).max()))
    csv.close()


if __name__ == "__main__":
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run()
