"""Fig. 7: random sampling at {1x, 3x, 10x} sample size vs generative at 1x.

Paper claim: 3x random helps marginally; 10x random HURTS (map-phase
partition-tree cost grows with k and eats the benefit); Gen at 1x beats all.
"""
from __future__ import annotations

from benchmarks.common import Csv, make_datasets, timed
from repro.core import spjoin


def run(n: int = 1200, k: int = 192, p: int = 12) -> None:
    csv = Csv(
        "bench_fig7.csv",
        ["dataset", "delta", "arm", "k", "join_s", "map_s", "verifications"],
    )
    for ds in make_datasets(n):
        delta = ds.deltas[-1]
        k1 = min(k, len(ds.data) // 12)  # keep the 10x arm < population
        arms = [("gen_1x", "generative", k1), ("random_1x", "random", k1),
                ("random_3x", "random", 3 * k1), ("random_10x", "random", 10 * k1)]
        for name, sampler, kk in arms:
            cfg = spjoin.JoinConfig(delta=delta, metric=ds.metric,
                                    sampler=sampler, partitioner="learning",
                                    k=kk, p=p, n_dims=8, seed=0)
            res, t = timed(spjoin.join, ds.data, cfg)
            csv.row(ds.name, round(delta, 4), name, kk, round(t, 3),
                    round(res.map_time_s, 3), res.n_verifications)
    csv.close()


if __name__ == "__main__":
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run()
