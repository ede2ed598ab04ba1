"""Fig. 6: join cost for {Random, Dist, Gen} x {Iter, Learn}.

Paper claim: Gen/Dist beat Random under every setting; Gen ~ Dist quality
with far lower sampling communication. Emits wall time + phase breakdown +
verification count per arm.
"""
from __future__ import annotations

from benchmarks.common import Csv, make_datasets, timed
from repro.core import spjoin

ARMS = [
    ("random", "iterative"), ("random", "learning"),
    ("distribution", "iterative"), ("distribution", "learning"),
    ("generative", "iterative"), ("generative", "learning"),
]


def run(n: int = 1200, k: int = 256, p: int = 12) -> None:
    csv = Csv(
        "bench_fig6.csv",
        ["dataset", "delta", "sampler", "partitioner", "join_s", "sample_s",
         "map_s", "verify_s", "verifications", "pairs"],
    )
    for ds in make_datasets(n):
        for delta in ds.deltas:
            for sampler, part in ARMS:
                cfg = spjoin.JoinConfig(
                    delta=delta, metric=ds.metric, sampler=sampler,
                    partitioner=part, k=k, p=p, n_dims=8, seed=0,
                )
                res, t = timed(spjoin.join, ds.data, cfg)
                csv.row(ds.name, round(delta, 4), sampler, part, round(t, 3),
                        round(res.sample_time_s, 3), round(res.map_time_s, 3),
                        round(res.verify_time_s, 3), res.n_verifications,
                        res.n_pairs)
    csv.close()


if __name__ == "__main__":
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run()
