"""Fig. 11: scale-up — join cost at 25/50/75/100% of the dataset.

Paper claim: near-linear growth in join time with data size (the partition
machinery keeps the quadratic term per-cell)."""
from __future__ import annotations

from benchmarks.common import Csv, make_datasets, timed
from repro.core import spjoin


def run(n: int = 1600, k: int = 256, p: int = 12) -> None:
    csv = Csv("bench_fig11.csv",
              ["dataset", "fraction", "n", "join_s", "verifications", "pairs"])
    for ds in make_datasets(n)[:2]:
        delta = ds.deltas[-1]
        for frac in (0.25, 0.5, 0.75, 1.0):
            sub = ds.data[: int(len(ds.data) * frac)]
            cfg = spjoin.JoinConfig(delta=delta, metric=ds.metric,
                                    sampler="generative", partitioner="learning",
                                    k=k, p=p, n_dims=8, seed=0)
            res, t = timed(spjoin.join, sub, cfg)
            csv.row(ds.name, frac, len(sub), round(t, 3),
                    res.n_verifications, res.n_pairs)
    csv.close()


if __name__ == "__main__":
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run()
