"""Fig. 8: sensitivity to the number of partitions p.

Paper claim: robust — best-to-worst spread ~10% over a wide p range.
(Wall time on one CPU conflates with constant factors; the load metric —
max per-cell verifications, i.e. the parallel critical path — is the
p-sensitivity the claim is about. Both are emitted.)
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import Csv, make_datasets, timed
from repro.core import spjoin


def run(n: int = 1200, k: int = 256) -> None:
    csv = Csv(
        "bench_fig8.csv",
        ["dataset", "p", "join_s", "verifications", "max_cell", "balance_std"],
    )
    for ds in make_datasets(n)[:2]:  # paper shows SIFT + AOL
        delta = ds.deltas[-1]
        for p in (4, 8, 12, 16, 24, 32):
            cfg = spjoin.JoinConfig(delta=delta, metric=ds.metric,
                                    sampler="generative", partitioner="learning",
                                    k=k, p=p, n_dims=8, seed=0)
            res, t = timed(spjoin.join, ds.data, cfg)
            csv.row(ds.name, p, round(t, 3), res.n_verifications,
                    int(res.cost.max_cell), round(res.cost.balance_std, 1))
    csv.close()


if __name__ == "__main__":
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run()
