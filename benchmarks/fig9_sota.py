"""Fig. 9: SP-Join vs the baseline algorithmic cores.

  spjoin        Gen + Learn (this paper)
  kpm-like      random sampling + KD equi-depth splits (Chen et al.'17 core)
  mrsim-like    ball partitioning, p pivots (Silva & Reed'12 core)
  cluster-like  ball partitioning with 2p pivots + window (Sarma et al.'14
                flavor: more, finer balls)

All four produce exact results (asserted); cost = wall time + verifications.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import Csv, make_datasets, timed
from repro.core import baselines, spjoin


def run(n: int = 1200, k: int = 256, p: int = 12) -> None:
    csv = Csv(
        "bench_fig9.csv",
        ["dataset", "delta", "system", "join_s", "verifications", "pairs"],
    )
    for ds in make_datasets(n):
        for delta in ds.deltas:
            cfg = spjoin.JoinConfig(delta=delta, metric=ds.metric,
                                    sampler="generative", partitioner="learning",
                                    k=k, p=p, n_dims=8, seed=0)
            res_sp, t_sp = timed(spjoin.join, ds.data, cfg)
            res_kpm, t_kpm = timed(
                spjoin.join, ds.data,
                baselines.kpm_config(delta, ds.metric, k=k, p=p, n_dims=8),
            )
            res_mr, t_mr = timed(baselines.ball_join, ds.data, delta, ds.metric, p)
            res_cl, t_cl = timed(baselines.ball_join, ds.data, delta, ds.metric, 2 * p)
            assert res_sp.n_pairs == res_kpm.n_pairs == res_mr.n_pairs == res_cl.n_pairs
            for name, res, t in [("spjoin", res_sp, t_sp), ("kpm-like", res_kpm, t_kpm),
                                 ("mrsim-like", res_mr, t_mr),
                                 ("cluster-like", res_cl, t_cl)]:
                csv.row(ds.name, round(delta, 4), name, round(t, 3),
                        res.n_verifications, res.n_pairs)
    csv.close()


if __name__ == "__main__":
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run()
