"""Fig. 12: number of verifications per technique combo.

Paper claims: Random+Iter worst; Gen+Learn best; ordering consistent with
Fig. 6 join times (verifications are the machine-independent cost).

Beyond-paper columns: the streaming verify engine's telemetry per arm —
tile count, static-bucket count and padding occupancy (valid / padded
verification ratio) — the TPU-native cost the bucketed engine trades for
compile-cache hits."""
from __future__ import annotations

from benchmarks.common import Csv, make_datasets
from repro.core import spjoin

ARMS = [("random", "iterative"), ("distribution", "iterative"),
        ("generative", "iterative"), ("generative", "learning")]


def run(n: int = 1200, k: int = 256, p: int = 12) -> None:
    csv = Csv("bench_fig12.csv",
              ["dataset", "delta", "arm", "verifications", "inner", "outer",
               "tiles", "buckets", "occupancy"])
    for ds in make_datasets(n):
        delta = ds.deltas[-1]
        for sampler, part in ARMS:
            cfg = spjoin.JoinConfig(delta=delta, metric=ds.metric,
                                    sampler=sampler, partitioner=part,
                                    k=k, p=p, n_dims=8, seed=0)
            res = spjoin.join(ds.data, cfg)
            vs = res.verify_stats
            csv.row(ds.name, round(delta, 4), f"{sampler}+{part}",
                    res.n_verifications, int(res.cost.inner),
                    int(res.cost.outer), vs.n_tiles, vs.n_buckets,
                    round(vs.occupancy, 3))
    csv.close()


if __name__ == "__main__":
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run()
