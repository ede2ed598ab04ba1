"""Table 3: per-partition verification balance (AVER / STDEV) per system.

Paper claim: SP-Join (Gen+Learn) has both the lowest mean and the lowest
std of per-partition verification counts — the load-balancing result. Each
row reports the TRUE per-cell loads the engine ran (``JoinResult.
per_cell_verified`` — |V_h|·|W_h| per cell), not a derived ratio.

A second table (``bench_table3_dist.csv``) extends the claim to the
distributed executor's per-DEVICE loads: the contiguous cell→device layout
vs the cost-model-guided LPT plan (``core.placement``) on a skewed mixture,
8 simulated devices — the paper's Table 3 balance story, finally measured
at placement granularity. Run in a subprocess so the device-count flag
never leaks into the parent; the child is a CPU rehearsal
(``JAX_PLATFORMS=cpu`` on virtual host devices), never a chip run.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from benchmarks.common import Csv, make_datasets
from repro.core import baselines, spjoin

_SUB_DIST = """
import os
os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'
import json, numpy as np, jax, jax.numpy as jnp
from repro.core import distributed
from repro.data import synthetic

mesh = jax.make_mesh((8,), ("data",))
data = synthetic.mixture({n}, 8, n_clusters=5, skew=0.8, seed=3)
out = {{}}
for strategy in ("contiguous", "lpt"):
    r = distributed.distributed_join(
        jnp.asarray(data), mesh=mesh, delta=2.5, metric="l1", k=256, p=16,
        n_dims=6, sampler="generative", backend="numpy",
        placement=strategy, seed=0)
    loads = np.asarray(r.device_loads, np.float64)
    out[strategy] = dict(
        aver=float(loads.mean()), stdev=float(loads.std()),
        makespan_ratio=float(r.makespan_ratio), hits=int(r.n_hits))
print(json.dumps(out))
"""


def run(n: int = 1200, k: int = 256, p: int = 12) -> None:
    csv = Csv("bench_table3.csv", ["dataset", "system", "aver", "stdev"])
    for ds in make_datasets(n):
        arms = {
            "kpm-like": baselines.kpm_config(ds.deltas[-1], ds.metric, k=k, p=p, n_dims=8),
            "random+iter": spjoin.JoinConfig(delta=ds.deltas[-1], metric=ds.metric,
                                             sampler="random", partitioner="iterative",
                                             k=k, p=p, n_dims=8),
            "dist+iter": spjoin.JoinConfig(delta=ds.deltas[-1], metric=ds.metric,
                                           sampler="distribution", partitioner="iterative",
                                           k=k, p=p, n_dims=8),
            "gen+iter": spjoin.JoinConfig(delta=ds.deltas[-1], metric=ds.metric,
                                          sampler="generative", partitioner="iterative",
                                          k=k, p=p, n_dims=8),
            "gen+learn": spjoin.JoinConfig(delta=ds.deltas[-1], metric=ds.metric,
                                           sampler="generative", partitioner="learning",
                                           k=k, p=p, n_dims=8),
        }
        for name, cfg in arms.items():
            res = spjoin.join(ds.data, cfg, return_pairs=False)
            # True per-cell verification loads the engine ran (|V_h|·|W_h|
            # per cell), straight from the result — the Table 3 metric.
            per_cell = np.asarray(res.per_cell_verified, np.float64)
            csv.row(ds.name, name, int(per_cell.mean()), int(per_cell.std()))
    csv.close()

    # Distributed arm: per-DEVICE balance, contiguous vs LPT placement.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # CPU rehearsal on 8 virtual host devices, pinned off any chip.
    env = {"PYTHONPATH": os.path.join(root, "src"), "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", root), "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, "-c", _SUB_DIST.format(n=n)],
        capture_output=True, text=True, timeout=1800, env=env, cwd=root,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    dist = json.loads(res.stdout.splitlines()[-1])
    csv2 = Csv("bench_table3_dist.csv",
               ["placement", "aver", "stdev", "makespan_ratio"])
    for strategy in ("contiguous", "lpt"):
        row = dist[strategy]
        csv2.row(strategy, int(row["aver"]), int(row["stdev"]),
                 round(row["makespan_ratio"], 3))
    csv2.close()


if __name__ == "__main__":
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run()
