"""Fig. 10: scale-out — nodes M in {2,4,8}.

Two views, because this container has one physical core:
  * real multi-device wall time via a subprocess per M (XLA host devices;
    same-core contention makes absolute speedups flat, so this validates
    *runnability*, not speedup). Each child is a CPU rehearsal, pinned to
    ``JAX_PLATFORMS=cpu`` so it never competes for a chip;
  * the parallel-critical-path proxy: max per-node verification load from
    the single-host executor sharded M ways — the quantity whose M-scaling
    the paper's Fig. 10 actually demonstrates.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from benchmarks.common import Csv, make_datasets


_SUB = """
import os
os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count={m}'
import json, numpy as np, jax, jax.numpy as jnp, time
from repro.core import distributed
from repro.data import synthetic
mesh = jax.make_mesh(({m},), ("data",))
data = synthetic.mixture({n}, 12, n_clusters=6, skew=0.3, seed=0)
t0 = time.perf_counter()
r = distributed.distributed_join(jnp.asarray(data), mesh=mesh, delta={delta},
                                 metric="l1", k=192, p={p}, n_dims=6, seed=0)
t = time.perf_counter() - t0
print(json.dumps(dict(m={m}, wall_s=t, hits=r.n_hits, verif=r.n_verifications,
                      max_cell=float(np.max(r.per_cell_verified)),
                      padding=r.capacity_padding)))
"""


def run(n: int = 1600, p: int = 16) -> None:
    csv = Csv(
        "bench_fig10.csv",
        ["nodes", "wall_s", "hits", "verifications", "max_cell_load", "padding"],
    )
    # delta from data scale
    from repro.core import distances
    import jax.numpy as jnp

    data = make_datasets(400)[0]
    delta = data.deltas[-1]
    for m in (2, 4, 8):
        out = subprocess.run(
            [sys.executable, "-c", _SUB.format(m=m, n=n, delta=delta, p=p)],
            capture_output=True, text=True, timeout=1200,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "HOME": os.environ.get("HOME", "."), "JAX_PLATFORMS": "cpu"},
            cwd=".",
        )
        assert out.returncode == 0, out.stderr[-2000:]
        r = json.loads(out.stdout.splitlines()[-1])
        csv.row(m, round(r["wall_s"], 2), r["hits"], r["verif"],
                int(r["max_cell"]), round(r["padding"], 2))
    csv.close()


if __name__ == "__main__":
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run()
