"""Hillclimb H3 (§Perf): the distributed SP-Join pipeline + the verify engine.

Sections (``--rs`` adds a fourth):

1. distributed — per-arm wall time of the 8-device shard_map pipeline
   (real wall clock; base / tighten / p-sweep / noprune arms), run in a
   subprocess so the device-count flag never leaks into the parent process.
   Every 8-device section (1, 3's distributed arm, 4 and 6) is a CPU
   rehearsal: its child runs with ``JAX_PLATFORMS=cpu`` on virtual host
   devices, never on a chip, and its times are CPU times.
   Each arm reports its pivot-filter pruning rate (fraction of candidate
   pairs skipping exact evaluation) and exact-evaluation count.
2. verify-engine — the reduce-phase hot spot head-to-head: the seed's dense
   per-cell eager loop (``verify.reference_verify``) vs the streaming tiled
   engine (``verify.verify_pairs``, numpy backend = jitted/fused XLA) on one
   shared partition plan, with and without pivot-filter pruning. Reports
   speedups, tile/bucket counts, padding occupancy, pruning rate and
   exact-evaluation counts; asserts prune="pivot" pairs are byte-identical
   to prune="none". Acceptance floor: engine >= 2x at N >= 20k on CPU.
3. map-phase — the fused single-pass map kernel (``kernels.ops.map_assign``:
   space map + kernel assign + packed membership) vs the legacy two-broadcast
   jnp path, on BOTH executors (reference: in-process; distributed: the
   8-device counting stage with ``fused=`` toggled). Reports ``map_ms`` /
   ``map_ms_legacy`` wall times, the modeled HBM-intermediate saving
   ``map_bytes_saved`` (2·N·p·n + N·p bool bytes avoided minus the N·⌈p/32⌉
   packed words written) and asserts outputs are byte-identical.
4. placement — cost-model-guided reduce placement (``core.placement``) on a
   hard-skew mixture: contiguous vs LPT cell→device plans on the 8-device
   mesh. Reports measured per-device ``balance_std`` / ``makespan_ratio``,
   the planner's quality report (certified bound), slot/split counts and the
   capacity effect; asserts both placements emit byte-identical pair sets.
5. incremental — the streaming layer (``MetricIndex.insert_batch``): one
   delta absorbed into a live index vs a from-scratch rebuild-and-join over
   the grown set, at 1% / 10% / 50% delta fractions. Reports the amortized
   delta cost, the rebuild cost it displaces, the drift monitor's decision
   and the ``incremental_identical`` certificate (accumulated pairs
   byte-identical to the from-scratch join — docs/STREAMING.md).
6. rs (``--rs``) — the two-set R×S cross join with asymmetric |R| << |S|
   (the skew-sensitive case), exactness-checked in-subprocess against the
   brute-force cross oracle; reports wall time, W capacity, the S-side
   duplication metric Σ|W_h|/|S| and the pruning rate.

Emits ``runs/bench_h3.csv`` + ``runs/h3_perf.json`` (the JSON is the CI
smoke-benchmark contract: ``python benchmarks/h3_join_perf.py --smoke --rs``
must run to completion, write it, report a NONZERO pruning rate, a
byte-identical map-phase section, a placement section with
``placement_identical == true`` and LPT ``balance_std`` no worse than
contiguous, and an incremental section with
``incremental_identical == true`` whose 1%-fraction arm absorbs the delta
cheaper than the rebuild it displaces). Schema of the JSON:
docs/BENCHMARKS.md.

Run:
    PYTHONPATH=src python benchmarks/h3_join_perf.py [--smoke] [--rs]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/h3_join_perf.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))  # repro without install

from benchmarks.common import Csv, OUT_DIR

_SUB = """
import os
os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'
import json, time, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import distributed
from repro.data import synthetic
from repro.launch import hloparse

mesh = jax.make_mesh((8,), ("data",))
data = synthetic.mixture({n}, 12, n_clusters=6, skew=0.5, seed=0)
out = []
for (label, tighten, p, prune) in {arms}:
    walls = []
    for rep in range(2):  # rep 0 warms compile caches; rep 1 is steady state
        t0 = time.perf_counter()
        r = distributed.distributed_join(
            jnp.asarray(data), mesh=mesh, delta={delta}, metric="l1", k=256,
            p=p, n_dims=6, sampler="generative", backend="numpy",
            tighten=tighten, prune=prune, seed=0)
        walls.append(time.perf_counter() - t0)
    out.append(dict(label=label, p=p, wall_cold_s=walls[0], wall_s=walls[-1],
                    hits=r.n_hits,
                    verif=r.n_verifications, cap_w=r.exact_cap_w,
                    padding=r.capacity_padding,
                    max_cell=float(np.max(r.per_cell_verified)),
                    pruning_rate=r.pruning_rate, n_exact=r.n_candidates,
                    predicted_survival=r.predicted_survival))
print(json.dumps(out))
"""


_SUB_RS = """
import os
os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'
import json, time, numpy as np, jax, jax.numpy as jnp
from repro.core import distributed, spjoin
from repro.data import synthetic

mesh = jax.make_mesh((8,), ("data",))
# Asymmetric |R| << |S| — the skew-sensitive cross-join case: every R row
# fans out against a much larger S side, so W capacity planning dominates.
r, s = synthetic.rs_mixture({n_r}, {n_s}, 12, n_clusters=6, skew=0.5, seed=0)
walls = []
for rep in range(2):  # rep 0 warms compile caches; rep 1 is steady state
    t0 = time.perf_counter()
    res = distributed.distributed_join(
        jnp.asarray(r), s=jnp.asarray(s), mesh=mesh, delta={delta},
        metric="l1", k=256, p=16, n_dims=6, sampler="generative",
        backend="numpy", emit_pairs=True, seed=0)
    walls.append(time.perf_counter() - t0)
truth = spjoin.brute_force_pairs(r, {delta}, "l1", s=s)
assert np.array_equal(res.pairs, truth), (res.pairs.shape, truth.shape)
print(json.dumps(dict(
    label="rs", n_r={n_r}, n_s={n_s}, wall_cold_s=walls[0], wall_s=walls[-1],
    pairs=int(res.pairs.shape[0]), verif=res.n_verifications,
    cap_w=res.exact_cap_w, padding=res.capacity_padding,
    duplication=res.duplication, pruning_rate=res.pruning_rate,
    n_exact=res.n_candidates, exact=True)))
"""


_SUB_MAP = """
import os
os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'
import json, time, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import distributed
from repro.data import synthetic

mesh = jax.make_mesh((8,), ("data",))
data = synthetic.mixture({n}, 12, n_clusters=6, skew=0.5, seed=0)
sharding = NamedSharding(mesh, P("data"))
x, valid, ids, _ = distributed._pad_shard_set(jnp.asarray(data), 8, sharding)

# One shared plan (sampling + control plane) — the map pass is what differs.
stats_fn = distributed.make_stage_stats(mesh, "data")
packets, confs, counts = jax.tree.map(np.asarray, stats_fn(x, valid))
kg, ka = jax.random.split(jax.random.PRNGKey(0))
c_min = float(np.clip(np.clip(confs / max(confs.max(), 1e-6), 1e-3, 1.0).min(), 0.05, 1.0))
pivots, _ = distributed.gibbs_from_packets(
    kg, jnp.asarray(packets), jnp.asarray(confs), jnp.asarray(counts), 256,
    int(np.ceil(256 / c_min * 1.5)) + 8)
plan = distributed.build_join_plan(
    ka, pivots, delta={delta}, metric="l1", p=16, n_dims=6, seed=0)

out, baseline = {{}}, None
for label, fused in (("legacy", False), ("fused", True)):
    fn = distributed.make_stage_counts(mesh, "data", plan, backend="numpy", fused=fused)
    walls = []
    for rep in range(3):  # rep 0 warms the compile cache
        t0 = time.perf_counter()
        res = jax.block_until_ready(fn(x, valid))
        walls.append(time.perf_counter() - t0)
    arrs = [np.asarray(a) for a in res]
    if baseline is None:
        baseline = arrs
    out[label] = dict(
        map_ms=min(walls[1:]) * 1e3,
        identical=all(a.tobytes() == b.tobytes() for a, b in zip(arrs, baseline)),
    )
print(json.dumps(out))
"""


_SUB_PLACEMENT = """
import os
os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'
import json, time, numpy as np, jax, jax.numpy as jnp
from repro.core import distributed
from repro.data import synthetic

mesh = jax.make_mesh((8,), ("data",))
# Hard-skew mixture: one cluster dominates, so contiguous placement parks
# the hot cell(s) on one straggler device — the regime Table 3 is about.
data = synthetic.mixture({n}, 12, n_clusters=5, skew={skew}, seed=3)
out = {{}}
pairs = {{}}
for strategy in ("contiguous", "lpt"):
    walls = []
    for rep in range(2):  # rep 0 warms compile caches; rep 1 is steady state
        t0 = time.perf_counter()
        r = distributed.distributed_join(
            jnp.asarray(data), mesh=mesh, delta={delta}, metric="l1", k=256,
            p=16, n_dims=6, sampler="generative", backend="numpy",
            placement=strategy, emit_pairs=True, seed=0)
        walls.append(time.perf_counter() - t0)
    pairs[strategy] = r.pairs.tobytes()
    pl = r.placement_plan
    out[strategy] = dict(
        wall_cold_s=walls[0], wall_s=walls[-1], hits=r.n_hits,
        verif=r.n_verifications,
        balance_std=float(r.balance_std),
        makespan_ratio=float(r.makespan_ratio),
        device_loads=[float(x) for x in r.device_loads],
        capacity_saved_bytes=int(r.capacity_saved_bytes),
        padding=float(r.capacity_padding),
        n_slots=int(pl.n_slots), n_split_cells=int(pl.n_split_cells),
        plan_makespan_ratio=float(pl.makespan_ratio),
        plan_certified_bound=float(pl.certified_bound),
    )
out["placement_identical"] = pairs["contiguous"] == pairs["lpt"]
print(json.dumps(out))
"""


def _run_sub(prog: str):
    """Run one 8-virtual-device section in a child process. The child is a
    CPU rehearsal of the mesh path: it is pinned to ``JAX_PLATFORMS=cpu`` so
    it never competes with a parent, or another process, for a chip."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {"PYTHONPATH": os.path.join(root, "src"), "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", root), "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=1800, env=env, cwd=root,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.splitlines()[-1])


def run_rs(n_r: int, n_s: int, delta: float) -> dict:
    """The R×S arm: exactness-checked cross join with |R| << |S|."""
    return _run_sub(_SUB_RS.format(n_r=n_r, n_s=n_s, delta=delta))


def run_distributed(n: int, delta: float, arms) -> list[dict]:
    return _run_sub(_SUB.format(n=n, delta=delta, arms=repr(arms)))


def run_placement(n: int, delta: float, skew: float = 0.85) -> dict:
    """Section 5: contiguous vs LPT reduce placement on a hard-skew mixture
    (8-device mesh). Reports measured per-device balance (`balance_std`,
    `makespan_ratio`), the planner's own quality report and the capacity
    effect; asserts the two placements emit byte-identical pair sets."""
    out = _run_sub(_SUB_PLACEMENT.format(n=n, delta=delta, skew=skew))
    assert out["placement_identical"], "placement changed the pair set"
    out["n"] = n
    out["skew"] = skew
    return out


def _map_bytes_saved(n: int, p: int, nd: int) -> int:
    """Modeled HBM-intermediate bytes the fused map pass avoids per shard:
    two (N, p, n) bool containment broadcasts + the (N, p) bool mask of the
    legacy path, minus the (N, ⌈p/32⌉) uint32 packed mask it writes instead
    (the (N, n) f32 coordinates are written by both paths)."""
    words = -(-p // 32)
    return 2 * n * p * nd + n * p - 4 * n * words


def run_map_phase(n: int, delta: float) -> dict:
    """Section 3: fused vs legacy map pass, both executors (ref in-process,
    distributed as the 8-device counting stage in a subprocess)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import partition, spjoin
    from repro.data import synthetic
    from repro.kernels import ops as kops

    data = synthetic.mixture(n, 12, n_clusters=6, skew=0.5, seed=0)
    cfg = spjoin.JoinConfig(delta=delta, metric="l1", k=256, p=16, n_dims=6,
                            sampler="generative", seed=0)
    key = jax.random.PRNGKey(cfg.seed)
    shards = list(jnp.array_split(jnp.asarray(data), 4))
    allx = jnp.concatenate(shards)
    k_sample, k_anchor = jax.random.split(key)
    node_stats = spjoin.fit_node_stats(shards, cfg.t_cells)
    pivots = spjoin.draw_pivots(k_sample, shards, node_stats, cfg)
    plan, smap = spjoin.build_plan(k_anchor, pivots, cfg)

    def legacy():
        xm = smap(allx)
        cells = partition.assign_kernel(plan, xm)
        member = partition.whole_membership(plan, xm)
        return jax.block_until_ready((xm, cells, member))

    def fused():
        xm, cells, bits = kops.map_assign(
            allx, smap.anchors, plan.kernel_lo, plan.kernel_hi,
            plan.whole_lo, plan.whole_hi, cfg.metric, backend="numpy",
        )
        member = kops.unpack_membership(bits, plan.p)
        return jax.block_until_ready((xm, cells, member))

    results = {}
    for label, fn in (("legacy", legacy), ("fused", fused)):
        walls, out = [], None
        for _ in range(3):  # rep 0 warms compile/dispatch caches
            t0 = time.perf_counter()
            out = fn()
            walls.append(time.perf_counter() - t0)
        results[label] = (min(walls[1:]) * 1e3, out)
    t_leg, (_, cells_l, member_l) = results["legacy"]
    t_fus, (_, cells_f, member_f) = results["fused"]
    identical = (
        np.asarray(cells_l).tobytes() == np.asarray(cells_f).tobytes()
        and np.asarray(member_l).tobytes() == np.asarray(member_f).tobytes()
    )
    reference = dict(
        executor="reference", n=n, p=plan.p,
        map_ms=round(t_fus, 3), map_ms_legacy=round(t_leg, 3),
        speedup=round(t_leg / max(t_fus, 1e-9), 2),
        map_bytes_saved=_map_bytes_saved(n, plan.p, plan.n_dims),
        identical=bool(identical),
    )

    sub = _run_sub(_SUB_MAP.format(n=n, delta=delta))
    distributed_row = dict(
        executor="distributed", n=n, p=16,
        map_ms=round(sub["fused"]["map_ms"], 3),
        map_ms_legacy=round(sub["legacy"]["map_ms"], 3),
        speedup=round(sub["legacy"]["map_ms"] / max(sub["fused"]["map_ms"], 1e-9), 2),
        map_bytes_saved=_map_bytes_saved(n, 16, 6),
        identical=bool(sub["fused"]["identical"] and sub["legacy"]["identical"]),
    )
    return dict(n=n, reference=reference, distributed=distributed_row)


def run_verify_engine(n: int, delta: float) -> dict:
    """Reference dense loop vs streaming engine on one shared partition plan."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import partition, spjoin, verify
    from repro.data import synthetic

    data = synthetic.mixture(n, 12, n_clusters=6, skew=0.5, seed=0)
    cfg = spjoin.JoinConfig(delta=delta, metric="l1", k=256, p=16, n_dims=6,
                            sampler="generative", seed=0)
    key = jax.random.PRNGKey(cfg.seed)
    shards = list(jnp.array_split(jnp.asarray(data), 4))
    allx = jnp.concatenate(shards)
    k_sample, k_anchor = jax.random.split(key)
    node_stats = spjoin.fit_node_stats(shards, cfg.t_cells)
    pivots = spjoin.draw_pivots(k_sample, shards, node_stats, cfg)
    plan, smap = spjoin.build_plan(k_anchor, pivots, cfg)
    xm = smap(allx)
    cells = partition.assign_kernel(plan, xm)
    plan = partition.tighten(plan, xm, cells)
    member = partition.whole_membership(plan, xm)
    cells_np, member_np = np.asarray(cells), np.asarray(member)

    # Symmetric protocol: min of 2 reps for ALL paths (rep 0 warms eager
    # dispatch caches on the reference and the per-bucket compile cache on
    # the engine), so the speedups compare steady state to steady state.
    t_ref, ref_pairs, n_verif = float("inf"), None, 0
    for _ in range(2):
        t0 = time.perf_counter()
        ref_pairs, n_verif = verify.reference_verify(
            allx, cells_np, member_np, cfg.delta, cfg.metric
        )
        t_ref = min(t_ref, time.perf_counter() - t0)

    ecfg = verify.EngineConfig(backend="numpy", prune="none")
    t_eng, eng_pairs, stats = float("inf"), None, None
    for _ in range(2):
        t0 = time.perf_counter()
        eng_pairs, stats = verify.verify_pairs(
            allx, cells_np, member_np, cfg.delta, cfg.metric, config=ecfg
        )
        t_eng = min(t_eng, time.perf_counter() - t0)
    assert np.array_equal(ref_pairs, eng_pairs), "engine != reference pairs"

    # Emission/pruning arms on the same plan: the host mask-readback path
    # with window pruning (compact and mask emission), plus the pivot-filter
    # telemetry arm. Hard invariant (the engine's soundness contract): every
    # arm's pair set is byte-identical to the unpruned mask run.
    xm_np = np.asarray(xm, np.float32)

    def _arm(prune: str, emit: str, coords=None, **tiles):
        acfg = verify.EngineConfig(backend="numpy", prune=prune, emit=emit,
                                   **tiles)
        t_best, pairs_a, stats_a = float("inf"), None, None
        for _ in range(3):
            t0 = time.perf_counter()
            pairs_a, stats_a = verify.verify_pairs(
                allx, cells_np, member_np, cfg.delta, cfg.metric, config=acfg,
                coords=coords,
            )
            t_best = min(t_best, time.perf_counter() - t0)
        assert pairs_a.tobytes() == eng_pairs.tobytes(), (
            f"engine arm prune={prune} emit={emit} changed the pair set"
        )
        return t_best, stats_a

    t_compact, _ = _arm("none", "compact")
    # The headline fused arm: window pruning (host-side ordered windows +
    # bounding-box skips — ZERO extra device lanes) + compact emission.
    # tile_v=128 narrows each V band's surviving W window; the batched
    # window dispatch keeps the smaller tiles from paying per-launch
    # overhead (core.verify, *Batched window dispatch*).
    _WTILES = dict(tile_v=128, tile_w=512)
    t_prune_mask, pmstats = _arm("window", "mask", xm_np, **_WTILES)
    t_prune, pstats = _arm("window", "compact", xm_np, **_WTILES)
    # The pivot-filter telemetry arm (per-pair bound lanes + fused on-device
    # compaction): exact per-pair pruning counts, block skips on Pallas.
    t_pivot, pvstats = _arm("pivot", "compact", xm_np)

    return dict(
        n=n, delta=delta, n_pairs=int(eng_pairs.shape[0]),
        n_verifications=n_verif,
        reference_s=round(t_ref, 3), engine_s=round(t_eng, 3),
        speedup=round(t_ref / max(t_eng, 1e-9), 2),
        n_tiles=stats.n_tiles, n_buckets=stats.n_buckets,
        occupancy=round(stats.occupancy, 3),
        compact_s=round(t_compact, 3),
        speedup_compact=round(t_eng / max(t_compact, 1e-9), 2),
        prune=pstats.prune,
        pruned_s=round(t_prune, 3),
        speedup_prune=round(t_eng / max(t_prune, 1e-9), 2),
        pruned_mask_s=round(t_prune_mask, 3),
        speedup_prune_mask=round(t_eng / max(t_prune_mask, 1e-9), 2),
        pruned_pivot_s=round(t_pivot, 3),
        speedup_prune_pivot=round(t_eng / max(t_pivot, 1e-9), 2),
        emit=pstats.emit,
        n_overflow_retries=pvstats.n_overflow_retries,
        pruning_rate=round(pstats.prune_rate, 4),
        pivot_pruning_rate=round(pvstats.prune_rate, 4),
        n_exact=pstats.n_exact,
        n_tiles_pruned=pmstats.n_tiles_pruned,
        prune_identical=True,  # asserted per arm above (byte-identity)
    )


def run_incremental(n: int, delta: float) -> dict:
    """Section 5: the streaming layer's amortization claim, measured.

    For each delta fraction f, build a live index on n rows, absorb an
    f·n-row delta through ``insert_batch`` (only the delta is mapped; the
    ΔR×R_old verify streams against the resident V lists), and compare
    against what a batch system pays for the same state: a from-scratch
    ``spjoin.join`` over the n + f·n rows. The exactness certificate rides
    along: build-time pairs ∪ insert_batch pairs must be byte-identical to
    the from-scratch pair set (the ISSUE-8 contract)."""
    import numpy as np
    from repro.core import index as index_lib, spjoin
    from repro.data import synthetic

    pool = synthetic.mixture(n + n // 2 + 1, 12, n_clusters=6, skew=0.5, seed=0)
    cfg = spjoin.JoinConfig(delta=delta, metric="l1", k=256, p=16, n_dims=6,
                            sampler="generative", seed=0)
    arms = []
    for frac in (0.01, 0.10, 0.50):
        n_delta = max(1, int(n * frac))
        base, delta_rows = pool[:n], pool[n : n + n_delta]
        full = pool[: n + n_delta]

        t0 = time.perf_counter()
        idx = index_lib.build_index(base, cfg)
        build_s = time.perf_counter() - t0
        base_pairs = idx.self_pairs()

        t0 = time.perf_counter()
        new_pairs, stats = idx.insert_batch(delta_rows, rebuild_cfg=cfg)
        delta_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        scratch = spjoin.join(full, cfg)
        rebuild_s = time.perf_counter() - t0

        acc = np.unique(np.concatenate([base_pairs, new_pairs]), axis=0)
        arms.append(dict(
            frac=frac, n=n, n_delta=n_delta,
            build_ms=round(build_s * 1e3, 1),
            delta_ms=round(delta_s * 1e3, 1),
            rebuild_ms=round(rebuild_s * 1e3, 1),
            amortization=round(rebuild_s / max(delta_s, 1e-9), 2),
            n_new_pairs=int(new_pairs.shape[0]),
            drift=round(stats.drift, 4), action=stats.action,
            identical=bool(acc.tobytes() == scratch.pairs.tobytes()),
        ))
    return dict(
        n=n, arms=arms,
        incremental_identical=bool(all(a["identical"] for a in arms)),
    )


def run(n: int = 4000, delta: float = 6.0, n_verify: int = 20_000,
        smoke: bool = False, rs: bool = False) -> dict:
    if smoke:
        # Smoke shrinks only sizes the caller left at their defaults, so
        # `--smoke --n-verify 50000` still measures the requested N.
        n = 400 if n == 4000 else n
        n_verify = 2_000 if n_verify == 20_000 else n_verify
        arms = [("tighten", True, 16, "pivot")]
    else:
        arms = [("base", False, 16, "pivot"), ("tighten", True, 16, "pivot"),
                ("tighten_p8", True, 8, "pivot"),
                ("tighten_p32", True, 32, "pivot"),
                ("noprune", True, 16, "none")]

    rows = run_distributed(n, delta, arms)
    csv = Csv("bench_h3.csv",
              ["arm", "p", "wall_warm_s", "wall_cold_s", "hits",
               "verifications", "n_exact", "pruning_rate", "cap_w", "padding",
               "max_cell"])
    for r in rows:
        csv.row(r["label"], r["p"], round(r["wall_s"], 2),
                round(r["wall_cold_s"], 2), r["hits"],
                r["verif"], r["n_exact"], round(r["pruning_rate"], 4),
                r["cap_w"], round(r["padding"], 2),
                int(r["max_cell"]))
    csv.close()

    engine = run_verify_engine(n_verify, delta)
    csv2 = Csv("bench_h3_verify.csv",
               ["n", "reference_s", "engine_s", "compact_s", "prune",
                "pruned_mask_s", "pruned_s", "pruned_pivot_s", "speedup",
                "speedup_prune", "speedup_prune_mask", "speedup_prune_pivot",
                "emit", "n_overflow_retries", "pruning_rate",
                "pivot_pruning_rate", "n_exact", "tiles", "tiles_pruned",
                "buckets", "occupancy"])
    csv2.row(engine["n"], engine["reference_s"], engine["engine_s"],
             engine["compact_s"], engine["prune"], engine["pruned_mask_s"],
             engine["pruned_s"], engine["pruned_pivot_s"], engine["speedup"],
             engine["speedup_prune"], engine["speedup_prune_mask"],
             engine["speedup_prune_pivot"], engine["emit"],
             engine["n_overflow_retries"], engine["pruning_rate"],
             engine["pivot_pruning_rate"], engine["n_exact"],
             engine["n_tiles"], engine["n_tiles_pruned"],
             engine["n_buckets"], engine["occupancy"])
    csv2.close()
    # The fused-engine acceptance gate: window pruning + compact emission
    # must BEAT the unpruned mask engine on the SAME plan (the windowed
    # mask-path and pivot-telemetry numbers ride along for the
    # emission-path comparison).
    assert engine["speedup_prune"] >= 1.0, (
        f"fused engine arm regressed: speedup_prune={engine['speedup_prune']} "
        f"(mask-path speedup_prune_mask={engine['speedup_prune_mask']})"
    )

    map_phase = run_map_phase(n, delta)
    csv_map = Csv("bench_h3_map.csv",
                  ["executor", "n", "p", "map_ms", "map_ms_legacy", "speedup",
                   "map_bytes_saved", "identical"])
    for row in (map_phase["reference"], map_phase["distributed"]):
        csv_map.row(row["executor"], row["n"], row["p"], row["map_ms"],
                    row["map_ms_legacy"], row["speedup"],
                    row["map_bytes_saved"], row["identical"])
    csv_map.close()

    placement = run_placement(max(n // 4, 400), delta)
    csv_pl = Csv("bench_h3_placement.csv",
                 ["strategy", "n", "skew", "wall_warm_s", "balance_std",
                  "makespan_ratio", "n_slots", "n_split_cells",
                  "capacity_saved_bytes", "padding", "identical"])
    for strategy in ("contiguous", "lpt"):
        row = placement[strategy]
        csv_pl.row(strategy, placement["n"], placement["skew"],
                   round(row["wall_s"], 2), round(row["balance_std"], 1),
                   round(row["makespan_ratio"], 3), row["n_slots"],
                   row["n_split_cells"], row["capacity_saved_bytes"],
                   round(row["padding"], 2), placement["placement_identical"])
    csv_pl.close()

    stream = run_incremental(max(n // 2, 400), delta)
    csv_st = Csv("bench_h3_stream.csv",
                 ["frac", "n", "n_delta", "build_ms", "delta_ms",
                  "rebuild_ms", "amortization", "n_new_pairs", "drift",
                  "action", "identical"])
    for a in stream["arms"]:
        csv_st.row(a["frac"], a["n"], a["n_delta"], a["build_ms"],
                   a["delta_ms"], a["rebuild_ms"], a["amortization"],
                   a["n_new_pairs"], a["drift"], a["action"], a["identical"])
    csv_st.close()

    report = dict(smoke=smoke, distributed=rows, verify_engine=engine,
                  map_phase=map_phase, placement=placement,
                  incremental=stream)

    if rs:
        # Asymmetric two-set arm: |R| = n/5 against |S| = n, exactness-checked
        # against the brute-force cross oracle inside the subprocess.
        rs_row = run_rs(max(n // 5, 16), n, delta)
        csv3 = Csv("bench_h3_rs.csv",
                   ["n_r", "n_s", "wall_warm_s", "wall_cold_s", "pairs",
                    "verifications", "n_exact", "pruning_rate", "cap_w",
                    "padding", "duplication"])
        csv3.row(rs_row["n_r"], rs_row["n_s"], round(rs_row["wall_s"], 2),
                 round(rs_row["wall_cold_s"], 2), rs_row["pairs"],
                 rs_row["verif"], rs_row["n_exact"],
                 round(rs_row["pruning_rate"], 4), rs_row["cap_w"],
                 round(rs_row["padding"], 2), round(rs_row["duplication"], 3))
        csv3.close()
        report["rs"] = rs_row
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "h3_perf.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report))
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes; CI contract: must finish and emit JSON")
    ap.add_argument("--n", type=int, default=4000,
                    help="distributed-section dataset size")
    ap.add_argument("--n-verify", type=int, default=20_000,
                    help="verify-engine-section dataset size")
    ap.add_argument("--delta", type=float, default=6.0)
    ap.add_argument("--rs", action="store_true",
                    help="also run the asymmetric R×S cross-join arm "
                         "(|R| = n/5 vs |S| = n, exactness-checked)")
    args = ap.parse_args()
    from repro.launch.mesh import use_compile_cache

    use_compile_cache()
    run(n=args.n, delta=args.delta, n_verify=args.n_verify, smoke=args.smoke,
        rs=args.rs)
