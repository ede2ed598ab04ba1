"""Single-host end-to-end SP-Join (reference executor).

Runs the full three-phase pipeline of Figure 1 on in-memory shards:

  sampling phase — per-"node" exponential-family fit + GoF confidence
                   (repro.core.expfam / gof), then Random / Dist / Gen pivots
  map phase      — anchor selection, space mapping, partition tree
                   (Iter / Learn), kernel assignment + whole membership
  reduce phase   — per-cell V_h × W_h verification via the streaming tiled
                   verify engine (repro.core.verify) — the same engine the
                   distributed executor routes through, with
                   backend="numpy"|"pallas"|"auto" dispatch

This executor keeps dynamic shapes (host loops over cells) — it is the
*semantic reference* the distributed static-shape executor and all benchmarks
are validated against, and it is what the paper-figure benchmarks run.

Pair de-duplication rule: a result pair (i, j), i's cell = g, j's cell = h,
is emitted by cell min(g, h) only; within one cell, both orders are present so
we keep i < j. Lemma 4 (applied symmetrically) guarantees the pair is seen by
both g and h, hence exactly once after the rule.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model, distances, expfam, gof, mapping, partition, sampling, tracing
from repro.core import placement as placement_lib
from repro.core import verify as verify_lib
from repro.kernels import ops as kops

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class JoinConfig:
    delta: float
    metric: str = "l1"
    sampler: str = "generative"  # random | distribution | generative
    partitioner: str = "learning"  # iterative | learning
    k: int = 1024  # sample (pivot) count; cf. required_sample_size
    p: int = 16  # number of partitions / reducers
    n_dims: int = 8  # target-space dimensionality n
    t_cells: int = 8  # GoF cells per dimension
    n_clusters: int | None = None  # labels for Learn (default: 2p)
    anchor_method: str = "fft"  # fft | random (paper)
    tighten: bool = True  # object-MBB tightening of whole boxes
    backend: str = "auto"  # verify engine: numpy | pallas | auto
    tile_v: int = 1024  # verify engine streaming tile (V side)
    tile_w: int = 4096  # verify engine streaming tile (W side)
    prune: str = "pivot"  # pivot-filter pruning: "pivot" | "window" | "none"
    #   ("window" = host-side range/tile pruning only — the wall-clock mode;
    #   sound for true metrics; cosine resolves back to "none" — core.verify)
    emit: str = "mask"  # verify-engine emission path: "mask" | "compact"
    #   (fused on-device pair compaction; reference-only metrics resolve
    #   back to "mask" — see core.verify, *Emission paths*). Pair sets are
    #   byte-identical either way.
    map_fused: bool = True  # single-pass map kernel (kernels.ops.map_assign);
    #   metrics without a kernel fall back to the two-pass path (capability,
    #   like backend dispatch). False: always the legacy two-pass path.
    #   On/off is byte-identical on the numpy backend; on Pallas, coordinate
    #   fp low bits at box edges may differ (pair sets stay exact).
    placement: str = "lpt"  # reduce-placement plan to REPORT ("lpt" |
    #   "contiguous" — core.placement). The reference executor is single-host
    #   so the plan never changes execution here; it is computed from the
    #   same cost-model loads (sampled pivots, survival-adjusted) and the
    #   same planner as the distributed executor, so parity tests can compare
    #   the two plans and benchmarks can read predicted balance without a
    #   device mesh. Devices modeled = the n_nodes argument of join().
    seed: int = 0

    def engine_config(self) -> verify_lib.EngineConfig:
        return verify_lib.EngineConfig(
            backend=self.backend, tile_v=self.tile_v, tile_w=self.tile_w,
            prune=self.prune, emit=self.emit,
        )


@dataclasses.dataclass
class JoinResult:
    pairs: np.ndarray  # (n_pairs, 2) int64, unique; self-join: i < j both
    #   indexing data — R×S: column 0 indexes R, column 1 indexes S
    n_verifications: int  # Σ_h |V_h|·|W_h| actually computed
    cost: cost_model.PartitionCost
    node_confidences: np.ndarray
    # Host seconds of the phases' spans (core.tracing): the map phase ends
    # with the host copies of its cells and membership, so it includes the
    # map kernel's wait and the reduce phase does not.
    sample_time_s: float
    map_time_s: float
    verify_time_s: float
    verify_stats: verify_lib.VerifyStats | None = None  # engine telemetry
    per_cell_verified: np.ndarray | None = None  # (p,) per-cell verification
    #   loads |V_h|·|W_h| the engine ran — the Table 3 AVER/STDEV input,
    #   same semantics as DistJoinResult.per_cell_verified
    placement_plan: placement_lib.PlacementPlan | None = None  # the reported
    #   cell→device plan (cfg.placement strategy over n_nodes devices)
    device_loads: np.ndarray | None = None  # (n_nodes,) PREDICTED loads of
    #   the plan (single host executes everything; the distributed executor
    #   reports the measured analogue)
    balance_std: float = 0.0  # std of per-device loads (predicted here;
    #   same definition as DistJoinResult.balance_std, which is measured)
    makespan_ratio: float = 1.0  # max/mean of per-device loads (predicted
    #   here, measured on DistJoinResult — one definition across executors;
    #   the plan's own makespan/lower-bound ratio is placement_plan.
    #   makespan_ratio)
    capacity_saved_bytes: int = 0  # modeled dispatch-buffer saving of the
    #   plan vs the contiguous global-max layout (cf. distributed executor)
    map_fused: bool = False  # the map phase ran the single-pass map kernel

    @property
    def n_pairs(self) -> int:
        return int(self.pairs.shape[0])


def fit_node_stats(shards: Sequence[Array], t_cells: int = 8) -> list[sampling.NodeStats]:
    """Sampling phase stages 1–2 (Alg. 1 lines 1–4) for every node."""
    out = []
    for shard in shards:
        params, res = gof.fit_best_family(jnp.asarray(shard), t=t_cells)
        out.append(
            sampling.NodeStats(
                family=params.family,
                params=params,
                confidence=float(res.confidence),
                count=int(shard.shape[0]),
            )
        )
    return out


def draw_pivots(
    key: jax.Array,
    shards: Sequence[Array],
    node_stats: list[sampling.NodeStats],
    cfg: JoinConfig,
) -> Array:
    if cfg.sampler == "random":
        allx = jnp.concatenate([jnp.asarray(s) for s in shards], axis=0)
        return sampling.random_sample(key, allx, cfg.k)
    if cfg.sampler == "distribution":
        return sampling.distribution_aware_sample(key, list(shards), node_stats, cfg.k)
    if cfg.sampler == "generative":
        if distances.get_metric(cfg.metric).discrete:
            # Equality-based metrics (raw MinHash vectors) have no continuous
            # support: a model-GENERATED pivot collides with no real
            # signature, every distance degenerates to 1.0, and the space
            # mapping collapses (caught by benchmarks — 100% verification
            # rate). The paper's own string/set story (§6.2) evaluates via
            # transformed vectors under L1 (our q-gram arm); for the MinHash
            # extension the generative arm falls back to distribution-aware
            # REAL samples. Flagged in DESIGN.md §limitations.
            return sampling.distribution_aware_sample(
                key, list(shards), node_stats, cfg.k
            )
        pivots, acc = sampling.generative_sample(key, node_stats, cfg.k)
        if float(acc) <= 0.0:
            warnings.warn(
                "gibbs chain accepted no draws (all node confidences ≈ 0); "
                "pivots fall back to raw chain draws", stacklevel=2,
            )
        return pivots
    raise ValueError(f"unknown sampler {cfg.sampler!r}")


def build_plan(
    key: jax.Array,
    pivots: Array,
    cfg: JoinConfig,
) -> tuple[partition.PartitionPlan, mapping.SpaceMap]:
    """Map phase control plane: anchors, mapping, labels, partition tree."""
    smap = mapping.select_anchors(key, pivots, cfg.n_dims, cfg.metric, cfg.anchor_method)
    pivots_mapped = np.asarray(smap(pivots))
    labels = None
    if cfg.partitioner == "learning":
        d = np.asarray(distances.pairwise(pivots, pivots, cfg.metric))
        labels = partition.single_linkage_labels(d, cfg.n_clusters or 2 * cfg.p)
    plan = partition.build_partition(
        pivots_mapped, cfg.p, cfg.delta, strategy=cfg.partitioner, labels=labels, seed=cfg.seed
    )
    return plan, smap


def _as_shards(x: Array | Sequence[Array], n_nodes: int) -> list[Array]:
    if isinstance(x, (list, tuple)):
        return [jnp.asarray(v) for v in x]
    x = jnp.asarray(x)
    if x.shape[0] == 0:
        return []
    return list(jnp.array_split(x, n_nodes))


def join(
    data: Array | Sequence[Array],
    cfg: JoinConfig,
    return_pairs: bool = True,
    n_nodes: int = 4,
    *,
    s: Array | Sequence[Array] | None = None,
) -> JoinResult:
    """Metric similarity join.

    Self-join (``s=None``): all pairs (i, j), i < j, with D(o_i, o_j) ≤ δ.

    Two-set R×S join (``s`` given): all pairs (i ∈ R, j ∈ S) with
    D(r_i, s_j) ≤ δ — ``data`` is R, ``s`` is S. Node stats are fitted on the
    union of R and S shards so pivots cover both distributions (Alg. 1 over
    every local node); V-side rows come from R's kernel cells, W-side rows
    from S's whole membership, and each cross pair is emitted exactly once
    (in R's kernel cell). Passing the same object as both ``data`` and ``s``
    (R = S aliasing) is detected and routed through the self-join path.

    ``data`` / ``s``: either the full (N, m) array (split into ``n_nodes``
    simulated local nodes) or an explicit list of per-node shards.
    """
    if s is data:
        s = None  # R = S aliasing: the canonical semantics is the self-join
    cross = s is not None
    with tracing.root("spjoin.join") as root:
        key = jax.random.PRNGKey(cfg.seed)
        shards = _as_shards(data, n_nodes)
        allx = jnp.concatenate(shards, axis=0) if shards else jnp.asarray(data)

        s_shards: list[Array] = _as_shards(s, n_nodes) if cross else []
        s_all = (
            jnp.concatenate(s_shards, axis=0)
            if s_shards
            else jnp.zeros((0, allx.shape[1]), allx.dtype)
        )
        root.add(rows=int(allx.shape[0]) + int(s_all.shape[0]))

        # ---- sampling phase -------------------------------------------------
        with tracing.span("spjoin.sample") as t_sample:
            k_sample, k_anchor = jax.random.split(key)
            # R∪S: pivots must cover both distributions (empty-set shards carry no
            # distribution and are skipped — the self path keeps its exact shard list).
            fit_shards = (
                [sh for sh in shards + s_shards if sh.shape[0] > 0] if cross else shards
            )
            node_stats = fit_node_stats(fit_shards, cfg.t_cells)
            pivots = draw_pivots(k_sample, fit_shards, node_stats, cfg)

        # ---- map phase -------------------------------------------------------
        with tracing.span("spjoin.map") as t_map:
            plan, smap = build_plan(k_anchor, pivots, cfg)
            # Fused single-pass map kernel (space map + assign + packed membership)
            # when the metric has one; reference-only metrics (angular,
            # jaccard_minhash) keep the two-pass jnp path — capability, not error,
            # exactly like backend dispatch. Outputs are byte-identical either way.
            fused = cfg.map_fused and kops.supports_kernel(cfg.metric)
            assign_backend = cfg.backend if fused else None
            if fused:
                # Membership is only worth computing in the first pass when the whole
                # boxes are final (no tighten, self-join) — otherwise request cells
                # only and pay for exactly one membership sweep below, same total
                # containment work as the legacy path.
                want = "both" if (not cfg.tighten and not cross) else "cells"
                x_mapped, cells, bits = kops.map_assign(
                    allx, smap.anchors, plan.kernel_lo, plan.kernel_hi,
                    plan.whole_lo, plan.whole_hi, cfg.metric, backend=cfg.backend,
                    want=want,
                )
            else:
                x_mapped = smap(allx)
                cells = partition.assign_kernel(plan, x_mapped)
                bits = None
            if cfg.tighten:
                # Kernel-cell MBBs come from R only (V rows); Lemma 4 still covers
                # every S partner: it lies within L∞ δ of an R member of the cell.
                plan = partition.tighten(plan, x_mapped, cells)
            s_mapped = None
            if cross:
                if s_all.shape[0] == 0:
                    s_mapped = jnp.zeros((0, smap.n_dims), jnp.float32)
                    member = jnp.zeros((0, plan.p), bool)
                elif fused:
                    # Same fused pass (and fp algorithm) as the R side — a borderline
                    # S coordinate must not land on a different side of a whole-box
                    # edge than R's kernel-computed MBB implies.
                    s_mapped, _, s_bits = kops.map_assign(
                        s_all, smap.anchors, plan.kernel_lo, plan.kernel_hi,
                        plan.whole_lo, plan.whole_hi, cfg.metric, backend=cfg.backend,
                        want="member",
                    )
                    member = kops.unpack_membership(s_bits, plan.p)
                else:
                    s_mapped = smap(s_all)
                    member = partition.whole_membership(plan, s_mapped)
            elif fused and not cfg.tighten:
                # The fused pass already produced membership for the final boxes.
                member = kops.unpack_membership(bits, plan.p)
            else:
                member = partition.whole_membership(plan, x_mapped, backend=assign_backend)
            # The phase ends on the host: reading its results here makes the map
            # time include the map kernel's wait, and keeps it out of the reduce.
            cells_np = np.asarray(cells)
            member_np = np.asarray(member)

        # ---- reduce phase: streaming tiled verify engine ---------------------
        # The mapped coordinates double as the verify phase's pivot filter
        # (prune="pivot"): the map phase already paid for them, the engine only
        # gathers them into tiles alongside the payload.
        with tracing.span("spjoin.reduce") as t_reduce:
            stats = partition.partition_stats(cells_np, member_np)
            pairs, vstats = verify_lib.verify_pairs(
                allx, cells_np, member_np, cfg.delta, cfg.metric,
                config=cfg.engine_config(), return_pairs=return_pairs,
                data_w=s_all if cross else None,
                coords=x_mapped, coords_w=s_mapped,
            )

        with tracing.span("spjoin.report"):
            if cross:
                cost = cost_model.rs_partition_cost(
                    stats["v_sizes"], stats["w_sizes"], int(s_all.shape[0])
                )
            else:
                cost = cost_model.partition_cost(stats["v_sizes"], stats["w_sizes"])

            # ---- reduce-placement report (same cost-model loads + planner as the
            # distributed executor; single-host, so the plan is telemetry only) ----
            piv_mapped = np.asarray(smap(pivots), np.float32)
            piv_cells = np.asarray(partition.assign_kernel(plan, jnp.asarray(piv_mapped)))
            piv_member = np.asarray(partition.whole_membership(plan, jnp.asarray(piv_mapped)))
            cell_loads, _, _, _ = placement_lib.planner_inputs(
                piv_mapped, piv_cells, piv_member,
                int(allx.shape[0]), int(s_all.shape[0]) if cross else int(allx.shape[0]),
                cfg.delta, vstats.prune == "pivot",
            )
            pl = placement_lib.plan_placement(
                cell_loads, max(len(shards), 1), strategy=cfg.placement
            )
            cap_saved = placement_lib.capacity_saved_bytes(
                pl, stats["v_sizes"][None, :], stats["w_sizes"][None, :],
                placement_lib.dispatch_row_bytes(
                    int(allx.shape[1]), smap.n_dims, vstats.prune == "pivot"
                ),
            )
            dev_loads = pl.device_loads

        return JoinResult(
            pairs=pairs,
            n_verifications=vstats.n_verifications,
            cost=cost,
            node_confidences=np.array([st.confidence for st in node_stats]),
            sample_time_s=t_sample.seconds,
            map_time_s=t_map.seconds,
            verify_time_s=t_reduce.seconds,
            verify_stats=vstats,
            per_cell_verified=(stats["v_sizes"] * stats["w_sizes"]).astype(np.int64),
            placement_plan=pl,
            device_loads=dev_loads,
            balance_std=float(dev_loads.std()),
            makespan_ratio=float(dev_loads.max(initial=0.0) / max(dev_loads.mean(), 1e-9)),
            capacity_saved_bytes=int(cap_saved),
            map_fused=fused,
        )


class IncrementalJoin:
    """Streaming self-join session: feed insertion batches, accumulate the
    canonical pair set (sorted unique (i, j) int64, i < j, GLOBAL ids in
    arrival order).

    Batch 0 runs the one-time build (``index.build_index`` — the only time
    sampling / anchor selection / partitioning execute) and emits its
    self-join pairs through the index's cached artifacts; every later batch
    goes through ``MetricIndex.insert_batch`` — only the delta is mapped,
    ΔR×R_old streams against the resident V lists and ΔR×ΔR self-joins
    under the updated member MBBs. The drift monitor rides along: a re-plan
    is a static permutation (pairs unchanged), and a re-sample-worthy drift
    rebuilds with this session's own ``cfg`` (the control plane the caller
    already chose).

    Exactness contract (tests/test_incremental.py): for a fixed seed and ANY
    split of R into batches, ``pairs`` after the last insert is
    byte-identical to ``join(R, cfg).pairs`` over the concatenated rows.
    """

    def __init__(
        self,
        cfg: JoinConfig,
        *,
        n_nodes: int = 4,
        n_devices: int | None = None,
        replan_drift: float | None = None,
        resample_drift: float | None = None,
    ):
        self.cfg = cfg
        self.n_nodes = n_nodes
        self.n_devices = n_devices
        self.replan_drift = replan_drift
        self.resample_drift = resample_drift
        self.index = None  # built lazily on the first non-empty batch
        self.stats: list = []  # one StreamStats per insert() call
        self._pairs = np.zeros((0, 2), np.int64)

    @property
    def pairs(self) -> np.ndarray:
        """Accumulated canonical pair set (sorted unique, global ids)."""
        return self._pairs

    @property
    def n_rows(self) -> int:
        return 0 if self.index is None else self.index.n_rows

    def insert(self, new_rows: Array | np.ndarray):
        """Absorb one insertion batch; returns (new_pairs, StreamStats)."""
        from repro.core import index as index_lib  # deferred: import cycle

        d_np = np.asarray(new_rows, np.float32)
        if self.index is None:
            if d_np.shape[0] == 0:
                # Nothing to build from yet — stay lazy, report a no-op.
                stats = index_lib.StreamStats(action="none")
                self.stats.append(stats)
                return np.zeros((0, 2), np.int64), stats
            bcfg = self.cfg
            if int(d_np.shape[0]) < bcfg.n_dims:
                # A tiny first batch can yield fewer distinct pivots than
                # mapped dimensions (row-fallback samplers cap pivots at B).
                # Clamping n_dims is free: exactness holds under ANY
                # containment-consistent plan, and a drift re-sample later
                # rebuilds with the full config once data exists.
                bcfg = dataclasses.replace(
                    bcfg, n_dims=max(1, int(d_np.shape[0]))
                )
            self.index = index_lib.build_index(
                d_np, bcfg,
                n_nodes=max(1, min(self.n_nodes, int(d_np.shape[0]))),
                n_devices=self.n_devices,
            )
            new_pairs = self.index.self_pairs()
            stats = index_lib.StreamStats(
                n_delta=int(d_np.shape[0]), n_resident=0,
                n_total=int(d_np.shape[0]),
                n_self_pairs=int(new_pairs.shape[0]),
                n_new_pairs=int(new_pairs.shape[0]),
                action="build",
            )
        else:
            new_pairs, stats = self.index.insert_batch(
                d_np,
                replan_drift=self.replan_drift,
                resample_drift=self.resample_drift,
                rebuild_cfg=self.cfg,
            )
        if new_pairs.shape[0]:
            self._pairs = np.unique(
                np.concatenate([self._pairs, new_pairs]), axis=0
            )
        self.stats.append(stats)
        return new_pairs, stats


def join_incremental(
    batches,
    cfg: JoinConfig,
    *,
    n_nodes: int = 4,
    n_devices: int | None = None,
    replan_drift: float | None = None,
    resample_drift: float | None = None,
) -> IncrementalJoin:
    """Run the streaming layer over an iterable of insertion batches and
    return the finished session (``.pairs`` is the accumulated canonical
    set, ``.stats`` the per-batch drift/telemetry trail, ``.index`` the
    live ``MetricIndex``). Equivalent to one ``IncrementalJoin`` with every
    batch ``insert``-ed in order — the convenience entry point benchmarks
    and tests use."""
    session = IncrementalJoin(
        cfg, n_nodes=n_nodes, n_devices=n_devices,
        replan_drift=replan_drift, resample_drift=resample_drift,
    )
    for b in batches:
        session.insert(b)
    return session


def brute_force_pairs(
    data: Array, delta: float, metric: str = "l1", s: Array | None = None
) -> np.ndarray:
    """Ground-truth pair list for tests and the chip smoke run (quadratic
    work, in device blocks — ``distances.oracle_pairs``).

    ``s=None``: self-join pairs (i, j), i < j. With ``s``: cross R×S pairs,
    column 0 indexing ``data`` (R), column 1 indexing ``s`` (S)."""
    return distances.oracle_pairs(data, delta, metric, s)
