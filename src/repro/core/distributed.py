"""Distributed SP-Join on a JAX device mesh (the paper's Spark pipeline,
re-derived as SPMD stages — DESIGN.md §2).

The paper's three phases map onto three jitted ``shard_map`` stages over the
``data`` mesh axis (each device along that axis is one "local node"):

  stage_stats    sampling phase stages 1–2 (Alg. 1 lines 1–4): per-shard
                 exponential-family MLE for every candidate family + chi-square
                 GoF, best-family selection by max confidence, then one
                 ``all_gather`` of the (2m+2)-float parameter packet per node —
                 the paper's "broadcast ⟨F_i(x), c_i⁰, N_i⟩" (line 5), O(M²)
                 scalars on the interconnect, *independent of k*.

  host control   the generative Gibbs chain runs identically on every host
  plane          from the gathered packets (zero sample bytes cross the
                 network — the paper's §4.2 claim, literally). Anchors,
                 labels, and the partition tree are built from those pivots,
                 all replicated deterministic work.

  stage_counts   one cheap counting pass: per-(cell, source-shard) |V| and |W|
                 counts, all-reduced. The host sizes the static dispatch
                 capacities from the *actual* counts (exact-fit planning pass,
                 a beyond-paper TPU adaptation: Spark shuffles dynamically;
                 XLA wants static shapes, so we buy exactness with one tiny
                 extra pass). The cost-model *predicted* capacity (paper
                 §5.1 / sample-scaled) is also computed and reported — the gap
                 between predicted and exact capacity is precisely the
                 sampling-quality metric the paper optimizes.

  stage_verify   map + reduce phases: the fused map kernel (one streamed
                 Pallas pass: anchor distances + kernel-cell assignment +
                 packed whole membership — ``kernels.ops.map_assign``;
                 ``map_fused=False`` keeps the legacy two-broadcast path),
                 capacity-bounded dispatch buffers, ONE ``all_to_all`` over
                 the data axis
                 (the shuffle — with ``prune="pivot"`` the mapped
                 coordinates ride it as trailing payload columns), then
                 per-local-cell blocked verification (pivot-filter L∞
                 pre-mask + Pallas pairdist + fused ≤ δ mask). Pair de-dup
                 happens in the mask epilogue via the min-cell rule.

  host placement the cost model's per-cell predicted loads (same pivot
  plan           sample) feed ``core.placement``'s cell→device planner; the
                 verify stage compiles with the plan's static slot
                 permutation and per-slot capacities (``placement=`` knob).

Skew economics on TPU: a skewed partition no longer straggles — it inflates
the static capacity every device must allocate and stream. The padding ratio
(Σ cap / Σ actual) is therefore the TPU-native analogue of the paper's
"curse of the last reducer", and it is exactly what better pivots shrink.
The placement plan attacks both sides: LPT balances per-device loads and
heavy-cell splitting bounds the worst slot the capacities are sized by
(docs/COST_MODEL.md).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import cost_model, distances, expfam, gof, mapping, partition, sampling, tracing
from repro.core import placement as placement_lib
from repro.core import verify as verify_lib
from repro.kernels import ops as kops
from repro.kernels import ref as kref

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# Compiled join stages, reused across joins
# ---------------------------------------------------------------------------

# The join's stages keyed by their static configuration: the mesh, the
# metric, the placement's routing tables and the capacities. The plan's
# anchors and boxes are arguments, so a second join of the same shapes
# under the same static configuration reuses every program. Least recently
# used first; the oldest stage goes when more than ``_STAGES_HELD`` are held.
_STAGES: dict[tuple, Any] = {}
_STAGES_HELD = 32


# The compact stage's first pair capacity per slot, for a configuration
# this process has not run yet. The survival estimate that sizes it is the
# pivot bound's, an overestimate of the hits: where the bound prunes
# nothing it reads the whole slot, and the compaction then costs as much as
# the slot's mask. An overflow reruns the stage at a capacity sized from
# the exact count, and that capacity is kept for the configuration's next
# joins.
_FIRST_PAIR_CAP = 1 << 16


def _remember(key: tuple, value) -> None:
    _STAGES.pop(key, None)
    while len(_STAGES) >= _STAGES_HELD:
        _STAGES.pop(next(iter(_STAGES)))
    _STAGES[key] = value


def _stage(key: tuple, build):
    fn = _STAGES.get(key)
    if fn is None:
        fn = build()
    _remember(key, fn)
    return fn


def clear_join_stages() -> int:
    """Drop every join stage held for reuse, and with them their compiled
    programs and the pair capacities that fitted earlier joins; returns
    how many entries there were. The serving stages of a ``DistIndex`` are
    its own and stay."""
    n = len(_STAGES)
    _STAGES.clear()
    return n


# ---------------------------------------------------------------------------
# Stage 1: per-shard stats + gather (sampling phase stages 1-2)
# ---------------------------------------------------------------------------


def _fit_all_families(x: Array, valid: Array, t_cells: int, backend: str):
    """Fit every candidate family on one shard; return (packed, conf) stacked
    per family. Families whose support excludes the data self-eliminate."""
    stats = expfam.suff_stats(x, valid)
    nonneg = jnp.all((x >= 0) | ~valid.astype(bool)[:, None])
    packed, confs = [], []
    for fam in expfam.FAMILIES:
        params = expfam.fit(fam, stats)
        u = expfam.cdf(params, x.astype(jnp.float32))
        nu = kops.histogram(u, t_cells, valid.astype(jnp.float32), backend=backend)
        n_eff = valid.astype(jnp.float32).sum()
        expected = jnp.maximum(n_eff / t_cells, 1e-9)
        k_star = (((nu - expected) ** 2) / expected).sum()
        m = x.shape[-1]
        # spjoin-lint: allow[host-sync] -- all Python ints (shape dim + static config), no tracer is concretized
        dof = jnp.maximum(float(m * (t_cells - params.n_params - 1)), 1.0)
        conf = gof.chi2_sf(k_star, dof)
        if fam in ("exponential", "gamma"):
            conf = jnp.where(nonneg, conf, 0.0)
        packed.append(expfam.pack(params))
        confs.append(conf)
    return jnp.stack(packed), jnp.stack(confs)  # (F, 2m+1), (F,)


def make_stage_stats(
    mesh: Mesh,
    axis: str,
    t_cells: int = 8,
    backend: str = "auto",
    use_kernel: bool | None = None,
):
    """Build the jitted stats stage. Input: global (N, m) data sharded on
    ``axis`` plus an (N,) validity mask. Output (replicated): per-node packed
    params (M, 2m+1), confidences (M,), counts (M,). Built once per static
    configuration (``_stage``)."""
    backend = kops.resolve_backend(backend, use_kernel=use_kernel)

    def build():
        def per_shard(x: Array, valid: Array):
            packed, confs = _fit_all_families(x, valid, t_cells, backend)
            best = jnp.argmax(confs)
            my_packet = packed[best]
            my_conf = confs[best]
            my_count = valid.astype(jnp.float32).sum()
            packets = jax.lax.all_gather(my_packet, axis)  # (M, 2m+1)
            conf_all = jax.lax.all_gather(my_conf, axis)  # (M,)
            count_all = jax.lax.all_gather(my_count, axis)  # (M,)
            return packets, conf_all, count_all

        shmap = jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        return jax.jit(shmap)

    return _stage(("stats", mesh, axis, t_cells, backend), build)


# ---------------------------------------------------------------------------
# Host control plane: replicated Gibbs + partition plan
# ---------------------------------------------------------------------------


def _packed_node_sample(packets: Array, key: jax.Array, e: Array) -> Array:
    """x ~ f_e with the family chosen by the *traced* id in packets[e, 0]."""
    v = packets[e]
    fid = v[0].astype(jnp.int32)

    def branch(fam):
        def f(key):
            return expfam.sample(expfam.unpack(v, fam), key, ())

        return f

    return jax.lax.switch(fid, [branch(f) for f in expfam.FAMILIES], key)


@functools.partial(jax.jit, static_argnames=("k", "length"))
def gibbs_from_packets(
    key: jax.Array, packets: Array, confs: Array, counts: Array, k: int, length: int
) -> tuple[Array, Array]:
    """Alg. 4 as a fixed-length scan over gathered packets (traced families).

    Deterministic in (key, packets): every host/device replays the identical
    chain, so pivots are replicated without communication. Acceptance runs
    on max-normalized confidences (scale-invariant for the C=1 branch; see
    sampling.gibbs_chain). Shortfall/zero-accept compaction is shared with
    the single-host chain (sampling._compact_accepted): tail slots repeat the
    first ACCEPTED row, and an all-rejected chain falls back to the raw
    draws with acceptance telemetry = 0.0 so the driver can warn."""
    conf = jnp.clip(confs.astype(jnp.float32), 1e-6, 1.0)
    conf = jnp.clip(conf / jnp.max(conf), 1e-3, 1.0)
    cnt = jnp.maximum(counts.astype(jnp.float32), 1.0)
    logw_c0 = jnp.log(cnt)
    logw_c1 = jnp.log(cnt) - jnp.log(conf)

    def step(c_prev, key):
        k_e, k_x, k_c = jax.random.split(key, 3)
        logw = jnp.where(c_prev == 1, logw_c1, logw_c0)
        e = jax.random.categorical(k_e, logw)
        x = _packed_node_sample(packets, k_x, e)
        c = (jax.random.uniform(k_c) < conf[e]).astype(jnp.int32)
        return c, (x, c)

    _, (xs, cs) = jax.lax.scan(step, jnp.int32(1), jax.random.split(key, length))
    return sampling._compact_accepted(xs, cs == 1, k)


@dataclasses.dataclass(frozen=True)
class JoinPlan:
    """Everything stage_verify needs, all replicated host-side artifacts."""

    anchors: Array  # (n, m)
    metric: str
    kernel_lo: Array  # (p, n)
    kernel_hi: Array
    whole_lo: Array
    whole_hi: Array
    delta: float
    p: int


def build_join_plan(
    key: jax.Array,
    pivots: Array,
    *,
    delta: float,
    metric: str = "l1",
    p: int = 16,
    n_dims: int = 8,
    partitioner: str = "learning",
    anchor_method: str = "fft",
    n_clusters: int | None = None,
    seed: int = 0,
) -> JoinPlan:
    smap = mapping.select_anchors(key, pivots, n_dims, metric, anchor_method)
    mapped = np.asarray(smap(pivots))
    labels = None
    if partitioner == "learning":
        d = np.asarray(distances.pairwise(pivots, pivots, metric))
        labels = partition.single_linkage_labels(d, n_clusters or 2 * p)
    plan = partition.build_partition(mapped, p, delta, partitioner, labels, seed)
    return JoinPlan(
        anchors=smap.anchors,
        metric=metric,
        kernel_lo=plan.kernel_lo,
        kernel_hi=plan.kernel_hi,
        whole_lo=plan.whole_lo,
        whole_hi=plan.whole_hi,
        delta=delta,
        p=p,
    )


def _plan_arrays(plan: JoinPlan) -> tuple[Array, ...]:
    """The plan's anchors and boxes, the arguments a join stage takes."""
    return tuple(
        jnp.asarray(a, jnp.float32)
        for a in (plan.anchors, plan.kernel_lo, plan.kernel_hi, plan.whole_lo, plan.whole_hi)
    )


def _traced_plan(arrays: tuple[Array, ...], metric: str, delta: float | None, p: int) -> JoinPlan:
    """The plan inside a stage, from its arguments and the static rest."""
    anchors, klo, khi, wlo, whi = arrays
    return JoinPlan(anchors, metric, klo, khi, wlo, whi, delta, p)


def _map_assign(plan: JoinPlan, x: Array, valid: Array, backend: str, fused: bool = True):
    """Space-map a shard and compute kernel cell + whole membership.

    ``fused=True`` (default) runs the single-pass ``kernels.ops.map_assign``
    op — anchor distances, cell id and the packed membership bitmask in one
    streamed kernel, no (n_loc, p, n) / (n_loc, p) HBM intermediates on the
    Pallas path. ``fused=False`` keeps the historical two-broadcast jnp path
    (the parity control — byte-identical outputs on fixed seeds).

    Also returns the mapped coordinates ``xm`` so callers that need them
    (the counting stage's MBB pass) don't recompute the pairdist."""
    if fused:
        xm, cells, bits = kops.map_assign(
            x, plan.anchors, plan.kernel_lo, plan.kernel_hi,
            plan.whole_lo, plan.whole_hi, plan.metric, backend=backend,
        )
        member = kops.unpack_membership(bits, plan.p)
    else:
        xm = kops.pairdist(x, plan.anchors, plan.metric, backend=backend)  # (n_loc, n)
        inside_k = (xm[:, None, :] >= plan.kernel_lo[None]) & (
            xm[:, None, :] < plan.kernel_hi[None]
        )
        cells = jnp.argmax(inside_k.all(-1), axis=1).astype(jnp.int32)
        member = (
            (xm[:, None, :] >= plan.whole_lo[None])
            & (xm[:, None, :] <= plan.whole_hi[None])
        ).all(-1)
    v = valid.astype(bool)
    return cells, member & v[:, None], v, xm


# ---------------------------------------------------------------------------
# Stage 2: counting pass (exact-fit capacity planning)
# ---------------------------------------------------------------------------


def make_stage_counts(
    mesh: Mesh,
    axis: str,
    plan: JoinPlan,
    backend: str = "auto",
    use_kernel: bool | None = None,
    fused: bool = True,
):
    """Returns jitted fn: (data, valid) ->
    (v_counts (M, p), w_counts (M, p), cell_lo (M, p, n), cell_hi (M, p, n)).

    The per-cell mapped-coordinate MBBs ride along for free (segment
    min/max): the host shrinks each WHOLE box to the δ-expanded MBB of the
    cell's actual members (§Perf H3-it1 — the paper's tighten trick applied
    distributed; Lemma 4 is preserved because every member stays inside its
    own cell's MBB).

    ``fused``: route the map pass through the single-pass
    ``kernels.ops.map_assign`` kernel (default) or the legacy two-broadcast
    jnp path (the benchmark/parity control).

    The plan's anchors and boxes are arguments of the compiled stage, so
    the tightened recount and later joins of the same shapes reuse it."""
    big = jnp.float32(partition.BIG)
    metric, p = plan.metric, plan.p
    backend = kops.resolve_backend(backend, metric, use_kernel)

    def build():
        def per_shard(arrays, x: Array, valid: Array):
            # The counting pass reads no δ: the boxes already hold it.
            traced = _traced_plan(arrays, metric, None, p)
            cells, member, v, xm = _map_assign(traced, x, valid, backend, fused)
            v_cnt = jnp.zeros((p,), jnp.int32).at[cells].add(v.astype(jnp.int32))
            w_cnt = member.sum(0).astype(jnp.int32)
            safe_cells = jnp.where(v, cells, p)  # invalid -> dropped
            lo = jnp.full((p + 1, xm.shape[1]), big).at[safe_cells].min(xm)[:p]
            hi = jnp.full((p + 1, xm.shape[1]), -big).at[safe_cells].max(xm)[:p]
            return (
                jax.lax.all_gather(v_cnt, axis),  # (M, p)
                jax.lax.all_gather(w_cnt, axis),
                jax.lax.all_gather(lo, axis),  # (M, p, n)
                jax.lax.all_gather(hi, axis),
            )

        shmap = jax.shard_map(
            per_shard, mesh=mesh, in_specs=(P(), P(axis), P(axis)),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
        return jax.jit(shmap)

    fn = _stage(("counts", mesh, axis, metric, p, backend, fused), build)
    return functools.partial(fn, _plan_arrays(plan))


# ---------------------------------------------------------------------------
# Stage 3: dispatch (all_to_all) + blocked verify
# ---------------------------------------------------------------------------


def _scatter_dispatch(
    rows: Array,  # (n_loc, m)
    ids: Array,  # (n_loc,) global ids
    cells_of_row: Array,  # (n_loc,) destination cell (or p = drop)
    own_cell: Array,  # (n_loc,) kernel cell of the row (carried for dedup)
    p: int,
    cap: int,
):
    """Scatter rows into a (p, cap, ...) buffer by (dest slot, intra-slot rank).

    ``cells_of_row`` is the destination DISPATCH SLOT of each row (the kernel
    cell under contiguous placement; the planner's permuted/slab slot under
    LPT — see ``core.placement``). Rows whose slot == p, or whose rank
    overflows cap, are dropped (mode=drop); the overflow count is returned so
    the caller can surface it. Vectorized, O(n_loc · p) for the rank
    computation (one cumsum per slot column)."""
    onehot = (cells_of_row[:, None] == jnp.arange(p)[None, :]).astype(jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - 1  # (n_loc, p)
    rank_of_row = jnp.take_along_axis(
        rank, jnp.clip(cells_of_row, 0, p - 1)[:, None], axis=1
    )[:, 0]
    slot_ok = (cells_of_row < p) & (rank_of_row < cap)
    cc = jnp.where(slot_ok, cells_of_row, p)  # p -> out of bounds -> dropped
    rr = jnp.clip(rank_of_row, 0, cap - 1)

    buf = jnp.zeros((p, cap, rows.shape[-1]), rows.dtype).at[cc, rr].set(
        rows, mode="drop"
    )
    buf_ids = jnp.full((p, cap), -1, jnp.int32).at[cc, rr].set(
        ids.astype(jnp.int32), mode="drop"
    )
    buf_cell = jnp.full((p, cap), -1, jnp.int32).at[cc, rr].set(
        own_cell.astype(jnp.int32), mode="drop"
    )
    overflow = ((cells_of_row < p) & (rank_of_row >= cap)).sum()
    return buf, buf_ids, buf_cell, overflow


@dataclasses.dataclass(frozen=True)
class _RoutingTables:
    """Static slot-routing tables of a placement plan, baked into stage
    traces. One construction shared by the join's verify stage and the
    serving stage (``make_stage_serve``) so the two can never disagree on
    how a cell maps to dispatch slots."""

    p: int
    n_slots: int
    first_slot: Array  # (p,) first slot of each cell
    n_slabs: Array  # (p,) V-slab count per cell
    disp_of_slot: Array  # (n_slots,) slot -> dispatch permutation
    w_col_of_disp: Array  # (n_slots,) membership gather column per dispatch
    #   index (padding slots -> the always-False extra column p)
    cell_id_of_disp: Array  # (n_slots,) original cell id, -1 = padding


def _routing_tables(pl: placement_lib.PlacementPlan) -> _RoutingTables:
    p = pl.p
    cell_of_disp_np = pl.cell_of_dispatch
    return _RoutingTables(
        p=p,
        n_slots=pl.n_slots,
        first_slot=jnp.asarray(pl.cell_first_slot, jnp.int32),
        n_slabs=jnp.asarray(pl.cell_n_slabs, jnp.int32),
        disp_of_slot=jnp.asarray(pl.dispatch_of_slot, jnp.int32),
        w_col_of_disp=jnp.asarray(
            np.where(cell_of_disp_np >= 0, cell_of_disp_np, p), jnp.int32
        ),
        cell_id_of_disp=jnp.asarray(cell_of_disp_np, jnp.int32),
    )


def _placement_key(pl: placement_lib.PlacementPlan) -> tuple:
    """What of a placement plan a stage compiles in: its routing tables."""
    return (pl.p, pl.n_slots) + tuple(
        tuple(np.asarray(a).tolist())
        for a in (pl.cell_first_slot, pl.cell_n_slabs, pl.dispatch_of_slot, pl.cell_of_dispatch)
    )


def _make_v_dispatch(rt: _RoutingTables, cap_v: int):
    """Each valid row -> its kernel cell's dispatch slot (a heavy cell's
    rows are dealt round-robin over its slabs by intra-cell rank)."""
    p, n_slots = rt.p, rt.n_slots

    def v_dispatch(x: Array, ids: Array, cells: Array, v: Array):
        v_cells = jnp.where(v, cells, p)
        safe = jnp.clip(v_cells, 0, p - 1)
        onehot = (v_cells[:, None] == jnp.arange(p)[None, :]).astype(jnp.int32)
        rank_in_cell = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - 1, safe[:, None], axis=1
        )[:, 0]
        slot = rt.first_slot[safe] + rank_in_cell % rt.n_slabs[safe]
        dest = jnp.where(v_cells < p, rt.disp_of_slot[slot], n_slots)
        return _scatter_dispatch(x, ids, dest, cells, n_slots, cap_v)

    return v_dispatch


def _make_w_dispatch(rt: _RoutingTables, cap_w: int):
    """Each valid row -> every whole-member cell's slot(s) — replicated into
    each slab of a split cell (ranked per dispatch slot)."""
    n_slots = rt.n_slots

    def w_dispatch(x: Array, ids: Array, cells: Array, member: Array):
        member_ext = jnp.concatenate(
            [member, jnp.zeros((member.shape[0], 1), member.dtype)], axis=1
        )
        member_d = member_ext[:, rt.w_col_of_disp]  # (n_loc, n_slots) disp order
        w_rank = jnp.cumsum(member_d.astype(jnp.int32), axis=0) - 1
        slot_ok = member_d & (w_rank < cap_w)
        cc = jnp.where(slot_ok, jnp.arange(n_slots)[None, :], n_slots)
        rr = jnp.clip(w_rank, 0, cap_w - 1)
        # Scatter row NUMBERS into the slot table, then gather the rows: a
        # payload scatter would broadcast every row to every slot first, an
        # (n_loc, n_slots, m) update that outgrows HBM at real sizes. Row
        # n_loc is the padding row (zeros, id -1, cell -1).
        n_loc = x.shape[0]
        src = (
            jnp.full((n_slots, cap_w), n_loc, jnp.int32)
            .at[cc, rr]
            .set(jnp.broadcast_to(jnp.arange(n_loc, dtype=jnp.int32)[:, None], cc.shape),
                 mode="drop")
        )
        w_buf = jnp.concatenate([x, jnp.zeros((1, x.shape[-1]), x.dtype)])[src]
        pad = jnp.full((1,), -1, jnp.int32)
        w_ids = jnp.concatenate([ids.astype(jnp.int32), pad])[src]
        w_own = jnp.concatenate([cells.astype(jnp.int32), pad])[src]
        overflow_w = (member_d & (w_rank >= cap_w)).sum()
        return w_buf, w_ids, w_own, overflow_w

    return w_dispatch


def _make_exchange(axis: str, M: int, spd: int):
    """The shuffle: ONE ``all_to_all`` over ``axis`` per buffer, plus the
    (M, spd, cap, ...) -> per-local-slot (spd, M·cap, ...) flattening."""

    def exchange(buf):
        # (n_slots, cap, ...) -> (M, spd, cap, ...) -> a2a -> received
        # from every source shard: (M, spd, cap, ...).
        shaped = buf.reshape(M, spd, *buf.shape[1:])
        return jax.lax.all_to_all(shaped, axis, split_axis=0, concat_axis=0)

    def flat(r):
        return jnp.moveaxis(r, 0, 1).reshape(spd, M * r.shape[2], *r.shape[3:])

    return exchange, flat


@dataclasses.dataclass(frozen=True)
class VerifyConfig:
    """Static knobs compiled into the verify stage.

    ``cap_v`` / ``cap_w``: per-(cell, source-shard) dispatch capacities — the
    static shapes the ``all_to_all`` buffers compile with (exact-fit planned
    by the counting pass, times ``capacity_slack``).
    ``prune``: "none" | "pivot" — pivot-filter pruning in the verify tiles.
    With "pivot" each row's mapped coordinates are concatenated onto its
    payload so they ride the SAME ``all_to_all`` as the data (n extra f32
    columns of shuffle volume), and the per-cell verification masks pairs
    whose L∞ lower bound exceeds δ before exact evaluation. Metrics without
    the triangle inequality (cosine, dot) resolve back to "none" —
    capability, not error (see ``core.verify.resolve_prune``).
    """

    cap_v: int  # per-(cell, source-shard) kernel-row capacity
    cap_w: int  # per-(cell, source-shard) whole-row capacity
    emit_pairs: bool = False  # also return hit masks + id buffers (tests)
    emit: str = "mask"  # pair-emission path when emit_pairs: "mask" returns
    #   the per-slot hit masks + id buffers; "compact" compacts each slot's
    #   hits in-trace, one slot at a time (ref.compact_mask under
    #   lax.map, so one slot's mask is live), into a static
    #   (pair_cap, 2) global-id buffer + true-count — an output-sensitive
    #   stage OUTPUT, not a new collective: the pairs ride the stage's
    #   existing out_specs, the all_to_all budget is unchanged. A count
    #   above pair_cap is the overflow sentinel (buffer unspecified, count
    #   exact); the driver re-sizes and re-runs, mask path as last resort.
    pair_cap: int = 0  # static per-slot pair capacity (emit="compact" only)
    backend: str = "auto"  # numpy | pallas | auto (see kernels.ops)
    use_kernel: bool | None = None  # legacy override of backend
    prune: str = "none"  # pivot-filter pruning: "none" | "pivot"
    delta_bound: float | None = None  # scale-aware fp band for the filter
    #   (verify.prune_band; None -> the scale-free ref.prune_delta default)
    map_fused: bool = True  # single-pass map kernel (False: legacy two-pass
    #   jnp broadcasts — the parity/benchmark control, byte-identical output)


def make_stage_verify(
    mesh: Mesh, axis: str, plan: JoinPlan, vcfg: VerifyConfig, cross: bool = False,
    pl: placement_lib.PlacementPlan | None = None,
):
    """The fused map+shuffle+reduce stage.

    Per shard: assign -> dispatch buffers keyed (dest slot, rank) ->
    all_to_all over ``axis`` -> per-local-slot masked blocked verification.

    Cell -> device is governed by ``pl`` (``core.placement``): dispatch slot
    ``d·spd + j`` lives on device ``d``. The default (``pl=None``) is the
    historical contiguous layout — cell h on device h // (p/M), identity
    permutation, no slabs; requires p % M == 0 (the driver rounds p up).
    Under an LPT plan the scatter targets are permuted through
    ``pl.dispatch_of_slot`` and a heavy cell's V rows are dealt round-robin
    over its slabs (W rows replicated into each slab) — same buffers, same
    single ``all_to_all``, byte-identical pair sets (each candidate pair
    lands in exactly one slab and every slab keeps the cell's original id
    for the de-dup rule).

    ``cross=False`` (self-join): V and W buffers are both scattered from the
    one data set; the min-cell de-dup rule applies. ``cross=True`` (R×S):
    the stage takes (xr, valid_r, ids_r, xs, valid_s, ids_s) — V buffers are
    scattered from R's shards (kernel cells), W buffers from S's shards
    (whole membership), one ``all_to_all`` each, and the de-dup rule
    degenerates to padding validity (each R row has a unique kernel cell).

    With ``vcfg.prune="pivot"`` the mapped coordinates (already computed by
    the in-stage ``_map_assign``) are appended to each row's payload before
    dispatch — the pivot distances ride the same ``all_to_all`` as the data
    — and split back off at the destination cell, where ``verify_tile``
    applies the L∞ pre-mask. Hit masks (hence emitted pairs) are identical
    to ``prune="none"``; the ``candidates`` output tracks how many pairs
    survived the filter (pruning-rate telemetry).
    """
    M = mesh.shape[axis]
    p = plan.p
    if pl is None:  # historical contiguous layout: cell h -> device h//(p/M)
        pl = placement_lib.plan_placement(
            np.zeros(p, np.float64), M, strategy="contiguous"
        )
    assert pl.p == p, f"placement planned for p={pl.p}, stage has p={p}"
    n_slots = pl.n_slots
    assert n_slots % M == 0, f"n_slots={n_slots} must be a multiple of {axis}={M}"
    spd = n_slots // M  # dispatch slots per device
    cap_v, cap_w = vcfg.cap_v, vcfg.cap_w
    map_fused = vcfg.map_fused
    metric, delta = plan.metric, plan.delta
    backend = kops.resolve_backend(vcfg.backend, metric, vcfg.use_kernel)
    if vcfg.prune == "window":
        # Host-streamed range pruning has no analogue inside a static
        # shard_map trace; the distributed stage filters per pair.
        raise ValueError('the distributed stage supports prune="none" | "pivot"')
    prune = verify_lib.resolve_prune(vcfg.prune, metric, True)
    emit = verify_lib.resolve_emit(vcfg.emit, metric) if vcfg.emit_pairs else "mask"
    if emit == "compact" and vcfg.pair_cap < 1:
        raise ValueError('emit="compact" needs pair_cap >= 1 (a static out-shape)')
    n_dims = plan.anchors.shape[0]
    delta_bound = vcfg.delta_bound  # static — shared by mask + telemetry

    def build():
        # Static routing tables + dispatch/shuffle closures (identity permutation
        # under contiguous placement) — shared with make_stage_serve.
        rt = _routing_tables(pl)
        cell_id_of_disp = rt.cell_id_of_disp
        v_dispatch = _make_v_dispatch(rt, cap_v)
        w_dispatch = _make_w_dispatch(rt, cap_w)
        exchange, flat = _make_exchange(axis, M, spd)

        def shuffle_and_verify(v_parts, w_parts, overflow):
            """ONE all_to_all per side over the data axis, then per-local-slot
            masked blocked verification."""
            fv, fvi, fvo = (flat(exchange(b)) for b in v_parts)
            fw, fwi, fwo = (flat(exchange(b)) for b in w_parts)

            my_dev = jax.lax.axis_index(axis)
            # De-dup runs against the slot's ORIGINAL cell id (slabs share it),
            # so placement can never change which pairs a cell emits.
            local_cells = cell_id_of_disp[my_dev * spd + jnp.arange(spd)]

            # Distances, threshold, padding validity, the de-dup rule and the
            # pivot filter all live in repro.core.verify — the same code path
            # the reference executor streams through.
            def verify_cell(vx, vids, vown, wx, wids, wown, cell_id):
                pv = pw = None
                if prune == "pivot":
                    # Mapped coords rode the payload's trailing n_dims columns.
                    vx, pv = vx[:, :-n_dims], vx[:, -n_dims:]
                    wx, pw = wx[:, :-n_dims], wx[:, -n_dims:]
                mask = verify_lib.verify_tile(
                    vx, wx, vids, wids, wown, cell_id,
                    delta=delta, metric=metric, backend=backend,
                    cross=cross, pv=pv, pw=pw, prune=prune,
                    delta_bound=delta_bound,
                )
                n_verified = verify_lib.pair_validity(vids, wids).sum()
                if prune == "pivot":
                    n_cand = verify_lib.candidate_mask(
                        pv, pw, vids, wids, delta, delta_bound
                    ).sum()
                else:
                    n_cand = n_verified
                return mask, n_verified, n_cand

            slots = (fv, fvi, fvo, fw, fwi, fwo, local_cells)
            if emit == "compact":
                # One slot at a time: only that slot's (M·cap_v, M·cap_w) mask
                # is ever live, and only its compacted pairs leave the loop —
                # under vmap every slot's mask would sit in HBM at once.
                # The masks are already validity- and de-dup-filtered
                # (verify_tile -> ref.emit_mask), so compaction just gathers
                # global ids.
                def compact_slot(slot):
                    mask, n_ver, n_cnd = verify_cell(*slot)
                    pairs, count = kref.compact_mask(mask, slot[1], slot[4], vcfg.pair_cap)
                    return pairs, count, n_ver, n_cnd

                cpairs, ccounts, n_verified, n_cand = jax.lax.map(compact_slot, slots)
                hit_count = ccounts.sum()
            else:
                masks, n_verified, n_cand = jax.vmap(verify_cell)(*slots)
                hit_count = masks.sum()
            out = {
                "hits": hit_count.astype(jnp.float32)[None],
                "verified": n_verified.sum().astype(jnp.float32)[None],
                "candidates": n_cand.sum().astype(jnp.float32)[None],
                # Per DISPATCH SLOT (== per cell under contiguous placement); the
                # host folds slabs back to cells and devices after the stage.
                "per_cell_verified": n_verified.astype(jnp.float32),
                "overflow": overflow.astype(jnp.float32)[None],
            }
            if vcfg.emit_pairs:
                if emit == "compact":
                    out["pairs"] = cpairs  # (spd, pair_cap, 2) int32, -1 padded
                    out["pair_counts"] = ccounts  # (spd,) int32 TRUE totals
                else:
                    out["masks"] = masks  # (spd, M*cap_v, M*cap_w)
                    out["v_ids"] = fvi
                    out["w_ids"] = fwi
            return out

        def payload(x: Array, xm: Array) -> Array:
            """Dispatch rows: the raw features, plus — under prune="pivot" — the
            mapped coordinates as trailing columns (same all_to_all, no second
            shuffle)."""
            if prune == "pivot":
                return jnp.concatenate([x, xm.astype(x.dtype)], axis=1)
            return x

        if cross:
            def per_shard(arrays, xr: Array, valid_r: Array, ids_r: Array,
                          xs: Array, valid_s: Array, ids_s: Array):
                traced = _traced_plan(arrays, metric, delta, p)
                cells_r, _, v_r, xm_r = _map_assign(traced, xr, valid_r, backend, map_fused)
                cells_s, member_s, _, xm_s = _map_assign(traced, xs, valid_s, backend, map_fused)
                v_buf, v_ids, v_own, overflow_v = v_dispatch(
                    payload(xr, xm_r), ids_r, cells_r, v_r
                )
                w_buf, w_ids, w_own, overflow_w = w_dispatch(
                    payload(xs, xm_s), ids_s, cells_s, member_s
                )
                return shuffle_and_verify(
                    (v_buf, v_ids, v_own), (w_buf, w_ids, w_own),
                    overflow_v + overflow_w,
                )
            in_specs = (P(),) + (P(axis),) * 6
        else:
            def per_shard(arrays, x: Array, valid: Array, ids: Array):
                traced = _traced_plan(arrays, metric, delta, p)
                cells, member, v, xm = _map_assign(traced, x, valid, backend, map_fused)
                rows = payload(x, xm)
                v_buf, v_ids, v_own, overflow_v = v_dispatch(rows, ids, cells, v)
                w_buf, w_ids, w_own, overflow_w = w_dispatch(rows, ids, cells, member)
                return shuffle_and_verify(
                    (v_buf, v_ids, v_own), (w_buf, w_ids, w_own),
                    overflow_v + overflow_w,
                )
            in_specs = (P(),) + (P(axis),) * 3

        out_specs = {
            "hits": P(axis),
            "verified": P(axis),
            "candidates": P(axis),
            "per_cell_verified": P(axis),
            "overflow": P(axis),
        }
        if vcfg.emit_pairs:
            if emit == "compact":
                out_specs.update({"pairs": P(axis), "pair_counts": P(axis)})
            else:
                out_specs.update({"masks": P(axis), "v_ids": P(axis), "w_ids": P(axis)})

        shmap = jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(shmap)

    stage = _stage(_verify_key(mesh, axis, plan, vcfg, cross, pl), build)
    return functools.partial(stage, _plan_arrays(plan))


def _verify_key(mesh: Mesh, axis: str, plan: JoinPlan, vcfg: VerifyConfig, cross: bool,
                pl: placement_lib.PlacementPlan) -> tuple:
    """What a verify stage compiles in besides its arguments' shapes."""
    return ("verify", mesh, axis, plan.metric, plan.p, float(plan.delta),
            plan.anchors.shape[0], vcfg, cross, _placement_key(pl))


# ---------------------------------------------------------------------------
# Driver: the end-to-end distributed join
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DistJoinResult:
    """Driver-level result + telemetry of one distributed join.

    ``n_verifications`` is the candidate pair area (Σ_h |V_h|·|W_h| over
    dispatched buffers — the paper's Fig. 12 metric, independent of prune
    mode); ``n_candidates`` is the subset surviving the pivot filter, i.e.
    the pairs that actually reach exact metric evaluation (== n_verifications
    when pruning is off).
    """

    n_hits: int
    n_verifications: int
    per_cell_verified: np.ndarray  # (p,) — Table 3 balance metric
    overflow: int
    capacity_padding: float  # Sigma cap / Sigma actual (TPU skew metric)
    predicted_cap_w: int  # cost-model capacity (sample-scaled)
    exact_cap_w: int
    node_confidences: np.ndarray
    accept_rate: float
    pairs: np.ndarray | None = None  # (n_pairs, 2) when emit_pairs; self-join
    #   columns are (min, max) over one set — R×S: (i ∈ R, j ∈ S)
    duplication: float = 0.0  # Σ_slots |W_slot| / |S| (|S|=N for self) — the
    #   ACTUAL S-side shuffle amplification: == the paper's Σ|W_h|/|S| under
    #   contiguous placement, and additionally counts the per-slab W replicas
    #   when heavy-cell splitting engages (splitting buys balance with bytes)
    n_candidates: int = 0  # pairs surviving the pivot filter (exact evals)
    pruning_rate: float = 0.0  # 1 − n_candidates / n_verifications
    predicted_survival: float = 1.0  # cost-model (sample-based) survival est.
    prune: str = "none"  # resolved prune mode the stage compiled with
    placement: str = "contiguous"  # cell→device strategy the stage compiled
    placement_plan: Any = None  # the core.placement.PlacementPlan (telemetry)
    device_loads: np.ndarray | None = None  # (M,) MEASURED verifications/dev
    balance_std: float = 0.0  # std of measured per-device loads (Table 3)
    makespan_ratio: float = 1.0  # max/mean of measured per-device loads
    capacity_saved_bytes: int = 0  # dispatch-buffer bytes the plan saved
    #   vs the contiguous global-max layout (negative = plan spends more)
    emit: str = "mask"  # pair-emission path the stage actually ran with
    #   (after capability resolution and any overflow fallback)
    n_overflow_retries: int = 0  # compact-emission stage re-runs forced by
    #   the overflow sentinel (same counter semantics as VerifyStats)
    backend: str = "numpy"  # resolved kernel backend of every stage
    map_fused: bool = True  # the stages mapped with the single-pass map kernel
    device_rows: np.ndarray | None = None  # (M,) V rows each device holds
    #   after the shuffle (dispatched, not padded)


def _pad_shard_set(x: Array, M: int, sharding) -> tuple[Array, Array, Array, int]:
    """Pad a set to a multiple of M rows (≥ M, so empty sets still shard),
    build validity + global-id vectors, and device_put all three."""
    n, m = x.shape
    pad = (-n) % M or (M if n == 0 else 0)
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, m), x.dtype)])
    valid = (jnp.arange(n + pad) < n).astype(jnp.float32)
    ids = jnp.arange(n + pad, dtype=jnp.int32)
    return (
        jax.device_put(x, sharding),
        jax.device_put(valid, sharding),
        jax.device_put(ids, sharding),
        n,
    )


def distributed_join(
    data: Array,
    *,
    mesh: Mesh,
    axis: str = "data",
    delta: float,
    metric: str = "l1",
    k: int = 1024,
    p: int | None = None,
    n_dims: int = 8,
    sampler: str = "generative",
    partitioner: str = "learning",
    t_cells: int = 8,
    emit_pairs: bool = False,
    emit: str = "mask",
    backend: str = "auto",
    use_kernel: bool | None = None,
    capacity_slack: float = 1.0,
    tighten: bool = True,
    prune: str = "pivot",
    map_fused: bool = True,
    placement: str = "lpt",
    seed: int = 0,
    s: Array | None = None,
) -> DistJoinResult:
    """End-to-end distributed join of ``data`` (N, m) on ``mesh``.

    Self-join by default. Pass ``s`` (N_s, m) for the two-set R×S join:
    ``data`` is R, ``s`` is S; per-set stats are gathered and pooled (2M
    "local nodes") so pivots cover both distributions, the counting pass and
    exact-fit capacities are computed per set (V capacity from R's kernel
    counts, W capacity from S's whole counts), and the verify stage scatters
    V buffers from R's shards and W buffers from S's — one ``all_to_all``
    each. ``emit_pairs`` then yields (i ∈ R, j ∈ S) pairs. Passing the same
    object as both (R = S aliasing) routes through the self-join path.

    ``sampler``: "generative" (default, Alg. 3/4) or "random" (baseline —
    pivots drawn uniformly from an all-gathered subsample, the prior-work
    scheme). "distribution" (Alg. 2) is intentionally routed through the
    single-host executor; its comm pattern (sample rows on the wire) is what
    the generative scheme was designed to remove.

    ``backend``: verify/mapping kernel dispatch ("numpy" | "pallas" | "auto");
    the legacy ``use_kernel`` bool overrides it when given. Unlike the
    single-host executor (whose verify engine falls back to the jnp path for
    kernel-less metrics), the distributed stages require a kernel metric on
    every path — fail fast with the supported set rather than deep in a
    shard_map trace.

    ``prune``: "pivot" (default) masks out candidate pairs whose L∞
    lower bound over the mapped coordinates exceeds δ before exact
    evaluation; the coordinates ride the dispatch ``all_to_all`` as trailing
    payload columns. Results are byte-identical to ``prune="none"`` — the
    bound never eliminates a true hit — and the pruning rate is reported in
    the result. Cosine (no triangle inequality) resolves back to "none".

    ``map_fused``: "pivot"-style toggle for the map phase — True (default)
    runs the single-pass fused map kernel in the counting and verify stages;
    False keeps the legacy two-broadcast jnp path. On the numpy backend the
    two are byte-identical (same XLA expressions); on the Pallas backend the
    coordinate fp low bits may differ at box edges, which can move an object
    between adjacent cells without ever changing the emitted pair set (the
    join is exact under any containment-consistent assignment).

    ``emit``: pair-emission path when ``emit_pairs`` — "mask" (default)
    reads back the per-slot hit masks and compacts on the host; "compact"
    compacts on device into static per-slot pair buffers sized from the
    cost model's survival estimate (``VerifyConfig.emit``) and retries at
    the next capacity bucket on overflow (the counter is exact), falling
    back to "mask" after a bounded number of retries. Pair sets are
    byte-identical either way; ``DistJoinResult.emit`` /
    ``n_overflow_retries`` report what actually ran.

    ``placement``: "lpt" (default) | "contiguous" — the cell→device plan of
    the reduce phase (``core.placement``). "contiguous" is the historical
    layout (cell h on device h // (p/M), one global worst-cell capacity);
    "lpt" plans a skew-aware assignment from the cost model's per-cell
    predicted loads (LPT bin packing + heavy-cell V-slab splitting) and
    sizes the static capacities from the planned per-slot loads. Pair sets
    are byte-identical under either — placement only moves work between
    devices. Plan + measured balance land in the result
    (``placement_plan``, ``device_loads``, ``balance_std``,
    ``makespan_ratio``, ``capacity_saved_bytes``).
    """
    if not kops.supports_kernel(metric):
        raise ValueError(
            f"distributed executor supports kernel metrics only ({kops.METRICS}); "
            f"got {metric!r} — use repro.core.spjoin for reference-path metrics"
        )
    if s is data:
        s = None  # R = S aliasing: the canonical semantics is the self-join
    if prune == "window":
        raise ValueError('distributed_join supports prune="none" | "pivot"')
    cross = s is not None
    backend = kops.resolve_backend(backend, metric, use_kernel)
    M = mesh.shape[axis]
    key = jax.random.PRNGKey(seed)
    n, m = data.shape
    p = p or 2 * M
    p = int(np.ceil(p / M) * M)
    with tracing.root("dist.join", rows=n, devices=M, p=p):
        with tracing.span("dist.put"):
            # Pre-padding host pools, only materialized for the random sampler
            # (the generative default never moves sample rows off-device).
            r_host = np.asarray(data) if sampler == "random" else None
            sharding = NamedSharding(mesh, P(axis))
            data, valid, ids, _ = _pad_shard_set(jnp.asarray(data), M, sharding)
            if cross:
                s_host = np.asarray(s) if sampler == "random" else None
                s_arr, valid_s, ids_s, n_s = _pad_shard_set(jnp.asarray(s), M, sharding)
            else:
                n_s = n

        # ---- sampling phase -------------------------------------------------
        with tracing.span("dist.sample"):
            stats_fn = make_stage_stats(mesh, axis, t_cells, backend)
            packets, confs, counts = jax.tree.map(np.asarray, stats_fn(data, valid))
            if cross:
                # S's shards are additional "local nodes": pool both sets'
                # packets so the replicated Gibbs chain samples from the R∪S
                # mixture.
                pk_s, cf_s, ct_s = jax.tree.map(np.asarray, stats_fn(s_arr, valid_s))
                packets = np.concatenate([packets, pk_s])
                confs = np.concatenate([confs, cf_s])
                counts = np.concatenate([counts, ct_s])
                # All-padding shards (|S| < M, or empty S) carry no distribution.
                keep = counts > 0
                packets, confs, counts = packets[keep], confs[keep], counts[keep]

            k_gibbs, k_anchor = jax.random.split(key)
            accept_rate = 1.0
            if sampler == "generative":
                conf_n = np.clip(confs / max(confs.max(), 1e-6), 1e-3, 1.0)
                c_min = float(np.clip(conf_n.min(), 0.05, 1.0))
                length = int(np.ceil(k / c_min * 1.5)) + 8
                pivots, acc = gibbs_from_packets(
                    k_gibbs, jnp.asarray(packets), jnp.asarray(confs), jnp.asarray(counts),
                    k, length,
                )
                accept_rate = float(acc)
                if accept_rate <= 0.0:
                    warnings.warn(
                        "gibbs_from_packets accepted no draws (all node confidences "
                        "≈ 0); pivots fall back to raw chain draws", stacklevel=2,
                    )
            elif sampler == "random":
                pool = np.concatenate([r_host, s_host]) if cross else r_host
                idx = jax.random.choice(
                    k_gibbs, pool.shape[0], shape=(min(k, pool.shape[0]),), replace=False
                )
                pivots = jnp.asarray(pool)[idx]
            else:
                raise ValueError(
                    f"distributed sampler must be generative|random, got {sampler!r}"
                )

        with tracing.span("dist.plan") as plan_span:
            # ---- control plane ----------------------------------------------
            plan = build_join_plan(
                k_anchor,
                pivots,
                delta=delta,
                metric=metric,
                p=p,
                n_dims=n_dims,
                partitioner=partitioner,
                seed=seed,
            )

            # ---- counting pass + capacity planning --------------------------
            # V capacities always come from R's kernel counts; W capacities
            # from the W-side set's whole counts (S when cross, R itself when
            # self).
            counts_fn = make_stage_counts(mesh, axis, plan, backend, fused=map_fused)
            v_cnt, w_cnt, cell_lo, cell_hi = jax.tree.map(
                np.asarray, counts_fn(data, valid)
            )  # (M, p[, n])
            if cross and not tighten:
                # With tighten the S recount below supersedes this pass entirely.
                _, w_cnt, _, _ = jax.tree.map(np.asarray, counts_fn(s_arr, valid_s))

            if tighten:
                # H3-it1: whole box := delta-expanded MBB of the cell's members.
                # Kernel-cell MBBs come from R (the V side) in both modes:
                # Lemma 4 puts every within-δ W partner inside the δ-expanded
                # R MBB.
                glo = cell_lo.min(0)  # (p, n) across shards
                ghi = cell_hi.max(0)
                empty = glo > ghi  # no members anywhere
                glo = np.where(empty, partition.BIG, glo)
                ghi = np.where(empty, -partition.BIG, ghi)
                plan = dataclasses.replace(
                    plan,
                    whole_lo=jnp.asarray(glo - plan.delta, jnp.float32),
                    whole_hi=jnp.asarray(ghi + plan.delta, jnp.float32),
                )
                # W counts changed: one cheap recount against the tightened
                # plan (kernel assignment — the V counts — is unaffected by
                # whole boxes). Same shapes, so the same compiled stage.
                counts_fn = make_stage_counts(mesh, axis, plan, backend, fused=map_fused)
                if cross:
                    _, w_cnt, _, _ = jax.tree.map(np.asarray, counts_fn(s_arr, valid_s))
                else:
                    v_cnt, w_cnt, _, _ = jax.tree.map(np.asarray, counts_fn(data, valid))

            # Cost-model prediction from the pivots alone (what a single-pass
            # system would have to provision) — reported for the EXPERIMENTS
            # Table 3 story, and the input of the placement planner below.
            piv_plan = partition.PartitionPlan(
                plan.kernel_lo, plan.kernel_hi, plan.whole_lo, plan.whole_hi, delta
            )
            piv_mapped = kops.pairdist(pivots, plan.anchors, metric, backend=backend)
            piv_cells = partition.assign_kernel(piv_plan, piv_mapped)
            piv_member = partition.whole_membership(piv_plan, piv_mapped)
            prune_resolved = verify_lib.resolve_prune(prune, metric, True)
            delta_bound = (
                verify_lib.prune_band(delta, metric, data, s_arr if cross else None)
                if prune_resolved == "pivot"
                else None
            )

            # ---- placement plan (cost-model-guided reduce placement) --------
            # Predicted per-cell verification loads (Eq. 33 costs from the
            # pivot sample, survival-adjusted — the fraction of candidate
            # pivot pairs surviving the L∞ bound forecasts the post-filter
            # exact-evaluation fraction) drive the cell→device plan; the
            # EXACT counting-pass counts, re-laid-out per planned slot, size
            # the static capacities — so placement never risks overflow, it
            # only moves work and shrinks the worst-slot capacity. In R×S
            # mode the W estimate scales with |S|, not |R|; caveat: the
            # pivots approximate the POOLED R∪S mixture, so when the two
            # distributions diverge the estimates are biased toward R's
            # geography — only the exact-count capacities govern
            # correctness; predicted_cap_w is the "single-pass provisioning"
            # story metric.
            cell_loads, predicted_survival, _, w_est = placement_lib.planner_inputs(
                np.asarray(piv_mapped), np.asarray(piv_cells), np.asarray(piv_member),
                n, n_s, delta, prune_resolved == "pivot",
            )
            predicted_cap_w = cost_model.predict_capacity(w_est, M, slack=1.25)
            pl = placement_lib.plan_placement(cell_loads, M, strategy=placement)
            v_slot, w_slot = placement_lib.slot_exact_counts(pl, v_cnt, w_cnt)
            exact_cap_v = max(int(v_slot.max(initial=0)), 1)
            exact_cap_w = max(int(w_slot.max(initial=0)), 1)
            cap_v = int(np.ceil(exact_cap_v * capacity_slack))
            cap_w = int(np.ceil(exact_cap_w * capacity_slack))
            cap_saved = placement_lib.capacity_saved_bytes(
                pl, v_cnt, w_cnt,
                placement_lib.dispatch_row_bytes(m, n_dims, prune_resolved == "pivot"),
                slack=capacity_slack,
            )

            # Compact emission: static per-slot pair capacity from the cost
            # model's survival estimate (an overestimate of the hit rate — the
            # safe direction), on the same quarter-pow2 bucket ladder as the
            # engine.
            emit_resolved = verify_lib.resolve_emit(emit, metric) if emit_pairs else "mask"
            vcfg = VerifyConfig(
                cap_v=cap_v, cap_w=cap_w, emit_pairs=emit_pairs, backend=backend,
                prune=prune, delta_bound=delta_bound, map_fused=map_fused,
                emit=emit_resolved,
            )
            fitted_key = ("pair_cap",) + _verify_key(mesh, axis, plan, vcfg, cross, pl)
            slot_area = max(int(v_slot.max(initial=0)) * int(w_slot.max(initial=0)), 1)
            if emit_resolved == "compact":
                est = int(slot_area * min(predicted_survival * verify_lib.EMIT_SLACK, 1.0))
                vcfg = dataclasses.replace(vcfg, pair_cap=min(
                    verify_lib.bucket_size(est + verify_lib._EMIT_FLOOR, slot_area),
                    _STAGES.get(fitted_key, _FIRST_PAIR_CAP),
                ))
            actual_v = int(v_slot.sum())  # dispatched rows (W counts slab replicas)
            actual_w = int(w_slot.sum())
            # Each slot verifies its V rows against its W rows, so the exact
            # counts give every device's load before the stage runs.
            planned_loads = np.bincount(
                pl.device_of_slot, weights=v_slot.sum(0) * w_slot.sum(0), minlength=M
            )
            plan_span.add(
                cap_v=cap_v, cap_w=cap_w, pair_cap=vcfg.pair_cap,
                duplication=float(actual_w / max(n_s, 1)),
                makespan_ratio=float(planned_loads.max() / max(planned_loads.mean(), 1e-9)),
            )

        # ---- dispatch + verify ----------------------------------------------
        n_overflow_retries = 0
        with tracing.span("dist.stage") as stage_span:
            for attempt in range(verify_lib._MAX_OVERFLOW_RETRIES + 2):
                verify_fn = make_stage_verify(mesh, axis, plan, vcfg, cross=cross, pl=pl)
                out = (
                    verify_fn(data, valid, ids, s_arr, valid_s, ids_s)
                    if cross
                    else verify_fn(data, valid, ids)
                )
                if vcfg.emit != "compact":
                    jax.block_until_ready(out)
                    break
                max_count = int(np.asarray(out["pair_counts"]).max(initial=0))
                if max_count <= vcfg.pair_cap:
                    break
                # Overflow sentinel: the counts are TRUE totals, so one
                # re-size is exact; a bounded ladder guards
                # monkeypatched/adversarial sizing, then the mask path —
                # emitted pairs are identical on every rung.
                n_overflow_retries += 1
                if attempt >= verify_lib._MAX_OVERFLOW_RETRIES:
                    vcfg = dataclasses.replace(vcfg, emit="mask", pair_cap=0)
                else:
                    vcfg = dataclasses.replace(
                        vcfg,
                        pair_cap=verify_lib.bucket_size(
                            max(max_count, 2 * vcfg.pair_cap), slot_area
                        ),
                    )
            if n_overflow_retries and vcfg.emit == "compact":
                _remember(fitted_key, max(vcfg.pair_cap, _STAGES.get(fitted_key, 0)))
            stage_span.add(overflow_retries=n_overflow_retries)

        with tracing.span("dist.readback") as readback:
            out = jax.device_get(out)
            n_hits = int(out["hits"].sum())
            readback.add(n_hits=n_hits)

        # Per-slot telemetry (dispatch order) folds back to cells and devices.
        per_slot = out["per_cell_verified"].reshape(-1)  # (n_slots,)
        cod = pl.cell_of_dispatch
        per_cell = np.zeros(p, np.float32)
        np.add.at(per_cell, cod[cod >= 0], per_slot[cod >= 0])
        device_loads = per_slot.reshape(M, -1).sum(1)
        padding = (pl.n_slots * M * (cap_v + cap_w)) / max(actual_v + actual_w, 1)

        pairs = None
        with tracing.span("dist.merge") as merge:
            if emit_pairs and vcfg.emit == "compact":
                # (M*spd, pair_cap, 2) compacted global-id pairs + per-slot
                # counts; rows past each slot's count are -1 padding (or,
                # pre-retry, unspecified) and are sliced off here.
                cpairs = out["pairs"].reshape(-1, vcfg.pair_cap, 2)
                ccounts = out["pair_counts"].reshape(-1)
                rows = [cp[:c] for cp, c in zip(cpairs, ccounts) if c]
                if rows:
                    pr = np.concatenate(rows).astype(np.int64)
                    if not cross:
                        pr = np.stack([pr.min(axis=1), pr.max(axis=1)], 1)
                    pairs = np.unique(pr, axis=0)
                else:
                    pairs = np.zeros((0, 2), np.int64)
            elif emit_pairs:
                masks = out["masks"]  # (M*spd, Mcap_v, Mcap_w) flattened over devices
                v_ids = out["v_ids"].reshape(masks.shape[0], -1)
                w_ids = out["w_ids"].reshape(masks.shape[0], -1)
                masks = masks.reshape(masks.shape[0], v_ids.shape[1], w_ids.shape[1])
                cell, vi, wi = np.nonzero(masks)
                gi = v_ids[cell, vi]
                gj = w_ids[cell, wi]
                if cross:
                    pr = np.stack([gi, gj], 1)  # columns index different sets
                else:
                    pr = np.stack([np.minimum(gi, gj), np.maximum(gi, gj)], 1)
                pairs = (
                    np.unique(pr, axis=0).astype(np.int64) if pr.size
                    else np.zeros((0, 2), np.int64)
                )
            merge.add(n_pairs=0 if pairs is None else int(pairs.shape[0]))

    n_verifications = int(out["verified"].sum())
    n_candidates = int(out["candidates"].sum())
    return DistJoinResult(
        n_hits=n_hits,
        n_verifications=n_verifications,
        per_cell_verified=per_cell,
        overflow=int(out["overflow"].sum()),
        capacity_padding=float(padding),
        predicted_cap_w=int(predicted_cap_w),
        exact_cap_w=exact_cap_w,
        node_confidences=confs,
        accept_rate=accept_rate,
        pairs=pairs,
        duplication=float(actual_w / max(n_s, 1)),
        n_candidates=n_candidates,
        pruning_rate=float(1.0 - n_candidates / max(n_verifications, 1)),
        predicted_survival=float(predicted_survival),
        prune=prune_resolved,
        placement=placement,
        placement_plan=pl,
        device_loads=device_loads,
        balance_std=float(device_loads.std()),
        makespan_ratio=float(device_loads.max() / max(device_loads.mean(), 1e-9)),
        capacity_saved_bytes=int(cap_saved),
        emit=vcfg.emit if emit_pairs else "mask",
        n_overflow_retries=n_overflow_retries,
        backend=backend,
        map_fused=map_fused,
        device_rows=np.bincount(
            pl.device_of_slot, weights=v_slot.sum(0), minlength=M
        ).astype(np.int64),
    )


# ---------------------------------------------------------------------------
# Query serving: pinned V buffers + W-side-only dispatch (core.index backend)
# ---------------------------------------------------------------------------


def make_stage_serve(
    mesh: Mesh,
    axis: str,
    qplan: JoinPlan,
    pl: placement_lib.PlacementPlan,
    *,
    cap_w: int,
    pair_cap: int,
    backend: str,
    prune: str,
    delta_bound: float | None = None,
    map_fused: bool = True,
):
    """The query phase of a persistent index: verify a query batch against
    V buffers that are ALREADY RESIDENT per device (``DistIndex`` pins them
    once at build) — only the queries move.

    Per shard: the same fused map-assign as the join's map phase routes the
    local queries to their whole-member cells under the δ-expanded query
    boxes, the shared W-dispatch scatters them (coords ride as trailing
    payload columns under the pivot filter), ONE ``all_to_all`` over
    ``axis``, then per-local-slot ``verify_tile`` in R×S mode against the
    pinned V slots. No sampling, no partitioning, zero V-side bytes on the
    wire per batch.

    The device's hit masks never leave it: they are compacted in the same
    program (``kref.select_hits``) into a static (``pair_cap``, 2) int32
    buffer of global (R id, query id) pairs in row-major (slot, v, w)
    order, padded with -1, beside the TRUE hit count. A count above
    ``pair_cap`` is the overflow sentinel (the buffer is then unspecified,
    the count exact); ``DistIndex`` reruns the batch at a capacity it fits.

    The routing tables, W dispatch and shuffle closures are the exact ones
    ``make_stage_verify`` compiles with (module-level factories), so serving
    and the one-shot join can never disagree on slot semantics.
    """
    M = mesh.shape[axis]
    rt = _routing_tables(pl)
    n_slots = rt.n_slots
    assert n_slots % M == 0, f"n_slots={n_slots} must be a multiple of {axis}={M}"
    spd = n_slots // M
    n_dims = qplan.anchors.shape[0]
    cell_id_of_disp = rt.cell_id_of_disp
    w_dispatch = _make_w_dispatch(rt, cap_w)
    exchange, flat = _make_exchange(axis, M, spd)

    def per_shard(fv: Array, fvi: Array, q: Array, valid: Array, ids: Array):
        # fv: (spd, cap_v, m[+n]) this device's pinned V slots (dispatch
        # order); fvi: (spd, cap_v) their global R ids (pad = -1).
        cells_q, member_q, _, qm = _map_assign(qplan, q, valid, backend, map_fused)
        rows = (
            jnp.concatenate([q, qm.astype(q.dtype)], axis=1)
            if prune == "pivot"
            else q
        )
        w_buf, w_ids, w_own, overflow = w_dispatch(rows, ids, cells_q, member_q)
        fw = flat(exchange(w_buf))
        fwi = flat(exchange(w_ids))
        fwo = flat(exchange(w_own))

        my_dev = jax.lax.axis_index(axis)
        local_cells = cell_id_of_disp[my_dev * spd + jnp.arange(spd)]

        def verify_slot(vx, vids, wx, wids, wown, cell_id):
            pv = pw = None
            if prune == "pivot":
                vx, pv = vx[:, :-n_dims], vx[:, -n_dims:]
                wx, pw = wx[:, :-n_dims], wx[:, -n_dims:]
            mask = verify_lib.verify_tile(
                vx, wx, vids, wids, wown, cell_id,
                delta=qplan.delta, metric=qplan.metric, backend=backend,
                cross=True, pv=pv, pw=pw, prune=prune,
                delta_bound=delta_bound,
            )
            return mask, verify_lib.pair_validity(vids, wids).sum()

        masks, n_verified = jax.vmap(verify_slot)(fv, fvi, fw, fwi, fwo, local_cells)
        cap_v = masks.shape[1]
        rows, w, count = kref.select_hits(masks.reshape(-1, masks.shape[2]), pair_cap)
        slot, v = rows // cap_v, rows % cap_v
        pairs = jnp.stack([fvi[slot, v], fwi[slot, w]], axis=1)
        fits = jnp.arange(pair_cap) < count
        return {
            "pairs": jnp.where(fits[:, None], pairs, -1),  # (pair_cap, 2)
            "count": count[None],  # TRUE hits, > pair_cap on overflow
            "hits": count.astype(jnp.float32)[None],
            "verified": n_verified.sum().astype(jnp.float32)[None],
            "overflow": overflow.astype(jnp.float32)[None],
        }

    shmap = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(axis),) * 5,
        out_specs={
            "pairs": P(axis), "count": P(axis), "hits": P(axis),
            "verified": P(axis), "overflow": P(axis),
        },
        check_vma=False,
    )
    return jax.jit(shmap)


@dataclasses.dataclass
class DistIndex:
    """A ``core.index.MetricIndex`` pinned on a device mesh for serving.

    ``from_index`` lays the indexed set's rows out per placement slot
    (slabs deal V rows round-robin by intra-cell rank, exactly like the
    join's V dispatch), device_puts the buffers sharded over ``axis`` ONCE,
    and re-plans placement (cheap: a static permutation from the stored
    cost-model loads — no re-sampling, no re-partitioning) when the mesh
    size differs from the plan the index was built for. Every
    ``query_batch`` after that moves only query bytes: one fused map pass,
    one W-side ``all_to_all``, per-slot tiled verification against the
    resident V buffers. See docs/SERVING.md for the lifecycle.
    """

    index: Any  # the host MetricIndex (duck-typed; no import cycle)
    mesh: Mesh
    axis: str
    pl: placement_lib.PlacementPlan  # re-planned for this mesh if needed
    backend: str  # resolved concrete backend
    prune: str  # resolved prune mode
    cap_v: int
    fv: Array  # (n_slots, cap_v, m[+n]) pinned V payload, dispatch order,
    #   sharded over ``axis`` on dim 0
    fv_ids: Array  # (n_slots, cap_v) int32 global R ids, same layout
    _x_abs: float  # max |payload| of the indexed set (prune-band input)
    _stages: dict = dataclasses.field(default_factory=dict, repr=False)
    # Per-device pair capacity of the serve stage: a power of two that only
    # grows, to the first one at least twice a batch's count that overflowed
    # it, so repeat batches reuse the compiled stage.
    _pair_cap: int = 1024

    @property
    def n_devices(self) -> int:
        return self.mesh.shape[self.axis]

    @classmethod
    def from_index(cls, index: Any, mesh: Mesh, axis: str = "data") -> "DistIndex":
        if not kops.supports_kernel(index.metric):
            raise ValueError(
                f"distributed serving supports kernel metrics only "
                f"({kops.METRICS}); got {index.metric!r} — query the host "
                f"MetricIndex directly for reference-path metrics"
            )
        M = mesh.shape[axis]
        backend = kops.resolve_backend(index.backend, index.metric)
        if index.prune == "window":
            raise ValueError('distributed serving supports prune="none" | "pivot"')
        prune = verify_lib.resolve_prune(index.prune, index.metric, True)
        pl = index.placement
        if pl.n_devices != M:
            # Cheap re-plan: same cost-model loads, new device count — a
            # static permutation, never a rebuild (docs/SERVING.md).
            pl = placement_lib.plan_placement(
                pl.cell_loads, M, strategy=index.placement_strategy
            )
        payload = (
            np.concatenate([index.data, index.coords.astype(index.data.dtype)], axis=1)
            if prune == "pivot"
            else index.data
        )
        # Slot layout (slot order): slab j of cell h takes the cell's rows
        # with intra-cell rank ≡ j (mod n_slabs) — the V-dispatch deal.
        slot_rows = []
        for slot in range(pl.n_slots):
            cell = int(pl.slot_cell[slot])
            if cell < 0:
                slot_rows.append(np.zeros(0, np.int64))
                continue
            rows = index.v_lists[cell]
            s = int(pl.cell_n_slabs[cell])
            slot_rows.append(rows[int(pl.slot_slab[slot])::s])
        cap_v = max(1, max(r.size for r in slot_rows))
        buf = np.zeros((pl.n_slots, cap_v, payload.shape[1]), np.float32)
        ids = np.full((pl.n_slots, cap_v), -1, np.int32)
        for slot, rows in enumerate(slot_rows):
            buf[slot, : rows.size] = payload[rows]
            ids[slot, : rows.size] = rows
        # Slot order -> dispatch order: device d owns dispatch d·spd .. — the
        # same addressing every stage's all_to_all output uses.
        disp = pl.dispatch_of_slot
        buf_d = np.empty_like(buf)
        ids_d = np.empty_like(ids)
        buf_d[disp] = buf
        ids_d[disp] = ids
        sharding = NamedSharding(mesh, P(axis))
        return cls(
            index=index,
            mesh=mesh,
            axis=axis,
            pl=pl,
            backend=backend,
            prune=prune,
            cap_v=cap_v,
            fv=jax.device_put(jnp.asarray(buf_d), sharding),
            fv_ids=jax.device_put(jnp.asarray(ids_d), sharding),
            _x_abs=float(np.abs(payload).max(initial=0.0)),
        )

    def _stage(self, delta: float, cap_w: int, delta_bound: float | None, pair_cap: int):
        key = (float(delta), int(cap_w), delta_bound, int(pair_cap))
        fn = self._stages.get(key)
        if fn is None:
            idx = self.index
            qlo, qhi = idx.query_boxes(delta)
            qplan = JoinPlan(
                anchors=jnp.asarray(idx.anchors),
                metric=idx.metric,
                kernel_lo=jnp.asarray(idx.kernel_lo),
                kernel_hi=jnp.asarray(idx.kernel_hi),
                whole_lo=jnp.asarray(qlo),
                whole_hi=jnp.asarray(qhi),
                delta=float(delta),
                p=idx.p,
            )
            fn = make_stage_serve(
                self.mesh, self.axis, qplan, self.pl,
                cap_w=cap_w, pair_cap=pair_cap, backend=self.backend, prune=self.prune,
                delta_bound=delta_bound, map_fused=idx.map_fused,
            )
            self._stages[key] = fn
        return fn

    def query_batch(
        self, q: Array | np.ndarray, delta: float | None = None
    ) -> np.ndarray:
        """Batched δ-range query over the mesh: (i ∈ R, j ∈ Q) pairs with
        D ≤ δ, byte-identical to the host index's ``query_batch`` (and hence
        to ``distances.brute_force_join``). Only query bytes move."""
        idx = self.index
        delta = idx.delta if delta is None else float(delta)
        q_np = np.asarray(q, np.float32)
        if q_np.shape[0] == 0:
            return np.zeros((0, 2), np.int64)
        M = self.n_devices
        with tracing.root("serve.query_batch", n_queries=int(q_np.shape[0])):
            with tracing.span("serve.put"):
                sharding = NamedSharding(self.mesh, P(self.axis))
                q_arr, valid, ids, _ = _pad_shard_set(jnp.asarray(q_np), M, sharding)

            # Exact-fit W capacity from a host routing pass (same fused map path
            # as the stage, so counts can never disagree), quantized up to a
            # power of two so repeat batches reuse the compiled stage.
            with tracing.span("serve.route") as route:
                _, member = idx.route(q_np, delta)
                n_tot = int(q_arr.shape[0])
                per = n_tot // M
                mem_pad = np.zeros((n_tot, idx.p), bool)
                mem_pad[: q_np.shape[0]] = member
                w_cnt = mem_pad.reshape(M, per, idx.p).sum(1)  # (M, p)
                w_slot = w_cnt[:, np.clip(self.pl.slot_cell, 0, None)]
                w_slot[:, self.pl.slot_cell < 0] = 0
                exact = int(w_slot.max(initial=1))
                cap_w = 1 << max(exact - 1, 1).bit_length()  # next pow2, ≥ 2
                route.add(n_routed=int(w_cnt.sum()), cap_w=cap_w)

            batch = (q_np, q_arr, valid, ids)
            counts, buf, pair_cap = self._run_stage(batch, delta, cap_w, 0)
            top = int(counts.max())
            if top > pair_cap:
                # The count is exact: one rerun at the next power of two
                # ≥ 2·count always fits.
                self._pair_cap = max(self._pair_cap, 1 << (2 * top - 1).bit_length())
                counts, buf, pair_cap = self._run_stage(batch, delta, cap_w, 1)

            with tracing.span("serve.unpack") as unpack:
                buf = buf.reshape(M, pair_cap, 2)
                pr = np.concatenate([b[:c] for b, c in zip(buf, counts)]).astype(np.int64)
                # Sorted unique (i, j) rows, through one int64 key a pair: a
                # 1-D unique, where np.unique(axis=0) sorts rows as bytes.
                n_q = int(q_arr.shape[0])
                key = np.unique(pr[:, 0] * n_q + pr[:, 1])
                pairs = np.stack([key // n_q, key % n_q], axis=1)
                unpack.add(n_hits=int(counts.sum()), n_pairs=int(pairs.shape[0]))
        return pairs

    def _run_stage(
        self, batch: tuple, delta: float, cap_w: int, retries: int
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Dispatch the serve stage at the current pair capacity (clamped to
        a device's mask elements) and read back, in one copy, its overflow
        flags, per-device hit counts and pair buffers."""
        q_np, q_arr, valid, ids = batch
        elems = self.pl.n_slots * self.cap_v * cap_w  # a device's mask elements
        pair_cap = min(self._pair_cap, elems)
        with tracing.span("serve.stage") as stage:
            delta_bound = None
            if self.prune == "pivot":
                # Scale-aware fp band; the query magnitude is quantized up to
                # a power of two so the (static) band doesn't recompile per
                # batch.
                q_abs = float(np.abs(q_np).max(initial=0.0))
                q_pow = float(2.0 ** np.ceil(np.log2(max(q_abs, 1e-9))))
                x_abs = max(self._x_abs, q_pow)
                delta_bound = kref.prune_delta(
                    delta, self.index.metric, x_abs, int(self.index.data.shape[1])
                )
            n_stages = len(self._stages)
            fn = self._stage(delta, cap_w, delta_bound, pair_cap)
            stage.add(compiled=int(len(self._stages) > n_stages), pair_retries=retries)
            out = fn(self.fv, self.fv_ids, q_arr, valid, ids)

        with tracing.span("serve.readback") as readback:
            overflow, counts, buf = jax.device_get((out["overflow"], out["count"], out["pairs"]))
            assert int(overflow.sum()) == 0, "serve W overflow"
            readback.add(mask_elems=self.n_devices * elems, pair_cap=pair_cap)
        return counts, buf, pair_cap

    def _repin(self) -> None:
        """Re-lay the host index out on the mesh after an absorb (or a
        drift-triggered re-plan/rebuild): fresh slot buffers, fresh routing
        plan, and — critically — a cleared stage cache, because the query
        boxes the serve stage compiled with are baked into its trace and the
        absorb just grew them."""
        fresh = DistIndex.from_index(self.index, self.mesh, self.axis)
        self.pl = fresh.pl
        self.backend = fresh.backend
        self.prune = fresh.prune
        self.cap_v = fresh.cap_v
        self.fv = fresh.fv
        self.fv_ids = fresh.fv_ids
        self._x_abs = fresh._x_abs
        self._stages.clear()

    def insert_batch(
        self,
        new_rows: Array | np.ndarray,
        *,
        replan_drift: float | None = None,
        resample_drift: float | None = None,
        rebuild_cfg=None,
    ):
        """Distributed mirror of ``MetricIndex.insert_batch``: same control
        flow, same drift monitor, byte-identical pair set — but the ΔR×R_old
        cross verify rides the serve stage, so only delta bytes cross the
        interconnect (one W-side ``all_to_all``) while the resident V
        buffers stay pinned. The ΔR×ΔR self-join and the index update run on
        the replicated host control plane (they touch only delta-sized
        state), then the grown index is re-pinned.

        Returns ``(new_pairs, StreamStats)`` exactly like the host method;
        global ids, i < j, sorted unique.
        """
        pairs, stats = self.index.insert_batch(
            new_rows,
            replan_drift=replan_drift,
            resample_drift=resample_drift,
            rebuild_cfg=rebuild_cfg,
            _cross_pairs_fn=self.query_batch,
        )
        self._repin()
        return pairs, stats
