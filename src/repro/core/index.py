"""Persistent metric index: build once, query millions (serving phase).

Every call to ``spjoin.join`` / ``distributed_join`` re-runs the whole
pipeline — sampling, GoF fits, anchor selection, the partition tree, the
placement plan — which is correct for one batch join and wrong for serving
query traffic. This module splits the pipeline into an explicit **build
phase** and a **query phase** (the DIMS three-stage shape — arXiv
2410.05091 — mapped onto our artifacts):

  build  (once)   sampling → anchors → kernel boxes → per-cell member MBBs
                  → cost-model placement plan → cached mapped coordinates
                  and per-cell V row lists of the indexed set R.
  query  (hot)    a batch of query points Q is routed through the SAME
                  fused map-assign kernel as the join's map phase — each
                  query's anchor distances (its mapped coordinates) are
                  computed exactly once and reused twice: first as the box
                  containment test that routes it to only the owning cells
                  (Lemma 4), then as the pivot-filter coordinates that
                  prune candidate pairs before exact evaluation
                  (``core.verify`` candidate mask). Verification streams
                  through the tiled verify engine in R×S mode (V = the
                  pinned index cells, W = the routed queries) without ever
                  re-sampling, re-fitting or re-partitioning.

δ at query time: the index stores the *pre-expansion* base boxes (the
tightened member MBB of each cell, or the kernel box when ``tighten=False``)
and expands them by the QUERY radius on the way in, so any ``delta`` — equal
to, below, or above the build-time default — answers exactly (Lemma 4 holds
for whatever radius the boxes were expanded by). The build-time δ is only the
default radius and the one the placement plan was costed at; see
docs/SERVING.md for the re-plan vs rebuild trade-off.

On-disk format (``index.save(path)`` / ``MetricIndex.load(path)``): a
directory holding ``manifest.json`` (format name + version, the build
config, array shapes, the placement summary) and ``arrays.npz`` (every
array, bit-exact). The manifest is validated first: an unknown format or a
version this code does not speak fails loudly (``IndexFormatError``), and a
manifest disagreeing with the caller's expected metric / δ / pivot count
fails with ``IndexMismatchError`` instead of silently mis-answering —
worked example in docs/SERVING.md.

The distributed serving path (``index.to_distributed(mesh)`` →
``core.distributed.DistIndex``) pins the per-slot V buffers on devices once
and serves query batches through the verify stage's slot machinery — one
W-side ``all_to_all`` per batch, zero R-side bytes moved after build.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model, distances, mapping, partition, spjoin, tracing
from repro.core import placement as placement_lib
from repro.core import verify as verify_lib
from repro.kernels import ops as kops

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids an import cycle
    from repro.core.distributed import DistIndex

Array = jnp.ndarray

FORMAT_NAME = "spjoin-metric-index"
# Version 2 adds the incremental-insert state: the manifest's "incremental"
# block (n_base / n_inserted / n_batches) and the observed_w drift telemetry
# array. Version-1 artifacts predate insert_batch and are refused (re-save
# with current code) — silently defaulting the counters would let a
# save→insert→load→insert round trip diverge from the unsaved session.
FORMAT_VERSION = 2

# Arrays persisted bit-exact in arrays.npz (name -> MetricIndex attribute).
_ARRAYS = (
    "data", "coords", "cells", "pivots", "anchors",
    "kernel_lo", "kernel_hi", "box_lo", "box_hi", "observed_w",
)
_PLAN_ARRAYS = (
    "cell_loads", "cell_first_slot", "cell_n_slabs",
    "slot_cell", "slot_slab", "slot_load", "dispatch_of_slot",
)


class IndexFormatError(ValueError):
    """The on-disk artifact is not a metric index this code can read."""


class IndexMismatchError(ValueError):
    """The manifest disagrees with the caller's expected query config."""


@dataclasses.dataclass
class QueryStats:
    """Telemetry of one ``query_batch`` call (the serving analogue of
    ``VerifyStats`` — which it embeds as ``verify``)."""

    n_queries: int = 0
    n_routed: int = 0  # Σ per-query owning-cell memberships (dispatch fan-out)
    n_cells_touched: int = 0  # cells that received ≥ 1 query
    route_s: float = 0.0  # map-assign + membership time
    verify_s: float = 0.0  # tiled engine time
    verify: verify_lib.VerifyStats | None = None

    @property
    def duplication(self) -> float:
        """Σ memberships / |Q| — the query-side routing amplification
        (the serving analogue of the shuffle metric Σ|W_h|/|S|)."""
        return self.n_routed / max(self.n_queries, 1)


@dataclasses.dataclass
class StreamStats:
    """Telemetry of one ``insert_batch`` call — the streaming analogue of
    ``QueryStats``, plus the drift monitor's decision trail.

    ``drift`` is ``cost_model.load_drift`` between the placement plan's
    predicted per-cell loads and the loads observed so far; ``action`` is
    what actually fired ("none" | "replan" | "resample"; the session layer
    also stamps "build" on the first batch). ``resample_due`` flags a drift
    past the re-sample threshold when no rebuild config was supplied — the
    cheap re-plan ran instead and the caller should rebuild when it can.
    ``balance_std_before``/``after`` score the plan in force before/after
    the action on the SAME observed loads (``placement.device_loads_under``),
    so a re-plan's improvement is directly visible.
    """

    n_delta: int = 0  # rows in this insertion batch
    n_resident: int = 0  # rows resident before the insert
    n_total: int = 0  # rows resident after the insert
    n_cross_pairs: int = 0  # ΔR×R_old pairs emitted
    n_self_pairs: int = 0  # ΔR×ΔR pairs emitted
    n_new_pairs: int = 0  # total pairs this batch contributed
    drift: float = 0.0
    replan_threshold: float = 0.0
    resample_threshold: float = 0.0
    action: str = "none"
    resample_due: bool = False
    balance_std_before: float = 0.0
    balance_std_after: float = 0.0
    route_s: float = 0.0  # fused delta map-assign time
    verify_s: float = 0.0  # cross + self verify time
    update_s: float = 0.0  # absorb + drift bookkeeping time
    cross_verify: verify_lib.VerifyStats | None = None
    self_verify: verify_lib.VerifyStats | None = None


def _member_matrix(
    coords: np.ndarray, wlo: np.ndarray, whi: np.ndarray, chunk: int = 65536
) -> np.ndarray:
    """(n, p) bool whole membership of mapped coordinates under δ-expanded
    boxes — the same closed-interval comparison the fused kernel packs into
    its bitmask, evaluated host-side from CACHED coordinates (no re-map).
    Row-chunked so the (n, p, dims) broadcast never materializes."""
    n = coords.shape[0]
    out = np.zeros((n, wlo.shape[0]), bool)
    for i0 in range(0, n, chunk):
        c = coords[i0 : i0 + chunk]
        out[i0 : i0 + chunk] = (
            (c[:, None, :] >= wlo[None]) & (c[:, None, :] <= whi[None])
        ).all(-1)
    return out


def _member_counts(
    coords: np.ndarray, wlo: np.ndarray, whi: np.ndarray, chunk: int = 65536
) -> np.ndarray:
    """(p,) float64 per-cell whole-member counts (drift telemetry baseline)."""
    out = np.zeros(wlo.shape[0], np.float64)
    for i0 in range(0, coords.shape[0], chunk):
        c = coords[i0 : i0 + chunk]
        out += (
            ((c[:, None, :] >= wlo[None]) & (c[:, None, :] <= whi[None]))
            .all(-1)
            .sum(0)
        )
    return out


@dataclasses.dataclass
class MetricIndex:
    """Everything the query phase needs, with the build phase paid once.

    All arrays are host numpy (the single-host serving path gathers verify
    tiles from them; ``to_distributed`` device-puts the per-slot V buffers).
    ``coords`` are R's mapped coordinates — the cached index-to-pivot
    distances the pivot filter reuses on every query.
    """

    # -- build config (the manifest scalars) --------------------------------
    metric: str
    delta: float  # build-time default query radius
    n_dims: int
    tighten: bool
    backend: str  # RESOLVED backend ("numpy" | "pallas") the build mapped with
    prune: str  # requested prune mode ("pivot" | "none")
    map_fused: bool
    tile_v: int
    tile_w: int
    seed: int
    placement_strategy: str
    n_devices: int  # devices the stored placement plan targets

    # -- build artifacts ----------------------------------------------------
    data: np.ndarray  # (N, m) the indexed set R
    coords: np.ndarray  # (N, n) R's mapped coordinates (pivot distances)
    cells: np.ndarray  # (N,) kernel cell of each R row
    pivots: np.ndarray  # (k, m) sampled pivots
    anchors: np.ndarray  # (n, m) anchor pivots of the space map
    kernel_lo: np.ndarray  # (p, n) half-open kernel boxes
    kernel_hi: np.ndarray
    box_lo: np.ndarray  # (p, n) PRE-expansion whole-box base (member MBB
    box_hi: np.ndarray  # when tighten, else the kernel box); query boxes
    #   are box ∓ query-δ — recomputed per batch, any radius answers exactly
    placement: placement_lib.PlacementPlan
    build_s: float = 0.0
    node_confidences: np.ndarray | None = None

    # -- incremental-insert state (persisted, format v2) --------------------
    n_base: int = 0  # rows the initial build indexed
    n_inserted: int = 0  # rows appended by insert_batch since build/rebuild
    n_batches: int = 0  # insert_batch calls absorbed (survives rebuilds)
    observed_w: np.ndarray | None = None  # (p,) observed whole-member counts
    #   — exact at build, then accumulated per delta at insert time (an old
    #   row's membership is not recomputed as boxes grow); drift TELEMETRY,
    #   never exactness-bearing (docs/STREAMING.md)

    # -- derived query-phase caches (never persisted) -----------------------
    _v_lists: list[np.ndarray] | None = dataclasses.field(default=None, repr=False)

    # ------------------------------------------------------------------ api

    @property
    def n_rows(self) -> int:
        return int(self.data.shape[0])

    @property
    def n_features(self) -> int:
        return int(self.data.shape[1])

    @property
    def k(self) -> int:
        return int(self.pivots.shape[0])

    @property
    def p(self) -> int:
        return int(self.kernel_lo.shape[0])

    @property
    def space_map(self) -> mapping.SpaceMap:
        return mapping.SpaceMap(jnp.asarray(self.anchors), self.metric)

    @property
    def v_lists(self) -> list[np.ndarray]:
        """Per-cell V row lists (global R indices), computed once per index."""
        if self._v_lists is None:
            order = np.argsort(self.cells, kind="stable")
            bounds = np.searchsorted(self.cells[order], np.arange(self.p + 1))
            self._v_lists = [
                order[bounds[h] : bounds[h + 1]] for h in range(self.p)
            ]
        return self._v_lists

    def query_boxes(self, delta: float) -> tuple[np.ndarray, np.ndarray]:
        """The δ-expanded whole boxes for a given query radius — the exact
        expression the build phase would have produced for that δ, so
        ``delta == self.delta`` reproduces the join's boxes bit-for-bit."""
        return (
            (self.box_lo - np.float32(delta)).astype(np.float32),
            (self.box_hi + np.float32(delta)).astype(np.float32),
        )

    def route(self, q: np.ndarray | Array, delta: float) -> tuple[np.ndarray, np.ndarray]:
        """Map a query batch and route it to its owning cells.

        Returns ``(q_coords (B, n), member (B, p))`` — the mapped
        coordinates (reused by the pivot filter) and the whole-box
        membership under the δ-expanded query boxes. Uses the same fused
        map-assign kernel (and fp algorithm) as the build phase, so a
        borderline query coordinate can never land on a different side of
        a box edge than the indexed MBB implies.
        """
        q = jnp.asarray(q, jnp.float32)
        wlo, whi = self.query_boxes(delta)
        if q.shape[0] == 0:
            return (
                np.zeros((0, self.n_dims), np.float32),
                np.zeros((0, self.p), bool),
            )
        if self.map_fused and kops.supports_kernel(self.metric):
            qm, _, bits = kops.map_assign(
                q, jnp.asarray(self.anchors),
                jnp.asarray(self.kernel_lo), jnp.asarray(self.kernel_hi),
                jnp.asarray(wlo), jnp.asarray(whi),
                self.metric, backend=self.backend, want="member",
            )
            member = kops.unpack_membership(bits, self.p)
        else:
            qm = self.space_map(q)
            member = (
                (qm[:, None, :] >= jnp.asarray(wlo)[None])
                & (qm[:, None, :] <= jnp.asarray(whi)[None])
            ).all(-1)
        return np.asarray(qm, np.float32), np.asarray(member, bool)

    def query_batch(
        self,
        q: np.ndarray | Array,
        delta: float | None = None,
        *,
        with_stats: bool = False,
    ):
        """Batched δ-range query: all pairs (i ∈ R, j ∈ Q) with
        D(r_i, q_j) ≤ δ, as an (n_pairs, 2) int64 array (column 0 indexes
        the indexed set, column 1 the query batch). ``delta=None`` uses the
        build-time default. Fixed-seed results are byte-identical to
        ``distances.brute_force_join(R, Q, delta)``.

        No sampling, fitting or partitioning happens here — only the fused
        map pass over Q and the tiled verify engine over the routed cells.
        """
        delta = self.delta if delta is None else float(delta)
        q_np = np.asarray(q, np.float32)
        with tracing.root("index.query_batch", n_queries=int(q_np.shape[0])):
            with tracing.span("index.route") as route:
                q_coords, member = self.route(q_np, delta)
            with tracing.span("index.verify") as verify:
                pairs, vstats = verify_lib.verify_resident(
                    self.data, self.cells, self.v_lists, member, delta, self.metric,
                    config=self._engine_config(), data_w=q_np,
                    coords=self.coords, coords_w=q_coords,
                )
        if not with_stats:
            return pairs
        touched = int((member.sum(0) > 0).sum())
        stats = QueryStats(
            n_queries=int(q_np.shape[0]),
            n_routed=int(member.sum()),
            n_cells_touched=touched,
            route_s=route.seconds,
            verify_s=verify.seconds,
            verify=vstats,
        )
        return pairs, stats

    def query(self, q: np.ndarray | Array, delta: float | None = None) -> np.ndarray:
        """Single-point δ-range query: sorted R row indices within δ of ``q``."""
        q = np.asarray(q, np.float32)
        if q.ndim != 1:
            raise ValueError(f"query() takes one point (m,); got shape {q.shape}")
        pairs = self.query_batch(q[None, :], delta)
        return np.sort(pairs[:, 0])

    # ------------------------------------------------------------ streaming

    def _engine_config(self) -> verify_lib.EngineConfig:
        return verify_lib.EngineConfig(
            backend=self.backend, tile_v=self.tile_v, tile_w=self.tile_w,
            prune=verify_lib.resolve_prune(self.prune, self.metric, True),
        )

    def _ensure_stream_state(self) -> None:
        """Initialize the incremental counters on indexes that predate them
        (hand-constructed in tests, or deserialized mid-refactor)."""
        if self.n_base == 0 and self.n_rows > self.n_inserted:
            self.n_base = self.n_rows - self.n_inserted
        if self.observed_w is None:
            wlo, whi = self.query_boxes(self.delta)
            self.observed_w = _member_counts(self.coords, wlo, whi)

    @property
    def observed_loads(self) -> np.ndarray:
        """(p,) OBSERVED per-cell verification loads |V_h|·|W_h| — the
        measured counterpart of the placement plan's predicted
        ``cell_loads`` and the drift monitor's second input."""
        self._ensure_stream_state()
        v_obs = np.bincount(self.cells, minlength=self.p).astype(np.float64)
        assert self.observed_w is not None
        return v_obs * self.observed_w[: self.p]

    def self_pairs(self) -> np.ndarray:
        """Self-join pairs of the resident set through the index's own
        cached artifacts (coords, cells, δ-expanded boxes) — what a one-shot
        ``spjoin.join`` over this partition geometry emits, without
        re-running any control plane. The streaming session uses this for
        batch 0; fixed-seed output is byte-identical to
        ``spjoin.brute_force_pairs`` (the join is exact under any
        containment-consistent plan)."""
        wlo, whi = self.query_boxes(self.delta)
        member = _member_matrix(self.coords, wlo, whi)
        pairs, _ = verify_lib.verify_pairs(
            self.data, self.cells, member, self.delta, self.metric,
            config=self._engine_config(), coords=self.coords,
        )
        return pairs

    def _delta_route(
        self, d_np: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Map an insertion delta through the SAME fused map-assign pass as
        the build: mapped coordinates, kernel cells, and whole membership
        under the CURRENT (pre-absorb) δ-expanded boxes — the Lemma-4 routing
        for the ΔR×R_old cross verify."""
        wlo, whi = self.query_boxes(self.delta)
        if self.map_fused and kops.supports_kernel(self.metric):
            dm, cells, bits = kops.map_assign(
                jnp.asarray(d_np), jnp.asarray(self.anchors),
                jnp.asarray(self.kernel_lo), jnp.asarray(self.kernel_hi),
                jnp.asarray(wlo), jnp.asarray(whi),
                self.metric, backend=self.backend, want="both",
            )
            member = kops.unpack_membership(bits, self.p)
            return (
                np.asarray(dm, np.float32),
                np.asarray(cells, np.int32),
                np.asarray(member, bool),
            )
        dm = np.asarray(self.space_map(jnp.asarray(d_np)), np.float32)
        pplan = partition.PartitionPlan(
            jnp.asarray(self.kernel_lo), jnp.asarray(self.kernel_hi),
            jnp.asarray(wlo), jnp.asarray(whi), self.delta,
        )
        cells = np.asarray(partition.assign_kernel(pplan, jnp.asarray(dm)), np.int32)
        member = _member_matrix(dm, wlo, whi)
        return dm, cells, member

    def _delta_self_pairs(
        self, d_np: np.ndarray, d_coords: np.ndarray, d_cells: np.ndarray
    ) -> tuple[np.ndarray, verify_lib.VerifyStats, np.ndarray, np.ndarray, np.ndarray]:
        """ΔR×ΔR self-join, DELTA-LOCAL ids, plus the updated base boxes.

        The member MBBs are first extended with the delta's own coordinates —
        only then does Lemma 4 cover delta-vs-delta partners (each delta row
        must sit inside its own cell's box before the δ-expansion can catch
        its neighbours). Returns (pairs_local, stats, new_box_lo, new_box_hi,
        member_new) with member_new the delta's membership under the UPDATED
        boxes (also the absorb's observed_w increment).
        """
        new_lo = self.box_lo.copy()
        new_hi = self.box_hi.copy()
        np.minimum.at(new_lo, d_cells, d_coords)
        np.maximum.at(new_hi, d_cells, d_coords)
        qlo = (new_lo - np.float32(self.delta)).astype(np.float32)
        qhi = (new_hi + np.float32(self.delta)).astype(np.float32)
        member_new = _member_matrix(d_coords, qlo, qhi)
        pairs, vstats = verify_lib.verify_pairs(
            d_np, d_cells, member_new, self.delta, self.metric,
            config=self._engine_config(), coords=d_coords,
        )
        return pairs, vstats, new_lo, new_hi, member_new

    def _absorb(
        self,
        d_np: np.ndarray,
        d_coords: np.ndarray,
        d_cells: np.ndarray,
        member_new: np.ndarray,
        new_lo: np.ndarray,
        new_hi: np.ndarray,
    ) -> None:
        """Append the delta to the resident arrays and every derived cache.

        The per-cell V lists are EXTENDED, not recomputed: delta ids are
        global-contiguous above the resident set, so appending each cell's
        delta members preserves the exact order the stable-argsort
        derivation would produce from scratch — repeated deltas amortize.
        """
        n_old = self.n_rows
        assert self.observed_w is not None
        self.data = np.concatenate([self.data, d_np])
        self.coords = np.concatenate([self.coords, d_coords])
        self.cells = np.concatenate([self.cells, d_cells.astype(self.cells.dtype)])
        self.box_lo = new_lo
        self.box_hi = new_hi
        if self._v_lists is not None:
            order = np.argsort(d_cells, kind="stable")
            bounds = np.searchsorted(d_cells[order], np.arange(self.p + 1))
            for h in range(self.p):
                extra = order[bounds[h] : bounds[h + 1]]
                if extra.size:
                    self._v_lists[h] = np.concatenate(
                        [self._v_lists[h], n_old + extra]
                    )
        self.observed_w = self.observed_w + member_new.sum(0)
        self.n_inserted += int(d_np.shape[0])
        self.n_batches += 1

    def _rebuild(self, cfg) -> None:
        """Re-sample pivots and rebuild from the full accumulated data (the
        expensive drift action): every artifact — pivots, anchors, partition,
        boxes, placement, caches — is replaced in place. The accumulated
        PAIR SET is untouched: the join is exact under any
        containment-consistent plan, so a rebuild resets predictions, never
        answers."""
        n_batches = self.n_batches
        if self.n_rows < cfg.n_dims:
            # Row-fallback samplers cap pivots at n_rows; a tiny stream can't
            # support the full mapped dimensionality yet (spjoin session
            # applies the same clamp on its first build).
            cfg = dataclasses.replace(cfg, n_dims=max(1, self.n_rows))
        fresh = build_index(
            self.data, cfg,
            n_nodes=max(1, min(4, self.n_rows)), n_devices=self.n_devices,
        )
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(fresh, f.name))
        self.n_batches = n_batches

    def _drift_step(
        self,
        stats: StreamStats,
        replan_drift: float,
        resample_drift: float,
        rebuild_cfg,
    ) -> None:
        """Measure drift against the plan in force and fire the cheap action
        (re-plan: a static permutation, pairs unchanged) before the expensive
        one (re-sample → full rebuild; needs ``rebuild_cfg``)."""
        observed = self.observed_loads
        stats.drift = cost_model.load_drift(self.placement.cell_loads, observed)
        stats.balance_std_before = float(
            placement_lib.device_loads_under(self.placement, observed).std()
        )
        action = placement_lib.drift_action(stats.drift, replan_drift, resample_drift)
        if action == "resample" and rebuild_cfg is None:
            # No control-plane config to rebuild with: fall back to the cheap
            # action and surface the debt (resample_due) to the caller.
            stats.resample_due = True
            action = "replan"
        if action == "resample":
            self._rebuild(rebuild_cfg)
        elif action == "replan":
            self.placement = placement_lib.plan_placement(
                observed, self.placement.n_devices,
                strategy=self.placement_strategy,
            )
        stats.action = action
        stats.balance_std_after = float(
            placement_lib.device_loads_under(self.placement, self.observed_loads).std()
        )

    def insert_batch(
        self,
        new_rows: np.ndarray | Array,
        *,
        replan_drift: float | None = None,
        resample_drift: float | None = None,
        rebuild_cfg=None,
        _cross_pairs_fn=None,
    ) -> tuple[np.ndarray, StreamStats]:
        """Absorb an insertion batch and return the NEW pairs it creates.

        Only the delta is mapped (one fused map-assign pass); the new pairs
        are ΔR×R_old — the delta routed against the RESIDENT per-cell V
        lists through the same ``verify_resident`` tile path as
        ``query_batch`` — plus the ΔR×ΔR self-join under the updated member
        MBBs. Returned pairs use GLOBAL row ids (delta row j ↦ n_resident +
        j), i < j, sorted unique; no sampling, fitting, partitioning or
        placement work happens unless the drift monitor fires.

        Exactness contract: for a fixed seed and ANY split of R into
        insertion batches, the union of ``build``-time pairs and every
        ``insert_batch`` return is byte-identical to a from-scratch join of
        the full R (property-tested in tests/test_incremental.py).

        ``replan_drift`` / ``resample_drift``: drift thresholds (default
        ``core.placement.REPLAN_DRIFT`` / ``RESAMPLE_DRIFT``). ``rebuild_cfg``
        (a ``spjoin.JoinConfig``) arms the re-sample action; without it a
        re-sample-worthy drift downgrades to a re-plan with
        ``StreamStats.resample_due`` set. ``_cross_pairs_fn`` lets the
        distributed mirror route the ΔR×R_old verify through its serve stage
        while sharing this exact control flow.
        """
        self._ensure_stream_state()
        rt = placement_lib.REPLAN_DRIFT if replan_drift is None else float(replan_drift)
        rs = placement_lib.RESAMPLE_DRIFT if resample_drift is None else float(resample_drift)
        d_np = np.asarray(new_rows, np.float32)
        if d_np.ndim != 2 or (d_np.shape[0] and d_np.shape[1] != self.n_features):
            raise ValueError(
                f"insert_batch expects (B, {self.n_features}) rows; got "
                f"shape {d_np.shape}"
            )
        stats = StreamStats(
            n_delta=int(d_np.shape[0]), n_resident=self.n_rows,
            n_total=self.n_rows + int(d_np.shape[0]),
            replan_threshold=rt, resample_threshold=rs,
        )
        if d_np.shape[0] == 0:
            # Empty delta: nothing routed, nothing absorbed, nothing fired.
            stats.drift = cost_model.load_drift(
                self.placement.cell_loads, self.observed_loads
            )
            return np.zeros((0, 2), np.int64), stats

        n_old = self.n_rows
        with tracing.root("index.insert_batch", n_delta=stats.n_delta):
            with tracing.span("index.route") as route:
                d_coords, d_cells, d_member_old = self._delta_route(d_np)

            with tracing.span("index.verify") as verify:
                if _cross_pairs_fn is None:
                    cross, cstats = verify_lib.verify_resident(
                        self.data, self.cells, self.v_lists, d_member_old,
                        self.delta, self.metric, config=self._engine_config(),
                        data_w=d_np, coords=self.coords, coords_w=d_coords,
                    )
                    stats.cross_verify = cstats
                else:
                    cross = np.asarray(_cross_pairs_fn(d_np), np.int64).reshape(-1, 2)
                self_local, sstats, new_lo, new_hi, member_new = self._delta_self_pairs(
                    d_np, d_coords, d_cells
                )
            stats.self_verify = sstats
            stats.n_cross_pairs = int(cross.shape[0])
            stats.n_self_pairs = int(self_local.shape[0])

            # Globalize: cross pairs are (i ∈ resident, j ∈ delta) — already
            # i < n_old + j; ΔΔ pairs shift both columns above the resident set.
            chunks = []
            if cross.shape[0]:
                chunks.append(
                    np.stack([cross[:, 0], n_old + cross[:, 1]], axis=1)
                )
            if self_local.shape[0]:
                chunks.append(self_local + n_old)
            if chunks:
                pairs = np.unique(np.concatenate(chunks), axis=0).astype(np.int64)
            else:
                pairs = np.zeros((0, 2), np.int64)
            stats.n_new_pairs = int(pairs.shape[0])

            with tracing.span("index.update") as update:
                self._absorb(d_np, d_coords, d_cells, member_new, new_lo, new_hi)
                self._drift_step(stats, rt, rs, rebuild_cfg)
        stats.route_s = route.seconds
        stats.verify_s = verify.seconds
        stats.update_s = update.seconds
        return pairs, stats

    # ----------------------------------------------------------- distributed

    def to_distributed(self, mesh, axis: str = "data") -> "DistIndex":
        """Pin the per-slot V buffers on ``mesh`` and serve query batches
        through the distributed verify-stage slot machinery (one W-side
        ``all_to_all`` per batch, no R bytes moved after this call).

        Re-plans placement (cheap — a static permutation from the stored
        cost-model loads) when the mesh size differs from the plan's
        ``n_devices``; never re-samples or re-partitions.
        """
        from repro.core import distributed as dist_lib

        return dist_lib.DistIndex.from_index(self, mesh, axis=axis)

    # ------------------------------------------------------------- save/load

    def manifest(self) -> dict:
        """The JSON manifest (format + config + shapes + placement summary)."""
        return {
            "format": FORMAT_NAME,
            "version": FORMAT_VERSION,
            "metric": self.metric,
            "delta": float(self.delta),
            "k": self.k,
            "p": self.p,
            "n_dims": self.n_dims,
            "n_rows": self.n_rows,
            "n_features": self.n_features,
            "tighten": bool(self.tighten),
            "backend": self.backend,
            "prune": self.prune,
            "map_fused": bool(self.map_fused),
            "tile_v": self.tile_v,
            "tile_w": self.tile_w,
            "seed": self.seed,
            "build_s": float(self.build_s),
            "incremental": {
                "n_base": int(self.n_base),
                "n_inserted": int(self.n_inserted),
                "n_batches": int(self.n_batches),
            },
            "placement": {
                "strategy": self.placement.strategy,
                "n_devices": self.placement.n_devices,
                "n_slots": self.placement.n_slots,
                "certified_bound": float(self.placement.certified_bound),
            },
            "arrays": {name: list(getattr(self, name).shape) for name in _ARRAYS},
        }

    def save(self, path: str) -> str:
        """Write the versioned on-disk format: ``path/manifest.json`` +
        ``path/arrays.npz`` (all arrays bit-exact). Returns ``path``."""
        self._ensure_stream_state()
        os.makedirs(path, exist_ok=True)
        arrays = {name: np.asarray(getattr(self, name)) for name in _ARRAYS}
        for name in _PLAN_ARRAYS:
            arrays[f"pl_{name}"] = np.asarray(getattr(self.placement, name))
        if self.node_confidences is not None:
            arrays["node_confidences"] = np.asarray(self.node_confidences)
        np.savez(os.path.join(path, "arrays.npz"), **arrays)
        with open(os.path.join(path, "manifest.json"), "w") as f:
            json.dump(self.manifest(), f, indent=2, sort_keys=True)
        return path

    @classmethod
    def load(
        cls,
        path: str,
        *,
        metric: str | None = None,
        delta: float | None = None,
        k: int | None = None,
    ) -> "MetricIndex":
        """Load an index, failing loudly instead of mis-answering.

        Format checks (``IndexFormatError``): missing/foreign manifest, a
        version this code does not speak, manifest/array shape disagreement.
        Config checks (``IndexMismatchError``): when the caller states the
        ``metric`` / ``delta`` / pivot count ``k`` its queries assume, any
        disagreement with the manifest raises with both values named.
        """
        mpath = os.path.join(path, "manifest.json")
        if not os.path.exists(mpath):
            raise IndexFormatError(f"no metric-index manifest at {mpath}")
        with open(mpath) as f:
            man = json.load(f)
        if man.get("format") != FORMAT_NAME:
            raise IndexFormatError(
                f"{mpath} is not a {FORMAT_NAME!r} artifact "
                f"(format={man.get('format')!r})"
            )
        version = man.get("version")
        if version != FORMAT_VERSION:
            raise IndexFormatError(
                f"index format version {version!r} is not supported by this "
                f"build (speaks version {FORMAT_VERSION}); re-save the index "
                f"with a matching version of the code"
            )
        if metric is not None and metric != man["metric"]:
            raise IndexMismatchError(
                f"index was built for metric {man['metric']!r} but the query "
                f"config expects {metric!r} — distances would be silently "
                f"wrong; rebuild the index for {metric!r}"
            )
        if delta is not None and not np.isclose(delta, man["delta"]):
            raise IndexMismatchError(
                f"index default delta is {man['delta']} but the query config "
                f"expects {delta} — pass delta= per query_batch() call for a "
                f"different radius, or rebuild to change the default"
            )
        if k is not None and k != man["k"]:
            raise IndexMismatchError(
                f"index holds {man['k']} pivots but the query config expects "
                f"k={k} — the partition plan would not match; rebuild"
            )

        with np.load(os.path.join(path, "arrays.npz")) as z:
            arrays = {name: z[name] for name in z.files}
        missing = [n for n in _ARRAYS if n not in arrays]
        if missing:
            raise IndexFormatError(f"arrays.npz is missing {missing}")
        for name, shape in man["arrays"].items():
            got = list(arrays[name].shape)
            if got != shape:
                raise IndexFormatError(
                    f"manifest says {name} has shape {shape} but arrays.npz "
                    f"holds {got} — artifact is corrupt or mixed between saves"
                )
        if int(man["k"]) != arrays["pivots"].shape[0]:
            raise IndexFormatError(
                f"manifest pivot count k={man['k']} disagrees with the stored "
                f"pivots array ({arrays['pivots'].shape[0]} rows)"
            )
        inc = man.get("incremental")
        if not isinstance(inc, dict) or not {
            "n_base", "n_inserted", "n_batches"
        } <= set(inc):
            raise IndexFormatError(
                "version-2 manifest is missing the incremental block "
                "(n_base / n_inserted / n_batches) — artifact is corrupt"
            )
        if int(inc["n_base"]) + int(inc["n_inserted"]) != int(man["n_rows"]):
            raise IndexMismatchError(
                f"incremental counters disagree with the stored data: "
                f"n_base={inc['n_base']} + n_inserted={inc['n_inserted']} != "
                f"n_rows={man['n_rows']} — the appended-delta history does "
                f"not describe this artifact; refusing to resume the stream"
            )

        pman = man["placement"]
        loads = arrays["pl_cell_loads"]
        plan = placement_lib.PlacementPlan(
            strategy=pman["strategy"],
            n_devices=int(pman["n_devices"]),
            p=int(man["p"]),
            n_slots=int(pman["n_slots"]),
            cell_loads=loads,
            cell_first_slot=arrays["pl_cell_first_slot"],
            cell_n_slabs=arrays["pl_cell_n_slabs"],
            slot_cell=arrays["pl_slot_cell"],
            slot_slab=arrays["pl_slot_slab"],
            slot_load=arrays["pl_slot_load"],
            dispatch_of_slot=arrays["pl_dispatch_of_slot"],
            certified_bound=float(pman["certified_bound"]),
        )
        return cls(
            metric=man["metric"],
            delta=float(man["delta"]),
            n_dims=int(man["n_dims"]),
            tighten=bool(man["tighten"]),
            backend=man["backend"],
            prune=man["prune"],
            map_fused=bool(man["map_fused"]),
            tile_v=int(man["tile_v"]),
            tile_w=int(man["tile_w"]),
            seed=int(man["seed"]),
            placement_strategy=pman["strategy"],
            n_devices=int(pman["n_devices"]),
            data=arrays["data"],
            coords=arrays["coords"],
            cells=arrays["cells"],
            pivots=arrays["pivots"],
            anchors=arrays["anchors"],
            kernel_lo=arrays["kernel_lo"],
            kernel_hi=arrays["kernel_hi"],
            box_lo=arrays["box_lo"],
            box_hi=arrays["box_hi"],
            placement=plan,
            build_s=float(man.get("build_s", 0.0)),
            node_confidences=arrays.get("node_confidences"),
            n_base=int(inc["n_base"]),
            n_inserted=int(inc["n_inserted"]),
            n_batches=int(inc["n_batches"]),
            observed_w=arrays["observed_w"],
        )


# ---------------------------------------------------------------------------
# The build phase
# ---------------------------------------------------------------------------


def _base_boxes(
    plan: partition.PartitionPlan,
    x_mapped: Array,
    cells: Array,
    tighten: bool,
) -> tuple[np.ndarray, np.ndarray]:
    """Pre-expansion whole-box base: the member MBB of each cell (the same
    segment min/max expression ``partition.tighten`` uses, so expanding by
    the build δ reproduces the join's whole boxes bit-for-bit), or the
    kernel box when tightening is off. Empty cells collapse to the inverted
    (BIG, −BIG) box — no query radius can ever route into them."""
    if not tighten:
        return np.asarray(plan.kernel_lo), np.asarray(plan.kernel_hi)
    p = plan.p
    seg_min = jax.ops.segment_min(x_mapped, cells, num_segments=p)
    seg_max = jax.ops.segment_max(x_mapped, cells, num_segments=p)
    counts = jax.ops.segment_sum(
        jnp.ones_like(cells, jnp.float32), cells, num_segments=p
    )
    empty = counts == 0
    lo = jnp.where(empty[:, None], partition.BIG, seg_min)
    hi = jnp.where(empty[:, None], -partition.BIG, seg_max)
    return np.asarray(lo, np.float32), np.asarray(hi, np.float32)


def build_index(
    data: np.ndarray | Array,
    cfg: spjoin.JoinConfig,
    *,
    n_nodes: int = 4,
    n_devices: int | None = None,
) -> MetricIndex:
    """Run the build phase ONCE: sampling → anchors → partition boxes →
    member MBBs → LPT placement plan → cached coordinates and V lists.

    ``data`` is the indexed set R (full array or per-node shard list, as for
    ``spjoin.join``); ``cfg`` carries the same knobs the join uses (δ becomes
    the default query radius). ``n_devices`` sizes the stored placement plan
    (default: ``n_nodes``) — ``to_distributed`` re-plans cheaply when the
    serving mesh differs.

    The exact same control-plane helpers as ``spjoin.join`` run here
    (``fit_node_stats`` → ``draw_pivots`` → ``build_plan``), so a fixed seed
    yields the identical partition geometry the one-shot join would use.
    """
    with tracing.root("index.build") as build:
        key = jax.random.PRNGKey(cfg.seed)
        shards = spjoin._as_shards(data, n_nodes)
        allx = jnp.concatenate(shards, axis=0) if shards else jnp.asarray(data)

        # ---- sampling phase (once, at build) ---------------------------------
        k_sample, k_anchor = jax.random.split(key)
        node_stats = spjoin.fit_node_stats(shards, cfg.t_cells)
        pivots = spjoin.draw_pivots(k_sample, shards, node_stats, cfg)

        # ---- map-phase control plane (once, at build) ------------------------
        plan, smap = spjoin.build_plan(k_anchor, pivots, cfg)
        fused = cfg.map_fused and kops.supports_kernel(cfg.metric)
        backend = (
            kops.resolve_backend(cfg.backend, cfg.metric)
            if kops.supports_kernel(cfg.metric)
            else "numpy"
        )
        if fused:
            x_mapped, cells, _ = kops.map_assign(
                allx, smap.anchors, plan.kernel_lo, plan.kernel_hi,
                plan.whole_lo, plan.whole_hi, cfg.metric, backend=backend,
                want="cells",
            )
        else:
            x_mapped = smap(allx)
            cells = partition.assign_kernel(plan, x_mapped)
        box_lo, box_hi = _base_boxes(plan, x_mapped, cells, cfg.tighten)

        # ---- placement plan (cost-model loads from the pivots alone) ---------
        n_dev = int(n_devices or max(len(shards), 1))
        piv_mapped = np.asarray(smap(pivots), np.float32)
        piv_plan = partition.PartitionPlan(
            plan.kernel_lo, plan.kernel_hi,
            jnp.asarray(box_lo - np.float32(cfg.delta)),
            jnp.asarray(box_hi + np.float32(cfg.delta)),
            cfg.delta,
        )
        piv_cells = np.asarray(partition.assign_kernel(piv_plan, jnp.asarray(piv_mapped)))
        piv_member = np.asarray(
            partition.whole_membership(piv_plan, jnp.asarray(piv_mapped))
        )
        prune_active = verify_lib.resolve_prune(cfg.prune, cfg.metric, True) == "pivot"
        cell_loads, _, _, _ = placement_lib.planner_inputs(
            piv_mapped, piv_cells, piv_member,
            int(allx.shape[0]), int(allx.shape[0]), cfg.delta, prune_active,
        )
        pl = placement_lib.plan_placement(cell_loads, n_dev, strategy=cfg.placement)

        idx = MetricIndex(
            metric=cfg.metric,
            delta=float(cfg.delta),
            n_dims=int(smap.n_dims),
            tighten=bool(cfg.tighten),
            backend=backend,
            prune=cfg.prune,
            map_fused=bool(fused),
            tile_v=cfg.tile_v,
            tile_w=cfg.tile_w,
            seed=cfg.seed,
            placement_strategy=cfg.placement,
            n_devices=n_dev,
            data=np.asarray(allx, np.float32),
            coords=np.asarray(x_mapped, np.float32),
            cells=np.asarray(cells, np.int32),
            pivots=np.asarray(pivots, np.float32),
            anchors=np.asarray(smap.anchors, np.float32),
            kernel_lo=np.asarray(plan.kernel_lo, np.float32),
            kernel_hi=np.asarray(plan.kernel_hi, np.float32),
            box_lo=box_lo,
            box_hi=box_hi,
            placement=pl,
            node_confidences=np.array([st.confidence for st in node_stats]),
            n_base=int(allx.shape[0]),
            observed_w=_member_counts(
                np.asarray(x_mapped, np.float32),
                (box_lo - np.float32(cfg.delta)).astype(np.float32),
                (box_hi + np.float32(cfg.delta)).astype(np.float32),
            ),
        )
    idx.build_s = build.seconds
    return idx


def brute_force_query(
    index_data: np.ndarray, q: np.ndarray, delta: float, metric: str
) -> np.ndarray:
    """Oracle for tests/benchmarks: (i ∈ R, j ∈ Q) pairs, computed in device
    blocks (``distances.oracle_pairs``) — the parity target of
    ``query_batch``."""
    return distances.oracle_pairs(index_data, delta, metric, q)
