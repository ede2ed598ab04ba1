"""Streaming tiled verify engine — the shared reduce phase of SP-Join.

The reduce phase (paper §5) checks every kernel-partition row V_h against
every whole-partition row W_h: Σ_h |V_h|·|W_h| distance evaluations. This
module is the ONE implementation of that stage; both executors route
through it:

  * ``spjoin.join``            calls :func:`verify_pairs` (host-streamed tiles)
  * ``distributed.stage_verify`` calls :func:`verify_tile` / :func:`apply_dedup`
                               inside its shard_map trace (static buffers)

so the reference and distributed paths cannot silently diverge on verify
semantics (padding validity + the min-cell de-dup rule live here, once).

Streaming + bucketing (the TPU/XLA adaptation of DIMS-style tile-scheduled
verification):

  * Each cell's |V_h| × |W_h| rectangle is cut into fixed-capacity tiles of
    at most ``tile_v × tile_w`` — peak working set is O(tile), never
    O(|V_h|·|W_h|), so skewed cells stream instead of blowing up memory.
  * Tiles are padded up to a small set of static *bucket* shapes (quarter-
    power-of-two quantized per axis), so XLA compiles O(buckets) executables
    instead of O(cells) — the classic static-shape trade: a bounded padding
    overhead (reported as ``occupancy``) buys compile-cache hits.
  * The distance + ``<= delta`` threshold is one fused jitted call per tile
    (Pallas ``pairdist_mask`` or the jnp oracle, per ``backend``); mask →
    global-pair-index extraction happens per tile on the host, with the
    min-cell de-dup rule already applied inside the compiled mask.

De-dup rule (same statement as the seed executor): a hit (i, j) with
cell(i) = g, cell(j) = h is emitted by cell min(g, h) only; within one cell
both orders are present so we keep id_i < id_j. Lemma 4 guarantees each
qualifying pair is seen by both cells, hence exactly once after the rule.

Two-set R×S mode (``cross=True`` / ``data_w`` given): V rows come from R's
kernel cells, W rows from S's whole membership. Each R row lives in exactly
one kernel cell and Lemma 4 puts every δ-neighbour s ∈ S inside that cell's
whole box, so "emit in R's kernel cell only" already yields each cross pair
exactly once — the min-cell + id ordering rule degenerates to plain padding
validity, and emitted pairs are (i ∈ R, j ∈ S), never reordered.

Pivot-filter pruning (``prune="pivot"`` — the DIMS-style triangle-inequality
candidate filter, run BEFORE any exact metric evaluation):

  * Each object's mapped coordinates (its distances to the shared anchors,
    produced once by ``core.mapping``) are threaded into the tiles alongside
    the payload rows. Every coordinate is 1-Lipschitz, so
    ``max_p |d(v,p) − d(w,p)|`` is a lower bound on D(v, w): a pair whose
    bound exceeds δ cannot be a hit and skips exact evaluation.
  * The bound is evaluated against a slightly slackened threshold
    (``ref.prune_delta`` — an fp guard band), which makes the filter sound
    in fp32 as well: fixed-seed pair sets are BYTE-IDENTICAL between
    ``prune="pivot"`` and ``prune="none"``. Pruning is a pure optimization,
    never a semantics change.
  * The streaming engine skips a tile's exact-distance call outright when
    every pair in it is pruned (``VerifyStats.n_tiles_pruned``); surviving
    tiles run the fused filter+pairdist kernel, whose Pallas path likewise
    skips the MXU/VPU accumulation for all-pruned blocks.
  * Capability, not error: metrics without the triangle inequality (cosine,
    dot) silently resolve to ``prune="none"`` — same treatment as backends
    without a kernel.
  * Window refinement (paper §5's ordered-range pruning): with pruning on,
    each cell's V and W lists are ordered by their first mapped coordinate,
    and a binary search slices each V tile's W range down to the
    ``± delta_bound`` window — rows outside it already exceed the L∞ lower
    bound on that single coordinate, so they are pruned before any gather
    or device dispatch ever happens. On top of the window, whole W tiles
    whose coordinate bounding box is farther than ``delta_bound`` from the
    V tile's box on ANY coordinate are skipped the same way (interval
    arithmetic on host-side min/max — every pair in such a tile provably
    fails the L∞ bound).

Two prune modes share that machinery:

  * ``prune="pivot"`` — windows + the per-PAIR bound mask above. Exact
    per-pair pruning telemetry (``n_pruned`` counts every bound-failing
    pair), and on the Pallas backend the fused kernel skips exact work for
    all-pruned blocks. The per-pair mask costs O(tile·n) extra lanes on
    backends that cannot skip them, so this mode optimizes telemetry and
    accelerator block-skipping, not host wall-clock.
  * ``prune="window"`` — windows + bounding-box tile skips ONLY: all
    pruning happens on the host BEFORE gather/dispatch, cutting real
    dispatch area with zero extra per-pair lanes. ``n_pruned`` counts the
    window/box-pruned pairs (a subset of what "pivot" would count). This
    is the wall-clock mode: the pruned arm does strictly less device work
    than ``prune="none"``.

Emission paths (``EngineConfig.emit``):

  * ``"mask"``: the original per-tile (cap_v, cap_w) hit mask is read back
    and compacted on the host (``np.nonzero`` + gather).
  * ``"compact"``: the fused verify+compaction tile
    (``ref.verify_compact`` / ``kernels.compact``) emits an on-device
    prefix-sum-compacted (capacity, 2) id-pair buffer plus a true-total
    counter — the readback is output-sensitive, O(capacity) instead of
    O(tile area). Capacity is seeded from the cost model's survival
    estimate on a quarter-pow2 bucket ladder; a counter above capacity is
    the overflow sentinel and the engine retries that tile at the exact
    next bucket (the counter is the true total), with a bounded number of
    retries and the mask path as last-resort fallback. Fixed-seed pair
    sets are byte-identical to ``emit="mask"`` on every metric, backend
    and executor. Reference-only metrics (no fused tile) resolve back to
    ``"mask"`` — capability, not error.

Emission lowering is a BACKEND decision: the pair-buffer contract above is
what crosses the tile boundary, not a prescribed instruction sequence. The
Pallas backend (and the "pivot" prune mode, whose survivor count rides the
buffer's counter row) runs the true fused prefix-sum compaction
(``kernels.compact`` / the ``ref.verify_compact`` oracle). The numpy
backend outside "pivot" mode has no device boundary to compact across —
host and "device" memory are the same arena — so the engine lowers compact
emission to the mask dispatch plus a host pack of the identical buffer
contents; same pairs, same counters, none of the O(area) prefix-sum work
that only pays off across a real DMA boundary.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model, distances, tracing
from repro.kernels import ops as kops
from repro.kernels import ref

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs for the streaming engine.

    ``backend``: "numpy" | "pallas" | "auto" (see ``kernels.ops``). Metrics
    without a Pallas kernel (angular, jaccard_minhash) always take the jnp
    path regardless — the engine treats the kernel metric set as a backend
    capability, not an error.
    ``tile_v`` / ``tile_w``: streaming tile capacity (rows per side). Peak
    per-tile footprint ≈ tile_v·tile_w bytes of mask + gathered rows.
    ``min_bucket``: smallest padded tile side; tiles below it still pad up.
    ``prune``: "none" | "pivot" | "window" — pivot-filter pruning (L∞ lower
    bound over mapped coordinates, module docstring). "pivot" adds the
    per-pair bound mask (exact telemetry, Pallas block-skips); "window"
    prunes only at range/tile granularity before dispatch (the wall-clock
    mode). Both require the caller to pass ``coords`` (and ``coords_w`` in
    R×S mode); metrics without the triangle inequality resolve back to
    "none" (capability, not error).
    ``emit``: "mask" | "compact" — how a tile's hits come back to the host
    (module docstring, *Emission paths*). "compact" reads back an on-device
    prefix-sum-compacted pair buffer instead of the full tile mask;
    reference-only metrics resolve back to "mask" (capability, not error).
    """

    backend: str = "auto"
    tile_v: int = 1024
    tile_w: int = 4096
    min_bucket: int = 8
    prune: str = "none"
    emit: str = "mask"


@dataclasses.dataclass
class VerifyStats:
    """What the engine actually did — fed to benchmarks and Table-3 metrics.

    ``n_verifications`` keeps its paper meaning (Σ_h |V_h|·|W_h|, the
    CANDIDATE pair area) so Fig.-12 numbers stay comparable across prune
    modes; ``n_exact`` is the subset that actually reached exact metric
    evaluation after the pivot filter (== n_verifications when pruning is
    off).

    Emission invariance: ``n_verifications``, ``n_hits`` and ``n_pruned``
    (hence ``prune_rate`` / ``n_exact``) are IDENTICAL across ``emit`` modes
    by construction. The dispatch-schedule counters — ``n_tiles``,
    ``n_padded``, ``n_dispatched``, ``n_tiles_pruned`` — legitimately
    differ: compact emission never host-skips a tile in "pivot" mode (its
    filter runs fused in-kernel), and with windowed pruning all-pruned V
    tiles never materialize W tiles at all.

    Prune-mode semantics of ``n_pruned``: "pivot" counts every pair the L∞
    bound eliminates (per-pair mask); "window" counts the pairs eliminated
    at range/tile granularity — a provable-non-hit SUBSET of the former, so
    ``n_exact`` is an upper bound on exact evaluations in window mode.
    """

    n_verifications: int = 0  # Σ_h |V_h|·|W_h| (valid pair area)
    n_padded: int = 0  # Σ padded tile area dispatched to exact evaluation
    n_dispatched: int = 0  # valid pair area of tiles that ran exact evaluation
    n_tiles: int = 0  # tiles that ran exact evaluation
    n_cells: int = 0  # non-empty cells
    n_hits: int = 0  # emitted (de-duplicated) hits
    n_pruned: int = 0  # valid pairs eliminated by the pivot filter / windows
    n_tiles_pruned: int = 0  # tiles skipped outright (every pair pruned)
    n_overflow_retries: int = 0  # compact-emission re-dispatches (overflow sentinel)
    prune: str = "none"  # resolved prune mode the engine actually ran
    emit: str = "mask"  # resolved emission path the engine actually ran
    backend: str = "numpy"  # resolved backend the tiles ran on
    bucket_shapes: set = dataclasses.field(default_factory=set)

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_shapes)

    @property
    def occupancy(self) -> float:
        """Valid / padded ratio of the exact-evaluation dispatch — 1.0 means
        zero padding waste. Tiles the pivot filter skipped count in neither
        numerator nor denominator (they cost a bound pass, not a dispatch)."""
        return self.n_dispatched / max(self.n_padded, 1)

    @property
    def n_exact(self) -> int:
        """Pairs that reached exact metric evaluation (post-filter)."""
        return self.n_verifications - self.n_pruned

    @property
    def prune_rate(self) -> float:
        """Fraction of candidate pairs the pivot filter eliminated."""
        return self.n_pruned / max(self.n_verifications, 1)


# ---------------------------------------------------------------------------
# Shared verify semantics (used verbatim by the distributed executor)
# ---------------------------------------------------------------------------


def pair_validity(vids: Array, wids: Array) -> Array:
    """(a, b) bool — True where both sides are real rows (padding id = -1)."""
    return (vids[:, None] >= 0) & (wids[None, :] >= 0)


def apply_dedup(
    hits: Array, vids: Array, wids: Array, wcells: Array, cell_id, cross: bool = False
) -> Array:
    """Mask a raw hit matrix down to pairs this cell should emit.

    Self-join (``cross=False``): ``wcells`` is the *kernel* cell of each W
    row; ``cell_id`` the cell being verified (V rows' own cell). Min-cell
    rule: emit iff the W row's cell is greater than this cell, or equal with
    id_v < id_w.

    R×S (``cross=True``): V and W rows index different sets, so no symmetric
    duplicate exists — every valid hit is emitted (each R row has exactly one
    kernel cell, hence each cross pair is verified exactly once).

    The rule itself lives in :func:`ref.emit_mask` — the single owner both
    emission paths (this mask path and the fused compaction tile) delegate
    to, so they cannot diverge on emission semantics.
    """
    return hits & ref.emit_mask(vids, wids, wcells, cell_id, cross=cross)


def verify_tile(
    xv: Array,
    xw: Array,
    vids: Array,
    wids: Array,
    wcells: Array,
    cell_id,
    *,
    delta: float,
    metric: str,
    backend: str,
    cross: bool = False,
    pv: Array | None = None,
    pw: Array | None = None,
    prune: str = "none",
    premask: Array | None = None,
    delta_bound: float | None = None,
) -> Array:
    """One tile's fused verify: (filter,) distances, threshold, validity, de-dup.

    jit-safe; the streaming engine wraps it in its own jit, the distributed
    stage calls it inside shard_map. ``backend`` and ``prune`` must already
    be concrete (resolve with :func:`resolve_engine_backend` /
    :func:`resolve_prune`). ``cross=True`` switches to R×S semantics
    (validity only, no min-cell). With ``prune="pivot"``, ``pv``/``pw`` are
    the tiles' mapped coordinates and the hit mask is additionally ANDed with
    the L∞ lower-bound survivor mask — identical output by construction (the
    bound never prunes a true hit), but the Pallas path skips exact-distance
    work for all-pruned blocks. ``premask`` (jnp-path only): a survivor mask
    the caller already computed via :func:`candidate_mask` — reused instead
    of re-deriving the bound, so the streaming engine pays for it once.
    ``delta_bound``: the (scale-aware) slackened prune threshold — compute
    it ONCE per join with ``ref.prune_delta(delta, metric, x_abs, m)`` and
    pass the same value to every sub-mask (pre-pass, fused kernel, stats)
    so they can never disagree; None falls back to the scale-free band.
    """
    if prune == "pivot":
        # resolve_prune guarantees coords are present in pivot mode; the
        # assert narrows `Array | None` for the type checker at zero trace
        # cost (it runs on static Python values, not tracers).
        assert pv is not None and pw is not None, 'prune="pivot" without coords'
        if backend == "pallas":
            # Fused kernel recomputes the (cheap, VPU) bound in-block — that
            # is what lets it skip the MXU/VPU exact work per pruned block.
            hits = kops.pairdist_mask_filtered(
                xv, xw, pv, pw, delta, metric, delta_bound=delta_bound,
                use_kernel=True,
            )
        else:
            bound = (
                premask
                if premask is not None
                else ref.bound_mask(pv, pw, delta, delta_bound)
            )
            if metric in ref.METRICS:
                hits = ref.pairdist_mask(xv, xw, delta, metric) & bound
            else:
                # True metrics only the reference module knows (angular,
                # jaccard_minhash): same bound, jnp distance path.
                hits = (distances.pairwise(xv, xw, metric) <= delta) & bound
    elif backend == "pallas":
        hits = kops.pairdist_mask(xv, xw, delta, metric, use_kernel=True)
    elif metric in ref.METRICS:
        hits = ref.pairdist_mask(xv, xw, delta, metric)
    else:
        # Metrics only the reference module knows (angular, jaccard_minhash).
        hits = distances.pairwise(xv, xw, metric) <= delta
    return apply_dedup(hits, vids, wids, wcells, cell_id, cross=cross)


def resolve_engine_backend(backend: str, metric: str) -> str:
    """Engine-level backend resolution: kernel-less metrics fall back to the
    jnp path even under an explicit "pallas" request (capability, not error)."""
    if not kops.supports_kernel(metric):
        return "numpy"
    return kops.resolve_backend(backend, metric)


def prune_supported(metric: str) -> bool:
    """True when the pivot filter is sound for ``metric``: the L∞ lower
    bound needs the triangle inequality, i.e. a TRUE metric (excludes cosine
    and dot — see ``distances.Metric.true_metric``)."""
    m = distances.METRICS.get(metric)
    return m is not None and m.true_metric


def resolve_prune(prune: str, metric: str, have_coords: bool) -> str:
    """Resolve a prune request to a concrete "none" | "pivot" | "window".

    Mirrors :func:`resolve_engine_backend`: a metric the filter is unsound
    for (no triangle inequality) falls back to "none" — capability, not
    error. Requesting pruning WITHOUT mapped coordinates, however, is a
    caller bug and raises.
    """
    if prune not in ("none", "pivot", "window"):
        raise ValueError(
            f'unknown prune mode {prune!r}; expected "none" | "pivot" | "window"'
        )
    if prune != "none" and not have_coords:
        raise ValueError(
            f'prune={prune!r} requires the mapped coordinates (coords / coords_w)'
        )
    if prune != "none" and not prune_supported(metric):
        return "none"
    return prune


def resolve_emit(emit: str, metric: str) -> str:
    """Resolve an emission request to a concrete "mask" | "compact".

    Mirrors :func:`resolve_engine_backend` / :func:`resolve_prune`: compact
    emission needs the fused verify+compaction tile, which exists for the
    exact-metric set (``ref.METRICS``); reference-only metrics (angular,
    jaccard_minhash) resolve back to "mask" — capability, not error.
    """
    if emit not in ("mask", "compact"):
        raise ValueError(f'unknown emit mode {emit!r}; expected "mask" | "compact"')
    if emit == "compact" and metric not in ref.METRICS:
        return "mask"
    return emit


def verify_tile_compact(
    xv: Array,
    xw: Array,
    vids: Array,
    wids: Array,
    wcells: Array,
    cell_id,
    *,
    delta: float,
    metric: str,
    backend: str,
    capacity: int,
    cross: bool = False,
    pv: Array | None = None,
    pw: Array | None = None,
    prune: str = "none",
    delta_bound: float | None = None,
) -> Array:
    """One tile's fused verify + on-device pair compaction, packed for ONE
    host readback.

    Same contract as :func:`verify_tile` on the verify side (filter,
    distances, threshold, validity, min-cell de-dup — all shared with the
    mask path through ``ref``), but instead of the (cap_v, cap_w) hit mask
    it returns a single (capacity + 1, 2) int32 array:

      * rows ``[0:capacity]`` — compacted (v_id, w_id) GLOBAL id pairs,
        padded with -1; emission order is unspecified (backends differ),
        the caller order-normalizes.
      * row ``capacity``     — ``[count, n_cand]``: the TRUE number of
        emitted pairs (``count > capacity`` is the overflow sentinel: the
        buffer contents are then unspecified but ``count`` is exact, so the
        retry capacity can be sized in one step) and the pivot-filter
        survivor count (== valid pair count when pruning is off), so the
        pruning telemetry needs no second readback.

    ``capacity`` must be static (it is an output shape); bucket it with
    :func:`bucket_size` so XLA's compile cache covers the tile stream.
    """
    if prune == "pivot":
        assert pv is not None and pw is not None, 'prune="pivot" without coords'
    else:
        pv = pw = None
    if backend == "pallas":
        pairs, count, n_cand = kops.verify_compact(
            xv, xw, vids, wids, wcells, cell_id, pv, pw,
            delta=delta, metric=metric, capacity=capacity, cross=cross,
            delta_bound=delta_bound, use_kernel=True,
        )
    else:
        pairs, count, n_cand = ref.verify_compact(
            xv, xw, vids, wids, wcells, cell_id,
            delta=delta, metric=metric, capacity=capacity, cross=cross,
            px=pv, py=pw, delta_bound=delta_bound,
        )
    tail = jnp.stack([count, n_cand]).astype(jnp.int32)[None, :]
    return jnp.concatenate([pairs, tail], axis=0)


def candidate_mask(
    pv: Array,
    pw: Array,
    vids: Array,
    wids: Array,
    delta: float,
    delta_bound: float | None = None,
) -> Array:
    """(a, b) bool — pivot-filter SURVIVORS among valid pairs: the L∞ lower
    bound over mapped coordinates is within the (fp-slackened) threshold and
    neither side is padding. Hits are always a subset of this mask when the
    caller passes the SAME ``delta_bound`` here and to the verify call.
    jit-safe; used for pruning-rate telemetry and the streaming engine's
    whole-tile skip."""
    return ref.bound_mask(pv, pw, delta, delta_bound) & pair_validity(vids, wids)


def prune_band(
    delta: float,
    metric: str,
    *arrays: Array | np.ndarray | None,
) -> float:
    """The scale-aware prune threshold for a join over ``arrays`` (payload
    sets; None entries skipped): ``ref.prune_delta`` fed with the joint
    coordinate magnitude and feature count. One value per join, shared by
    every mask so the filter is self-consistent."""
    live = [a for a in arrays if a is not None and a.shape[0] > 0]
    if not live:
        return ref.prune_delta(delta, metric, 0.0, 0)
    # One device->host sync for the whole join, after every per-array max
    # has been enqueued — not one blocking float() per array.
    x_abs = float(jnp.max(jnp.stack([jnp.max(jnp.abs(a)) for a in live])))
    n_feat = max(int(a.shape[1]) for a in live)
    return ref.prune_delta(delta, metric, x_abs, n_feat)


_tile_verify = jax.jit(
    verify_tile,
    static_argnames=("delta", "metric", "backend", "cross", "prune", "delta_bound"),
)

_tile_candidates = jax.jit(candidate_mask, static_argnames=("delta", "delta_bound"))

_tile_compact = jax.jit(
    verify_tile_compact,
    static_argnames=(
        "delta", "metric", "backend", "capacity", "cross", "prune", "delta_bound",
    ),
)


# ---------------------------------------------------------------------------
# Capacity bucketing
# ---------------------------------------------------------------------------


def bucket_size(n: int, cap: int, floor: int = 8) -> int:
    """Quantize a tile side to a static bucket capacity.

    Quarter-power-of-two steps: within each octave [2^k, 2^(k+1)) sizes round
    up to a multiple of 2^k / 4, giving ≤ 33% padding per axis with at most 4
    shapes per octave — small enough that XLA's compile cache covers every
    tile after a handful of traces.
    """
    n = max(int(n), 1)
    if n >= cap:
        return cap
    octave = 1 << max(n - 1, 0).bit_length()  # smallest pow2 >= n
    quantum = max(octave // 4, floor)
    return min(cap, -(-n // quantum) * quantum)


def _pad_gather(
    data: np.ndarray, idx: np.ndarray, cap: int
) -> tuple[np.ndarray, np.ndarray]:
    """Gather rows ``idx`` of ``data`` into a (cap, m) buffer; ids pad = -1."""
    a = idx.size
    rows = np.zeros((cap, data.shape[1]), data.dtype)
    rows[:a] = data[idx]
    ids = np.full((cap,), -1, np.int64)
    ids[:a] = idx
    return rows, ids


def _pad_rows(
    rows: np.ndarray, ids: np.ndarray, cap: int
) -> tuple[np.ndarray, np.ndarray]:
    """Pad pre-gathered rows (a contiguous slice) into a (cap, m) buffer;
    ids pad = -1. The slice-copy twin of :func:`_pad_gather` — the windowed
    prune modes gather each cell ONCE and tile by slicing, so the per-tile
    cost is a memcpy, not a fancy index."""
    a = ids.size
    buf = np.zeros((cap, rows.shape[1]), rows.dtype)
    buf[:a] = rows
    out_ids = np.full((cap,), -1, np.int64)
    out_ids[:a] = ids
    return buf, out_ids


def _prep_w_tiles(
    w_sub: np.ndarray,
    data_w_np: np.ndarray,
    cells_np: np.ndarray,
    coords_w_np: np.ndarray | None,
    cross: bool,
    config: EngineConfig,
) -> list[tuple]:
    """Gather + pad a W-side index range into padded tiles (host-side numpy).

    Returns ``[(wt, cap_w, xw, wids, wc, pw, wbox), ...]`` — one entry per
    ``tile_w`` slice; ``pw`` is None unless mapped coordinates are given,
    ``wbox`` (coordinate bounding box) is None here (no-prune path).
    """
    tiles = []
    for w0 in range(0, w_sub.size, config.tile_w):
        wt = w_sub[w0 : w0 + config.tile_w]
        cap_w = bucket_size(wt.size, config.tile_w, config.min_bucket)
        xw, wids = _pad_gather(data_w_np, wt, cap_w)
        wc = np.full((cap_w,), -1, np.int64)
        if not cross:  # W kernel cells only exist / matter for self-join
            wc[: wt.size] = cells_np[wt]
        pw = None
        if coords_w_np is not None:
            pw = _pad_gather(coords_w_np, wt, cap_w)[0]
        tiles.append((wt, cap_w, xw, wids, wc, pw, None))
    return tiles


def _prep_w_tiles_sorted(
    w_idx: np.ndarray,
    w_data: np.ndarray,
    w_cells: np.ndarray | None,
    w_coords: np.ndarray,
    lo: int,
    hi: int,
    config: EngineConfig,
    need_pw: bool,
) -> list[tuple]:
    """Windowed-mode tile prep over the per-cell PRE-SORTED buffers: the
    [lo, hi) window is contiguous in every buffer, so each tile is a slice
    copy plus its coordinate bounding box (for the bbox skip) — no per-tile
    fancy gather. Same tuple layout as :func:`_prep_w_tiles`."""
    tiles = []
    for w0 in range(lo, hi, config.tile_w):
        w1 = min(w0 + config.tile_w, hi)
        wt = w_idx[w0:w1]
        cap_w = bucket_size(w1 - w0, config.tile_w, config.min_bucket)
        xw, wids = _pad_rows(w_data[w0:w1], wt, cap_w)
        wc = np.full((cap_w,), -1, np.int64)
        if w_cells is not None:  # self-join: kernel cell per W row
            wc[: w1 - w0] = w_cells[w0:w1]
        cw = w_coords[w0:w1]
        pw = _pad_rows(cw, wt, cap_w)[0] if need_pw else None
        tiles.append((wt, cap_w, xw, wids, wc, pw, (cw.min(axis=0), cw.max(axis=0))))
    return tiles


# --- Compact-emission capacity sizing --------------------------------------
#
# The pair buffer's capacity is a STATIC output shape, so it rides the same
# quarter-pow2 bucket ladder as the tile sides. It is seeded from the cost
# model's bound-survival estimate (an overestimate of the hit rate, hence a
# conservative buffer), padded by a slack factor, floored, and grown online
# from observed per-tile counts. All knobs are module-level on purpose —
# tests monkeypatch them to force the overflow→retry→fallback ladder.

DEFAULT_EMIT_RATE = 0.05  # prior hit fraction when no coordinate sample exists
EMIT_SLACK = 2.0  # capacity head-room multiplier over the estimated rate
_EMIT_FLOOR = 32  # minimum pre-bucket capacity, absorbs tiny-tile noise
_EMIT_SAMPLE = 256  # rows fed to the survival estimate (O(sample^2) pairs)
_MAX_OVERFLOW_RETRIES = 3  # capacity doublings before the mask-path fallback


# --- Batched window dispatch ------------------------------------------------
#
# prune="window" cuts tiles small by design (the surviving W window shrinks
# with tile_v), so a per-tile Python->XLA dispatch would swallow the pruned
# area in launch overhead. The jnp window path therefore DEFERS its tiles and
# verifies every same-bucket batch in one vmapped call: one dispatch and one
# host readback per bucket shape per flush, not per tile. The flush area cap
# bounds resident mask memory; emission order does not matter (the final
# sort+unique canonicalizes), so flushing early is always safe.

_BATCH_FLUSH_AREA = 1 << 24  # max summed mask elements resident per flush

_BATCH_VERIFY_JIT: dict[tuple[str, bool], Callable] = {}


def _batched_tile_verify(metric: str, cross: bool) -> Callable:
    """jit(vmap) of :func:`verify_tile` over a leading tile-batch axis, one
    cached trace per (metric, cross); delta rides as a traced scalar so every
    bucket shape shares the same wrapper."""
    fn = _BATCH_VERIFY_JIT.get((metric, cross))
    if fn is None:
        def _one(xv, xw, vids, wids, wcells, cell_id, delta):
            return verify_tile(
                xv, xw, vids, wids, wcells, cell_id,
                delta=delta, metric=metric, backend="numpy", cross=cross,
            )

        fn = jax.jit(jax.vmap(_one, in_axes=(0, 0, 0, 0, 0, 0, None)))
        _BATCH_VERIFY_JIT[(metric, cross)] = fn
    return fn


def _flush_window_batch(
    pending: list[tuple],
    delta: float,
    metric: str,
    cross: bool,
    stats: VerifyStats,
    chunks: list[np.ndarray],
    return_pairs: bool,
) -> None:
    """Dispatch the deferred window tiles: stack same-bucket tiles, run ONE
    vmapped verify per bucket shape, emit hits with one batched nonzero.
    Identical per-tile masks to the immediate path by construction (vmap of
    the same :func:`verify_tile`)."""
    with tracing.span("verify.flush"):
        fn = _batched_tile_verify(metric, cross)
        groups: dict[tuple[int, int], list[int]] = {}
        for i, t in enumerate(pending):
            groups.setdefault((t[0].shape[0], t[1].shape[0]), []).append(i)
        batches = []
        for idxs in groups.values():
            xv = np.stack([pending[i][0] for i in idxs])
            xw = np.stack([pending[i][1] for i in idxs])
            vids = np.stack([pending[i][2] for i in idxs])
            wids = np.stack([pending[i][3] for i in idxs])
            wcs = np.stack([pending[i][4] for i in idxs])
            hs = np.fromiter((pending[i][5] for i in idxs), np.int64, len(idxs))
            batches.append((vids, wids, fn(xv, xw, vids, wids, wcs, hs, float(delta))))
        # ONE device->host sync for the whole flush, after every bucket-shape
        # batch has been enqueued — not one blocking readback per batch (the
        # prune_band idiom).
        outs = jax.device_get([b[2] for b in batches])
        for (vids, wids, _), out in zip(batches, outs):
            bi, vi, wi = out.nonzero()
            stats.n_hits += int(bi.size)
            if return_pairs and bi.size:
                # Padding lanes carry id -1 but can never be hits (pair
                # validity is ANDed inside verify_tile), so the gathered ids
                # are always real rows.
                chunks.append(
                    np.stack([vids[bi, vi], wids[bi, wi]], axis=1).astype(np.int64)
                )
    pending.clear()


def _estimate_emit_rate(coords: np.ndarray, delta: float) -> float:
    """Survival-rate prior for compact-emission capacity sizing.

    The cost model's pivot-pair bound-survival fraction over a deterministic
    row subsample — the engine-side analogue of the distributed planner's
    ``predicted_survival``. An OVERestimate of the true hit rate (the L∞
    bound admits every hit), which is the safe direction for buffer sizing.
    """
    n = coords.shape[0]
    k = min(n, _EMIT_SAMPLE)
    if k < 2:
        return 1.0
    idx = np.linspace(0, n - 1, k).astype(np.int64)
    rate = cost_model.estimate_survival_rate(coords[idx], delta)
    return float(min(max(rate, 1.0 / (k * k)), 1.0))


# ---------------------------------------------------------------------------
# The streaming engine
# ---------------------------------------------------------------------------


def verify_cell_lists(
    data: Array | np.ndarray,
    cells_of: np.ndarray,
    v_lists: Sequence[np.ndarray],
    w_lists: Sequence[np.ndarray],
    delta: float,
    metric: str,
    *,
    config: EngineConfig = EngineConfig(),
    return_pairs: bool = True,
    data_w: Array | np.ndarray | None = None,
    coords: Array | np.ndarray | None = None,
    coords_w: Array | np.ndarray | None = None,
) -> tuple[np.ndarray, VerifyStats]:
    """Run the full reduce phase over explicit per-cell index sets.

    ``data``: (N, m) objects; ``cells_of``: (N,) kernel cell per object;
    ``v_lists[h]`` / ``w_lists[h]``: global row indices of V_h / W_h.
    Returns (pairs, stats) with pairs (n_pairs, 2) int64, i < j, unique.

    Two-set mode: when ``data_w`` is given, ``w_lists`` index into ``data_w``
    (the S side) while ``v_lists``/``cells_of`` index ``data`` (the R side);
    pairs come back as (i ∈ R, j ∈ S) — not reordered, unique by
    construction (each R row sits in exactly one kernel cell).

    Pivot-filter pruning: with ``config.prune="pivot"``, ``coords`` is the
    (N, n) mapped-coordinate matrix of ``data`` (``coords_w`` of ``data_w``
    in two-set mode). Per tile the engine first evaluates the cheap L∞
    lower-bound mask (O(tile·n) vs O(tile·m) exact work); a tile with zero
    surviving pairs skips exact evaluation entirely, the rest run the fused
    filter+pairdist kernel. ``config.prune="window"`` keeps only the
    host-side range/tile pruning (ordered windows + bounding-box skips,
    module docstring) — no per-pair bound lanes, so the pruned dispatch is
    strictly smaller than unpruned. Output pairs are byte-identical to
    ``prune="none"`` in both modes — pruning only ever removes non-hits.

    Compact emission: with ``config.emit="compact"`` each dispatched tile
    returns the fused on-device pair buffer instead of the hit mask (module
    docstring, *Emission paths*); the pair capacity is seeded from the cost
    model's survival estimate when ``coords`` is given, grown on overflow,
    with the mask path as bounded last-resort fallback. Output pairs are
    byte-identical to ``emit="mask"``.
    """
    data_np = np.asarray(data, np.float32)
    cells_np = np.asarray(cells_of)
    cross = data_w is not None
    data_w_np = np.asarray(data_w, np.float32) if cross else data_np
    backend = resolve_engine_backend(config.backend, metric)
    have_coords = coords is not None and (not cross or coords_w is not None)
    prune = resolve_prune(config.prune, metric, have_coords)
    delta_bound = None
    if prune != "none":
        coords_np = np.asarray(coords, np.float32)
        coords_w_np = np.asarray(coords_w, np.float32) if cross else coords_np
        # One scale-aware fp guard band for the whole call — every sub-mask
        # (window, bbox skip, pre-pass, fused kernel) shares it, so
        # hits ⊆ candidates always.
        delta_bound = prune_band(
            delta, metric, data_np, data_w_np if cross else None
        )
    emit = resolve_emit(config.emit, metric)
    # Which tiles actually carry the on-device pair buffer (module docstring,
    # *Emission lowering*): the Pallas backend always; the jnp path only in
    # "pivot" mode, where the buffer's counter row carries the per-pair
    # survivor count the telemetry contract needs. Everything else lowers
    # compact emission to mask dispatch + host pack — identical bytes.
    buffered = emit == "compact" and (backend == "pallas" or prune == "pivot")
    # Batched window dispatch (see _flush_window_batch): the jnp window path
    # defers its (deliberately small) tiles and verifies same-bucket batches
    # in one vmapped call each, so launch overhead cannot swallow the area
    # the windows pruned. The Pallas path keeps per-tile dispatch — its
    # block-skip already amortizes launches in-kernel.
    batch_w = prune == "window" and backend != "pallas"
    pending: list[tuple] = []
    pending_area = 0
    emit_rate = DEFAULT_EMIT_RATE
    if buffered and coords is not None:
        # Capacity prior: bound-survival fraction on a coordinate subsample,
        # measured at delta_bound when the filter runs so prior and filter
        # can never disagree on what survives.
        emit_rate = _estimate_emit_rate(
            np.asarray(coords, np.float32),
            float(delta_bound if delta_bound is not None else delta),
        )
    stats = VerifyStats(prune=prune, emit=emit, backend=backend)
    chunks: list[np.ndarray] = []

    for h, (v_idx, w_idx) in enumerate(zip(v_lists, w_lists)):
        # spjoin-lint: allow[host-sync] -- index lists arrive as host arrays/lists; once per CELL, not per tile
        v_idx = np.asarray(v_idx)
        w_idx = np.asarray(w_idx)  # spjoin-lint: allow[host-sync] -- same: host-side cell index normalization
        if v_idx.size == 0 or w_idx.size == 0:
            continue
        stats.n_cells += 1
        stats.n_verifications += int(v_idx.size) * int(w_idx.size)
        with tracing.span("verify.cell", v=int(v_idx.size), w=int(w_idx.size)):
            w_coord0 = None
            if prune != "none":
                # Window refinement (module docstring): order both sides by ONE
                # mapped coordinate, so V tiles become coordinate bands and the
                # binary search below slices each one's W range down to the
                # ± delta_bound window. Any 1-Lipschitz coordinate is sound, so
                # pick the one this cell's W rows spread widest on — the kernel
                # grid already localizes the partitioned coordinates, leaving
                # them little window to cut. Pure reordering — the emitted pair
                # SET is unchanged; everything sliced off is a provable non-hit.
                wc_all = coords_w_np[w_idx]
                sort_dim = int((wc_all.max(axis=0) - wc_all.min(axis=0)).argmax())
                v_idx = v_idx[np.argsort(coords_np[v_idx, sort_dim], kind="stable")]
                word = np.argsort(wc_all[:, sort_dim], kind="stable")
                w_idx = w_idx[word]
                # One gather per cell into sort order; every tile below is a
                # contiguous slice of these buffers (window = contiguous range).
                w_coords_cell = wc_all[word]
                w_coord0 = w_coords_cell[:, sort_dim]
                w_data_cell = data_w_np[w_idx]
                w_cells_cell = None if cross else cells_np[w_idx]
                v_coords_cell = coords_np[v_idx]
                v_data_cell = data_np[v_idx]
                w_tiles = None  # sliced per V tile from the surviving window
            else:
                # W tiles are prepared once per cell (not per V tile): the copies
                # are O(|W_h|·m) — linear in cell size, like the input rows
                # themselves — while only the pair product streams tile-by-tile.
                with tracing.span("verify.w_tiles"):
                    w_tiles = _prep_w_tiles(w_idx, data_w_np, cells_np, None, cross, config)
            for v0 in range(0, v_idx.size, config.tile_v):
                vt = v_idx[v0 : v0 + config.tile_v]
                cap_v = bucket_size(vt.size, config.tile_v, config.min_bucket)
                pv = v_box = None
                if prune != "none":
                    v_coords = v_coords_cell[v0 : v0 + config.tile_v]
                    v_box = (v_coords.min(axis=0), v_coords.max(axis=0))
                    xv, vids = _pad_rows(v_data_cell[v0 : v0 + config.tile_v], vt, cap_v)
                    if prune == "pivot":  # per-pair bound rides into the tile
                        pv = _pad_rows(v_coords, vt, cap_v)[0]
                    vc = v_coords[:, sort_dim]
                    lo = int(np.searchsorted(w_coord0, vc.min() - delta_bound, "left"))
                    hi = int(np.searchsorted(w_coord0, vc.max() + delta_bound, "right"))
                    # W rows outside [lo, hi) differ from every V row in this
                    # tile by more than delta_bound on one 1-Lipschitz coordinate
                    # — already above the L∞ lower bound, pruned with zero
                    # gather and zero dispatch.
                    stats.n_pruned += int(vt.size) * int(w_idx.size - (hi - lo))
                    if lo == hi:
                        continue
                    with tracing.span("verify.w_tiles"):
                        w_tiles = _prep_w_tiles_sorted(
                            w_idx, w_data_cell, w_cells_cell, w_coords_cell,
                            lo, hi, config, need_pw=prune == "pivot",
                        )
                else:
                    xv, vids = _pad_gather(data_np, vt, cap_v)
                for wt, cap_w, xw, wids, wc, pw, w_box in w_tiles:
                    n_valid = int(vt.size) * int(wt.size)
                    if v_box is not None and w_box is not None:
                        # Bounding-box tile skip: interval arithmetic on the
                        # mapped coordinates. The gap between the V and W boxes
                        # lower-bounds every pair's L∞ bound, so a gap beyond
                        # delta_bound means the whole tile is provable non-hits
                        # — skipped before any dispatch, on every coordinate
                        # (the window above only exploits the sort coordinate).
                        gap = np.maximum(
                            w_box[0] - v_box[1], v_box[0] - w_box[1]
                        ).max()
                        if gap > delta_bound:
                            stats.n_pruned += n_valid
                            stats.n_tiles_pruned += 1
                            continue
                    tile = tracing.span(
                        "verify.tile", cap_v=cap_v, cap_w=cap_w, n_valid=n_valid
                    )
                    with tile:
                        premask = None
                        if emit == "mask" and prune == "pivot":
                            # Cheap pre-pass: O(tile·n) bound vs O(tile·m) exact.
                            # Compact emission skips it — its filter runs fused
                            # in-kernel and the survivor count comes back in-band.
                            with tracing.span("verify.prepass"):
                                cand_dev = _tile_candidates(
                                    pv, pw, vids, wids, delta=float(delta),
                                    delta_bound=delta_bound,
                                )
                                # spjoin-lint: allow[host-sync] -- the whole-tile skip decision IS a sync: O(tile*n) bound read back to elide the O(tile*m) kernel
                                n_cand = int(np.asarray(cand_dev).sum())
                            stats.n_pruned += n_valid - n_cand
                            if n_cand == 0:
                                # Every pair pruned: the exact kernel never runs.
                                stats.n_tiles_pruned += 1
                                tile.add(n_cand=0, n_hits=0, retries=0)
                                continue
                            tile.add(n_cand=n_cand)
                            if backend != "pallas":
                                premask = cand_dev  # jnp path reuses the bound
                        stats.n_tiles += 1
                        stats.n_padded += cap_v * cap_w
                        stats.n_dispatched += n_valid
                        stats.bucket_shapes.add((cap_v, cap_w))
                        if batch_w:
                            pending.append((xv, xw, vids, wids, wc, h))
                            pending_area += cap_v * cap_w
                            if pending_area >= _BATCH_FLUSH_AREA:
                                # Cap resident mask memory; early flushes are safe
                                # (the final sort+unique canonicalizes pair order).
                                _flush_window_batch(
                                    pending, delta, metric, cross,
                                    stats, chunks, return_pairs,
                                )
                                pending_area = 0
                            continue
                        # "window" prunes entirely on the host (above); the tile
                        # itself runs the plain verify — no per-pair bound lanes.
                        tile_prune = prune if prune == "pivot" else "none"
                        tile_band = delta_bound if tile_prune == "pivot" else None
                        mode = "compact" if buffered else "mask"
                        cap_pairs = 0
                        if mode == "compact":
                            cap_pairs = bucket_size(
                                int(n_valid * min(emit_rate * EMIT_SLACK, 1.0)) + _EMIT_FLOOR,
                                cap_v * cap_w,
                            )
                        tile_counts = None
                        out = None
                        retries = 0
                        for attempt in range(_MAX_OVERFLOW_RETRIES + 2):
                            with tracing.span("verify.dispatch"):
                                if mode == "compact":
                                    out_dev = _tile_compact(
                                        xv, xw, vids, wids, wc, h,
                                        delta=float(delta), metric=metric, backend=backend,
                                        capacity=cap_pairs, cross=cross, pv=pv, pw=pw,
                                        prune=tile_prune, delta_bound=tile_band,
                                    )
                                else:
                                    out_dev = _tile_verify(
                                        xv, xw, vids, wids, wc, h,
                                        delta=float(delta), metric=metric, backend=backend,
                                        cross=cross, pv=pv, pw=pw, prune=tile_prune,
                                        premask=premask, delta_bound=tile_band,
                                    )
                            with tracing.span("verify.readback"):
                                # spjoin-lint: allow[host-sync] -- tile result must land on host to become (i, j) pairs; ONE readback per dispatch, both emission paths
                                out = np.asarray(out_dev)
                            if mode != "compact":
                                break
                            tile_counts = (int(out[-1, 0]), int(out[-1, 1]))
                            if tile_counts[0] <= cap_pairs:
                                break
                            # Overflow sentinel: count > capacity means the buffer
                            # contents are unspecified, but count itself is the TRUE
                            # total — the retry bucket is sized exactly in one step.
                            # Bounded retries, then the mask path as last resort;
                            # the emitted pair set is identical on every rung.
                            stats.n_overflow_retries += 1
                            retries += 1
                            if attempt >= _MAX_OVERFLOW_RETRIES:
                                mode = "mask"
                            else:
                                cap_pairs = bucket_size(
                                    max(tile_counts[0], 2 * cap_pairs), cap_v * cap_w
                                )
                        if tile_counts is not None:
                            # The compact path's survivor count comes back in-band.
                            tile.add(n_cand=tile_counts[1])
                        with tracing.span("verify.emit"):
                            if mode == "compact":
                                count, n_cand = tile_counts
                                if prune == "pivot":
                                    stats.n_pruned += n_valid - n_cand
                                # Grow the prior from observed hit rates so one hot tile
                                # does not turn into a retry per tile downstream.
                                emit_rate = max(emit_rate, count / max(n_valid, 1))
                                n_hits = count
                                if return_pairs and count:
                                    chunks.append(out[:count].astype(np.int64))
                            else:
                                if tile_counts is not None and prune == "pivot":
                                    # Overflow fallback: the mask path ran, but the last
                                    # compact dispatch already reported the survivor
                                    # count — pruning telemetry stays emission-invariant.
                                    stats.n_pruned += n_valid - tile_counts[1]
                                mask = out
                                n_hits = 0
                                if mask.any():
                                    vi, wi = np.nonzero(mask)
                                    n_hits = vi.size
                                    if return_pairs:
                                        chunks.append(np.stack([vt[vi], wt[wi]], axis=1))
                            stats.n_hits += n_hits
                        tile.add(n_hits=n_hits, retries=retries)

    if pending:
        _flush_window_batch(
            pending, delta, metric, cross, stats, chunks, return_pairs
        )
    with tracing.span("verify.finalize"):
        if chunks:
            # Each pair is emitted once (min-cell rule / unique kernel cell);
            # sort+unique is kept as a cheap invariant matching the seed
            # executor. Cross pairs index different sets, so no column sort.
            pairs = np.concatenate(chunks)
            if not cross:
                pairs = np.sort(pairs, axis=1)
            pairs = np.unique(pairs, axis=0)
        else:
            pairs = np.zeros((0, 2), np.int64)
        pairs = pairs.astype(np.int64)
    return pairs, stats


def verify_resident(
    data: Array | np.ndarray,
    cells_of: np.ndarray,
    v_lists: Sequence[np.ndarray],
    member_w: np.ndarray,
    delta: float,
    metric: str,
    *,
    config: EngineConfig = EngineConfig(),
    data_w: Array | np.ndarray,
    coords: Array | np.ndarray | None = None,
    coords_w: Array | np.ndarray | None = None,
) -> tuple[np.ndarray, VerifyStats]:
    """Delta-vs-resident cross verify: W rows come from a whole-membership
    matrix (|W|, p) over ``data_w`` (a routed query batch or an insertion
    delta), V rows from the RESIDENT per-cell index lists. This is the one
    tile path both the serving ``query_batch`` and the streaming
    ``insert_batch`` stream through — one membership→w_lists derivation, so
    the two callers can never disagree on how a routed row reaches a cell.
    Pairs come back as (i ∈ resident, j ∈ delta), R×S semantics.
    """
    member_np = np.asarray(member_w, bool)
    w_lists = [np.flatnonzero(member_np[:, h]) for h in range(len(v_lists))]
    return verify_cell_lists(
        data, np.asarray(cells_of), v_lists, w_lists, delta, metric,
        config=config, data_w=data_w, coords=coords, coords_w=coords_w,
    )


def verify_pairs(
    data: Array | np.ndarray,
    cells: np.ndarray,
    member: np.ndarray,
    delta: float,
    metric: str,
    *,
    config: EngineConfig = EngineConfig(),
    return_pairs: bool = True,
    data_w: Array | np.ndarray | None = None,
    coords: Array | np.ndarray | None = None,
    coords_w: Array | np.ndarray | None = None,
) -> tuple[np.ndarray, VerifyStats]:
    """Reduce phase from a kernel-cell assignment + whole-membership matrix.

    Self-join: ``cells``: (N,) int cell id of ``data``; ``member``: (N, p)
    bool whole membership of the same rows.

    R×S: ``data``/``cells`` describe R (the V side); ``data_w`` is S and
    ``member`` is then S's whole membership (|S|, p) — V_h comes from R's
    kernel cells, W_h from S's whole membership.

    ``coords`` / ``coords_w``: mapped coordinates of ``data`` / ``data_w``
    (required when ``config.prune="pivot"`` — see the module docstring).

    Derives the per-cell index sets and streams them through
    :func:`verify_cell_lists`.
    """
    cells_np = np.asarray(cells)
    member_np = np.asarray(member)
    p = member_np.shape[1]
    order = np.argsort(cells_np, kind="stable")
    bounds = np.searchsorted(cells_np[order], np.arange(p + 1))
    v_lists = [order[bounds[h] : bounds[h + 1]] for h in range(p)]
    w_lists = [np.flatnonzero(member_np[:, h]) for h in range(p)]
    return verify_cell_lists(
        data, cells_np, v_lists, w_lists, delta, metric,
        config=config, return_pairs=return_pairs, data_w=data_w,
        coords=coords, coords_w=coords_w,
    )


# ---------------------------------------------------------------------------
# The seed's dense per-cell loop — kept as the benchmark baseline / oracle
# ---------------------------------------------------------------------------


def reference_verify(
    data: Array | np.ndarray,
    cells: np.ndarray,
    member: np.ndarray,
    delta: float,
    metric: str,
    *,
    return_pairs: bool = True,
) -> tuple[np.ndarray, int]:
    """The pre-engine reduce loop: one dense eager pairwise matrix per cell.

    O(|V_h|·|W_h|·m) intermediates per cell, no tiling, no fusion. Retained
    verbatim so benchmarks can report engine speedup against the seed path
    and tests can cross-check semantics. Returns (pairs, n_verifications).
    """
    allx = jnp.asarray(data)
    cells_np = np.asarray(cells)
    member_np = np.asarray(member)
    metric_fn = distances.get_metric(metric)
    n_verif = 0
    chunks: list[np.ndarray] = []
    for h in range(member_np.shape[1]):
        v_idx = np.flatnonzero(cells_np == h)
        w_idx = np.flatnonzero(member_np[:, h])
        if v_idx.size == 0 or w_idx.size == 0:
            continue
        n_verif += int(v_idx.size) * int(w_idx.size)
        d = np.asarray(metric_fn.pairwise(allx[v_idx], allx[w_idx]))
        hit_v, hit_w = np.nonzero(d <= delta)
        gi = v_idx[hit_v]
        gj = w_idx[hit_w]
        cj = cells_np[gj]
        keep = ((cj == h) & (gi < gj)) | (cj > h)
        if return_pairs and keep.any():
            chunks.append(np.stack([gi[keep], gj[keep]], axis=1))
    if chunks:
        pairs = np.unique(np.sort(np.concatenate(chunks), axis=1), axis=0)
    else:
        pairs = np.zeros((0, 2), np.int64)
    return pairs.astype(np.int64), n_verif
