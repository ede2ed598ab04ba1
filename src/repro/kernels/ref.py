"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's test sweeps shapes/dtypes
and asserts allclose against the function of the same name here. They are
deliberately written in the most obvious form (no tiling, no fusion).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jnp.ndarray

METRICS = ("l1", "l2", "linf", "cosine", "dot")


def _normalize(x: Array) -> Array:
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def pairdist(x: Array, y: Array, metric: str = "l2") -> Array:
    """All-pairs distances, x: (a, m), y: (b, m) -> (a, b) float32."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    if metric == "l1":
        return jnp.abs(x[:, None, :] - y[None, :, :]).sum(-1)
    if metric == "linf":
        return jnp.abs(x[:, None, :] - y[None, :, :]).max(-1)
    if metric == "l2":
        sq = (x * x).sum(-1)[:, None] + (y * y).sum(-1)[None, :] - 2.0 * x @ y.T
        return jnp.sqrt(jnp.maximum(sq, 0.0))
    if metric == "cosine":
        return 1.0 - _normalize(x) @ _normalize(y).T
    if metric == "dot":
        return x @ y.T
    raise ValueError(f"unknown metric {metric!r}")


def pairdist_mask(x: Array, y: Array, delta: float, metric: str = "l2") -> Array:
    """Thresholded join mask: (a, b) bool, True where D(x_i, y_j) <= delta."""
    return pairdist(x, y, metric) <= delta


_EPS32 = float(jnp.finfo(jnp.float32).eps)


def prune_delta(
    delta: float, metric: str = "l1", x_abs: float = 0.0, n_feat: int = 0
) -> float:
    """The pivot filter's fp guard band — the threshold the L-inf lower
    bound is pruned against.

    Mathematically the bound over mapped coordinates never exceeds the true
    distance (each coordinate is 1-Lipschitz), but both sides are computed
    in fp32, and the DISTANCE side is the worse-conditioned one: l2's
    MXU-friendly dot-expansion ``sqrt(|x|^2 + |y|^2 - 2xy)`` carries an
    absolute error ~ X^2·eps/delta near the threshold (X = coordinate
    magnitude), and l1/linf accumulate ~ m·X·eps — so a pair whose computed
    distance is <= delta can see a (well-conditioned) computed bound above
    delta when the data sits far from the origin. Pruning against a
    SCALE-AWARE slackened threshold restores fp soundness: callers pass
    ``x_abs`` (max |payload coordinate|) and ``n_feat`` (payload dims), and
    the slack covers the worst-case rounding of the distance path, the
    bound path (coordinates are distances, <= the m·X-ish diameter), and
    the threshold compare. This is what the byte-identity invariant
    (prune="pivot" == prune="none") relies on; the slack only admits extra
    candidates for exact evaluation, it never changes emitted pairs.

    With the scale left at 0 (unknown), only the fixed band remains —
    sound for data of modest magnitude (|x| up to ~1e2 at delta ~1e-2+),
    which is why every internal caller threads the real scale through.
    """
    d = float(delta)
    x = float(x_abs)
    m = float(max(n_feat, 1))
    if metric == "l2":
        # dot-expansion: |d̂² − d²| ≲ c·m·eps·X² (each of the ~2m+4 terms
        # rounds at ulp(X²)). Through the sqrt the worst DISTANCE violation
        # is sqrt of that (when d̂² collapses toward 0) plus the first-order
        # term near the threshold; the coordinates are l2 distances with the
        # same error profile, hence the 3x on the sqrt term (x-side, y-side,
        # bound-side). Empirically ~2x above the measured worst case.
        e2 = 8.0 * m * _EPS32
        slack = 3.0 * (e2 ** 0.5) * x + e2 * x * x / (2.0 * max(d, _EPS32))
    elif metric in ("l1", "linf"):
        # Same-sign close subtractions are exact (Sterbenz); what is left is
        # accumulation rounding of the coordinate distances themselves,
        # whose magnitudes reach the ~m·X diameter — hence m²·X·eps.
        slack = 4.0 * m * (m + 1.0) * _EPS32 * x
    else:
        # Bounded-output metrics (angular, jaccard_minhash, cosine): the
        # distance and the coordinates live in [0, 1]-ish ranges.
        slack = 16.0 * _EPS32
    return d * (1.0 + 1e-4) + 1e-6 + slack


def bound_mask(
    px: Array, py: Array, delta: float, delta_bound: float | None = None
) -> Array:
    """Pivot-filter survivor mask: (a, b) bool over mapped coordinates.

    ``px``/``py`` are per-object distances to the shared anchors (the space
    mapping's output). True where the L-inf lower bound
    max_p |px_i[p] - py_j[p]| is within the slackened threshold — i.e. the
    pair CANNOT be pruned and must be exactly evaluated. ``delta_bound``
    overrides the (scale-free) default band; every engine/executor path
    threads a single scale-aware value through all of its sub-masks so the
    pre-pass, the fused kernel and the telemetry always agree.
    """
    if delta_bound is None:
        delta_bound = prune_delta(delta)
    return pairdist(px, py, "linf") <= delta_bound


def pairdist_mask_filtered(
    x: Array,
    y: Array,
    px: Array,
    py: Array,
    delta: float,
    metric: str = "l2",
    delta_bound: float | None = None,
) -> Array:
    """Fused pivot-filter + thresholded join mask (a, b) bool.

    Semantically ``pairdist_mask(x, y, delta, metric) & bound_mask(px, py,
    delta, delta_bound)``; because the bound is a true lower bound (triangle
    inequality over the anchors, plus the fp guard band of
    :func:`prune_delta`), the result is IDENTICAL to the unfiltered mask —
    the filter only removes pairs whose distance already exceeds delta.
    Oracle for the fused Pallas kernel, which additionally skips the
    exact-distance work for fully pruned tiles.
    """
    return pairdist_mask(x, y, delta, metric) & bound_mask(px, py, delta, delta_bound)


def pairdist_count(x: Array, y: Array, delta: float, metric: str = "l2") -> Array:
    """Per-row join fan-out: (a,) int32 — |{j : D(x_i, y_j) <= delta}|."""
    return pairdist_mask(x, y, delta, metric).sum(-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fused reduce phase: emission semantics + on-device pair compaction
# ---------------------------------------------------------------------------


def emit_mask(
    vids: Array, wids: Array, wcells: Array, cell_id, cross: bool = False
) -> Array:
    """(a, b) bool — pairs this cell is allowed to emit (pre-distance).

    Padding validity (id = -1 rows are never emitted) plus the min-cell
    de-dup rule of the reduce phase: a hit (v, w) with kernel cells
    (g = ``cell_id``, h = ``wcells[j]``) is emitted by cell min(g, h) only;
    within one cell both orders are present, so keep id_v < id_w. R×S mode
    (``cross=True``): the sets are disjoint and each R row lives in exactly
    one kernel cell, so validity alone suffices. Single owner of the rule —
    ``core.verify.apply_dedup`` and the fused compaction kernel both
    delegate here.
    """
    valid = (vids[:, None] >= 0) & (wids[None, :] >= 0)
    if cross:
        return valid
    return valid & (
        (wcells[None, :] > cell_id)
        | ((wcells[None, :] == cell_id) & (vids[:, None] < wids[None, :]))
    )


_SCAN_WIDTH = 128


def prefix_sum(counts: Array, peak: int = 1) -> Array:
    """Inclusive prefix sum of a flat int32 vector with entries in [0, peak].

    ``jnp.cumsum`` of the same vector, computed in rows of 128 with one
    upper-triangular matmul per level while that level's row sums (at most
    128·peak) are integers f32 holds exactly (at most 2^24); the row totals
    left then (n / 2^21 of them for a 0/1 mask) take a plain cumsum. The
    TPU compiler takes tens of seconds over a plain cumsum of a
    million-entry tile mask, and the engine compiles one per tile bucket;
    this form compiles in a few seconds at any tile size."""
    n = counts.shape[0]
    if n <= _SCAN_WIDTH or peak * _SCAN_WIDTH > 1 << 24:
        return jnp.cumsum(counts)
    rows = jnp.pad(counts, (0, (-n) % _SCAN_WIDTH)).reshape(-1, _SCAN_WIDTH)
    upper = jnp.triu(jnp.ones((_SCAN_WIDTH, _SCAN_WIDTH), jnp.float32))
    inner = jnp.dot(
        rows.astype(jnp.float32), upper, precision=jax.lax.Precision.HIGHEST
    ).astype(jnp.int32)
    row_total = inner[:, -1]
    offset = prefix_sum(row_total, peak * _SCAN_WIDTH) - row_total
    return (inner + offset[:, None]).reshape(-1)[:n]


def compact_mask(
    mask: Array, vids: Array, wids: Array, capacity: int
) -> tuple[Array, Array]:
    """Prefix-sum compaction of a hit mask into a fixed-capacity pair buffer.

    Returns ``(pairs, count)``: ``pairs`` is (capacity, 2) int32 holding
    ``(vids[i], wids[j])`` for the True cells of ``mask`` in row-major
    (``np.nonzero``) order, padded with -1; ``count`` is int32 and equals the
    TRUE total number of hits — ``count > capacity`` signals overflow, in
    which case the retained prefix is the first ``capacity`` hits but callers
    must treat the buffer as unspecified and retry at a larger capacity.

    Scatter-free formulation (the jnp/XLA fast path): the k-th hit's flat
    position is the first index where the inclusive prefix sum of the
    flattened mask reaches k — a ``searchsorted`` over ``capacity`` query
    points inverts the cumsum without a 1-element-scatter per hit.
    """
    a, b = mask.shape
    if a == 0 or b == 0:
        return (
            jnp.full((capacity, 2), -1, jnp.int32),
            jnp.zeros((), jnp.int32),
        )
    incl = prefix_sum(mask.astype(jnp.int32).reshape(-1))
    count = incl[-1].astype(jnp.int32)
    q = jnp.arange(1, capacity + 1, dtype=incl.dtype)
    pos = jnp.minimum(jnp.searchsorted(incl, q, side="left"), a * b - 1)
    ok = q <= count
    pv = jnp.where(ok, vids[pos // b].astype(jnp.int32), -1)
    pw = jnp.where(ok, wids[pos % b].astype(jnp.int32), -1)
    return jnp.stack([pv, pw], axis=1), count


def select_hits(mask: Array, capacity: int) -> tuple[Array, Array, Array]:
    """Row and column of the first ``capacity`` hits of a 2-D mask.

    Returns ``(rows, cols, count)``: ``rows`` and ``cols`` are (capacity,)
    int32 in row-major (``np.nonzero``) order, meaningful for the first
    ``min(count, capacity)`` entries and clamped into the mask elsewhere;
    ``count`` is int32 and equals the TRUE number of hits (``count >
    capacity`` is the overflow sentinel, as in :func:`compact_mask`).

    A two-level rank, for masks too large for one flat prefix sum: the hits
    of each chunk of 128 columns are counted, the chunk counts take a
    :func:`prefix_sum`, a ``searchsorted`` finds the k-th hit's chunk, and
    one 128-wide triangular matmul over the gathered chunks ranks it within
    its chunk. Work past the chunk counts grows with ``capacity``, not with
    the mask.
    """
    a, b = mask.shape
    if a == 0 or b == 0:
        zeros = jnp.zeros((capacity,), jnp.int32)
        return zeros, zeros, jnp.zeros((), jnp.int32)
    width = min(b, _SCAN_WIDTH)
    per_row = -(-b // width)
    if per_row * width != b:
        mask = jnp.pad(mask, ((0, 0), (0, per_row * width - b)))
    chunks = mask.reshape(-1, width)
    counts = chunks.sum(axis=1, dtype=jnp.int32)
    incl = prefix_sum(counts, width)
    count = incl[-1]
    k = jnp.arange(1, capacity + 1, dtype=jnp.int32)
    chunk = jnp.minimum(jnp.searchsorted(incl, k, side="left"), chunks.shape[0] - 1)
    rank = k - (incl[chunk] - counts[chunk])  # 1-based, within the chunk
    upper = jnp.triu(jnp.ones((width, width), jnp.float32))
    within = jnp.dot(
        chunks[chunk].astype(jnp.float32), upper, precision=jax.lax.Precision.HIGHEST
    )
    col = (within < rank[:, None].astype(jnp.float32)).sum(axis=1, dtype=jnp.int32)
    cols = jnp.minimum((chunk % per_row) * width + col, b - 1)
    return (chunk // per_row).astype(jnp.int32), cols.astype(jnp.int32), count


def verify_compact(
    x: Array,
    y: Array,
    vids: Array,
    wids: Array,
    wcells: Array,
    cell_id,
    *,
    delta: float,
    metric: str,
    capacity: int,
    cross: bool = False,
    px: Array | None = None,
    py: Array | None = None,
    delta_bound: float | None = None,
) -> tuple[Array, Array, Array]:
    """Fused verify + on-device pair compaction, the obvious-form oracle.

    One tile's whole reduce step: (optional) pivot-filter bound, exact
    pairwise distance, ``<= delta`` threshold, validity + min-cell de-dup,
    then prefix-sum compaction of the surviving hits into a (capacity, 2)
    int32 id-pair buffer. Returns ``(pairs, count, n_cand)``:

      * ``pairs`` / ``count`` as :func:`compact_mask` (count is the TRUE hit
        total — ``count > capacity`` means overflow, retry bigger);
      * ``n_cand`` int32: valid pairs surviving the pivot filter (== the
        valid pair count when ``px`` is None) — same quantity the streaming
        engine's candidate pre-pass reports, so prune telemetry is identical
        across emission modes.
    """
    valid = (vids[:, None] >= 0) & (wids[None, :] >= 0)
    hits = pairdist_mask(x, y, delta, metric)
    if px is not None:
        assert py is not None
        bound = bound_mask(px, py, delta, delta_bound)
        n_cand = (bound & valid).sum().astype(jnp.int32)
        hits = hits & bound
    else:
        n_cand = valid.sum().astype(jnp.int32)
    hits = hits & emit_mask(vids, wids, wcells, cell_id, cross)
    pairs, count = compact_mask(hits, vids, wids, capacity)
    return pairs, count, n_cand


MEMBER_WORD = 32  # whole-membership bits per packed uint32 word
BIG = 3.0e38  # finite ±inf stand-in for box edges (fp32-representable);
#   core.partition aliases this — one owner for the sentinel


def pack_membership(member: Array) -> Array:
    """Pack an (N, p) bool membership mask 32 partitions per uint32 word:
    (N, ⌈p/32⌉), bit ``j % 32`` of word ``j // 32`` set iff ``member[:, j]``.
    Trailing pad bits of the last word are 0 (padded partitions are never
    members). Disjoint-bit sum == bitwise or, so the pack is exact."""
    n, p = member.shape
    pad = (-p) % MEMBER_WORD
    words = (p + pad) // MEMBER_WORD
    m = jnp.pad(member.astype(jnp.uint32), ((0, 0), (0, pad)))
    m = m.reshape(n, words, MEMBER_WORD)
    shift = jnp.arange(MEMBER_WORD, dtype=jnp.uint32)
    return (m << shift[None, None, :]).sum(-1)


def unpack_membership(bits: Array, p: int) -> Array:
    """Inverse of :func:`pack_membership`: (N, ⌈p/32⌉) uint32 → (N, p) bool."""
    shift = jnp.arange(MEMBER_WORD, dtype=jnp.uint32)
    b = (bits[:, :, None] >> shift[None, None, :]) & jnp.uint32(1)
    n, words = bits.shape
    return b.reshape(n, words * MEMBER_WORD)[:, :p].astype(bool)


def assign_kernel_cells(xm: Array, kernel_lo: Array, kernel_hi: Array) -> Array:
    """(N,) int32 kernel cell ids — the half-open [lo, hi) containment argmax
    (exactly one box contains; an all-False row degenerates to cell 0)."""
    xm = xm.astype(jnp.float32)
    inside_k = (xm[:, None, :] >= kernel_lo[None]) & (xm[:, None, :] < kernel_hi[None])
    return jnp.argmax(inside_k.all(-1), axis=1).astype(jnp.int32)


def membership_bits(xm: Array, whole_lo: Array, whole_hi: Array) -> Array:
    """(N, ⌈p/32⌉) uint32 packed whole membership — closed [lo, hi] boxes."""
    xm = xm.astype(jnp.float32)
    inside_w = (xm[:, None, :] >= whole_lo[None]) & (xm[:, None, :] <= whole_hi[None])
    return pack_membership(inside_w.all(-1))


def assign_membership(
    xm: Array,
    kernel_lo: Array,
    kernel_hi: Array,
    whole_lo: Array,
    whole_hi: Array,
) -> tuple[Array, Array]:
    """Kernel cell id + packed whole membership from mapped coordinates.

    The obvious (N, p, n) broadcast form — bit-for-bit the historical jnp
    map-phase path (``partition.assign_kernel`` / ``whole_membership``):
    kernel boxes are half-open [lo, hi), whole boxes closed [lo, hi]. Oracle
    for the fused Pallas kernel in ``mapassign.py``. Returns
    (cells (N,) int32, bits (N, ⌈p/32⌉) uint32).
    """
    return (
        assign_kernel_cells(xm, kernel_lo, kernel_hi),
        membership_bits(xm, whole_lo, whole_hi),
    )


def map_assign(
    x: Array,
    anchors: Array,
    kernel_lo: Array,
    kernel_hi: Array,
    whole_lo: Array,
    whole_hi: Array,
    metric: str = "l2",
) -> tuple[Array, Array, Array]:
    """Full map phase: space map + assign + membership, unfused.

    Semantic ground truth for the fused kernel: ``xm = pairdist(x, anchors)``
    then :func:`assign_membership`. Returns (xm, cells, bits)."""
    xm = pairdist(x, anchors, metric)
    cells, bits = assign_membership(xm, kernel_lo, kernel_hi, whole_lo, whole_hi)
    return xm, cells, bits


def histogram(u: Array, t: int, weights: Array | None = None) -> Array:
    """Per-dimension equal-width histogram of u in [0, 1): (n, m) -> (m, t).

    This is the GoF cell-count pass (paper Eq. 9): cell_j counts per marginal.
    ``weights``: optional (n,) validity/padding mask.
    """
    cell = jnp.clip((u.astype(jnp.float32) * t).astype(jnp.int32), 0, t - 1)
    onehot = (cell[:, :, None] == jnp.arange(t)[None, None, :]).astype(jnp.float32)
    if weights is not None:
        onehot = onehot * weights.astype(jnp.float32)[:, None, None]
    return onehot.sum(0)
