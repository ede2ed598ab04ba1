"""Public jit'd wrappers around the Pallas kernels.

Handles everything the raw kernels assume away: zero-padding to block
multiples, cosine pre-normalization, backend dispatch, and padding removal.

Backend dispatch (``backend=`` on every wrapper):

  "pallas"  the Pallas kernel — compiled on TPU, ``interpret=True`` elsewhere
            (the kernel body then runs as reference Python on CPU, which is
            how CI exercises the kernel path without an accelerator).
  "numpy"   the pure-jnp oracle in ``ref.py`` (XLA-compiled when called under
            jit — this is the CPU *fast* path, not just a debug path).
  "auto"    "pallas" on TPU, "numpy" elsewhere; metrics without a kernel
            always resolve to "numpy".

The legacy ``use_kernel`` bool is still accepted everywhere and, when given,
overrides ``backend`` (True -> "pallas", False -> "numpy").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import compact as _compact
from repro.kernels import pairdist as _pairdist
from repro.kernels import histogram as _histogram
from repro.kernels import mapassign as _mapassign
from repro.kernels import ref

Array = jnp.ndarray

METRICS = _pairdist.METRICS
BACKENDS = ("numpy", "pallas", "auto")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def supports_kernel(metric: str) -> bool:
    """True when ``metric`` has a Pallas kernel implementation."""
    return metric in METRICS


def resolve_backend(
    backend: str = "auto", metric: str | None = None, use_kernel: bool | None = None
) -> str:
    """Resolve a backend request to a concrete "numpy" | "pallas".

    ``use_kernel`` (legacy bool) wins over ``backend`` when not None. "auto"
    picks the kernel only on TPU; explicitly asking for "pallas" with a metric
    that has no kernel is an error (callers that want graceful fallback go
    through "auto" or check :func:`supports_kernel` first).
    """
    if use_kernel is not None:
        backend = "pallas" if use_kernel else "numpy"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == "auto":
        if metric is not None and not supports_kernel(metric):
            return "numpy"
        return "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend == "pallas" and metric is not None and not supports_kernel(metric):
        raise ValueError(
            f"metric {metric!r} has no Pallas kernel; supported: {METRICS}"
        )
    return backend


def _pad_to(x: Array, mult: int, axis: int) -> Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _feature_block(metric: str, m: int, bm: int | None) -> int:
    """Feature (lane) block of a kernel call: ``bm`` (default 128), or for
    the VPU metrics the whole width padded to the in-kernel chunk when that
    fits one block — Mosaic accepts a last block dim that is a multiple of
    128 or spans the array, and narrow l1/linf inputs then pad 16, not 128."""
    bm = bm or 128
    if metric in _pairdist.MXU_METRICS:
        return bm
    full = -(-m // _pairdist.CHUNK) * _pairdist.CHUNK
    return full if full <= bm else bm


def _prep(x: Array, y: Array, metric: str, bv: int, bw: int, bm: int):
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}; kernels support {METRICS}")
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    if metric == "cosine":
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
        y = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), 1e-12)
    xp = _pad_to(_pad_to(x, bv, 0), bm, 1)
    yp = _pad_to(_pad_to(y, bw, 0), bm, 1)
    return xp, yp


@functools.partial(
    jax.jit, static_argnames=("metric", "bv", "bw", "bm", "backend", "use_kernel")
)
def pairdist(
    x: Array,
    y: Array,
    metric: str = "l2",
    *,
    bv: int = 128,
    bw: int = 128,
    bm: int | None = None,
    backend: str = "auto",
    use_kernel: bool | None = None,
) -> Array:
    """All-pairs distance matrix (a, b) float32."""
    if resolve_backend(backend, metric, use_kernel) == "numpy":
        return ref.pairdist(x, y, metric)
    bm = _feature_block(metric, x.shape[1], bm)
    a, b = x.shape[0], y.shape[0]
    xp, yp = _prep(x, y, metric, bv, bw, bm)
    out = _pairdist.pairdist_blocked(
        xp, yp, metric=metric, delta=None, bv=bv, bw=bw, bm=bm, interpret=_interpret()
    )
    return out[:a, :b]


@functools.partial(
    jax.jit,
    static_argnames=("metric", "delta", "bv", "bw", "bm", "backend", "use_kernel"),
)
def pairdist_mask(
    x: Array,
    y: Array,
    delta: float,
    metric: str = "l2",
    *,
    bv: int = 128,
    bw: int = 128,
    bm: int | None = None,
    backend: str = "auto",
    use_kernel: bool | None = None,
) -> Array:
    """Fused thresholded join mask (a, b) bool — distances never hit HBM."""
    if resolve_backend(backend, metric, use_kernel) == "numpy":
        return ref.pairdist_mask(x, y, delta, metric)
    bm = _feature_block(metric, x.shape[1], bm)
    a, b = x.shape[0], y.shape[0]
    xp, yp = _prep(x, y, metric, bv, bw, bm)
    out = _pairdist.pairdist_blocked(
        xp,
        yp,
        metric=metric,
        delta=float(delta),
        bv=bv,
        bw=bw,
        bm=bm,
        interpret=_interpret(),
    )
    # Padded y-columns of an x row can false-positive (distance to the zero
    # vector may be <= delta); the slice removes them. Padded rows likewise.
    return out[:a, :b].astype(bool)


PRUNABLE_METRICS = ("l1", "l2", "linf")


def supports_prune(metric: str) -> bool:
    """True when the pivot filter is SOUND for ``metric`` on the kernel path.

    The L-inf lower bound over anchor distances needs the triangle inequality
    in the origin metric; "cosine" and "dot" are not true metrics, so pruning
    could drop genuine hits there. (The engine-level capability check in
    ``core.verify`` additionally admits the reference-only true metrics —
    angular, jaccard_minhash — which never reach this kernel.)
    """
    return metric in PRUNABLE_METRICS


@functools.partial(
    jax.jit,
    static_argnames=(
        "metric", "delta", "delta_bound", "bv", "bw", "bm", "backend",
        "use_kernel",
    ),
)
def pairdist_mask_filtered(
    x: Array,
    y: Array,
    px: Array,
    py: Array,
    delta: float,
    metric: str = "l2",
    *,
    delta_bound: float | None = None,
    bv: int = 128,
    bw: int = 128,
    bm: int | None = None,
    backend: str = "auto",
    use_kernel: bool | None = None,
) -> Array:
    """Fused pivot-filter + thresholded join mask (a, b) bool.

    ``px``/``py`` are the mapped coordinates (per-row distances to the shared
    anchors). Identical output to :func:`pairdist_mask` — the filter's L-inf
    lower bound (slackened by ``ref.prune_delta``; pass ``delta_bound`` for
    the scale-aware band) only removes pairs whose distance already exceeds
    ``delta`` — but the Pallas path skips the exact-distance accumulation
    for tiles where every pair is pruned.
    """
    if not supports_prune(metric):
        raise ValueError(
            f"pivot filter is unsound for {metric!r} (needs the triangle "
            f"inequality); prunable kernel metrics: {PRUNABLE_METRICS}"
        )
    if delta_bound is None:
        delta_bound = ref.prune_delta(delta, metric)
    if resolve_backend(backend, metric, use_kernel) == "numpy":
        return ref.pairdist_mask_filtered(x, y, px, py, delta, metric, delta_bound)
    bm = _feature_block(metric, x.shape[1], bm)
    a, b = x.shape[0], y.shape[0]
    xp, yp = _prep(x, y, metric, bv, bw, bm)
    # Pivot coords ride un-normalized (they are distances, not payload);
    # zero row/column padding is exact for the L-inf max.
    pxp = _pad_to(_pad_to(px.astype(jnp.float32), bv, 0), _pairdist.CHUNK, 1)
    pyp = _pad_to(_pad_to(py.astype(jnp.float32), bw, 0), _pairdist.CHUNK, 1)
    out = _pairdist.pairdist_filtered_blocked(
        xp, yp, pxp, pyp, metric=metric, delta=float(delta),
        delta_bound=float(delta_bound), bv=bv, bw=bw, bm=bm,
        interpret=_interpret(),
    )
    # Padded rows/cols can false-positive exactly like pairdist_mask; slice.
    return out[:a, :b].astype(bool)


@functools.partial(
    jax.jit,
    static_argnames=(
        "delta", "metric", "capacity", "cross", "delta_bound",
        "bv", "bw", "bm", "backend", "use_kernel",
    ),
)
def verify_compact(
    x: Array,
    y: Array,
    vids: Array,
    wids: Array,
    wcells: Array,
    cell_id,
    px: Array | None = None,
    py: Array | None = None,
    *,
    delta: float,
    metric: str,
    capacity: int,
    cross: bool = False,
    delta_bound: float | None = None,
    bv: int = 128,
    bw: int = 128,
    bm: int | None = None,
    backend: str = "auto",
    use_kernel: bool | None = None,
) -> tuple[Array, Array, Array]:
    """Fused single-dispatch reduce step: (filter,) distance, threshold,
    validity + min-cell de-dup, and on-device pair compaction.

    ``vids`` / ``wids`` / ``wcells``: (a,) / (b,) int ids with padding = -1;
    ``cell_id`` the verified cell (traced, not static — no recompile per
    cell). With ``px``/``py`` (mapped coordinates) the pivot-filter bound is
    fused in front of the exact distance (prunable metrics only, same rules
    as :func:`pairdist_mask_filtered`).

    Returns ``(pairs, count, n_cand)``: ``pairs`` (capacity, 2) int32 id
    pairs padded with -1, ``count`` int32 the TRUE hit total (``count >
    capacity`` == overflow -> the caller retries at the next capacity
    bucket), ``n_cand`` int32 the pivot-filter survivor count (== valid pair
    count when unfiltered). Pairs come in row-major (``np.nonzero``) order
    on both backends. Semantics oracle: ``ref.verify_compact``.
    """
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    if px is not None:
        if not supports_prune(metric):
            raise ValueError(
                f"pivot filter is unsound for {metric!r} (needs the triangle "
                f"inequality); prunable kernel metrics: {PRUNABLE_METRICS}"
            )
        if delta_bound is None:
            delta_bound = ref.prune_delta(delta, metric)
    if resolve_backend(backend, metric, use_kernel) == "numpy":
        pairs, count, n_cand = ref.verify_compact(
            x, y, vids, wids, wcells, cell_id, delta=delta, metric=metric,
            capacity=capacity, cross=cross, px=px, py=py,
            delta_bound=delta_bound,
        )
        return pairs, count, n_cand
    a, b = x.shape[0], y.shape[0]
    if a == 0 or b == 0:  # empty tile: nothing to grid over
        return (
            jnp.full((capacity, 2), -1, jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )
    bm = _feature_block(metric, x.shape[1], bm)
    xp, yp = _prep(x, y, metric, bv, bw, bm)
    # Row padding carries id/wcell = -1 so padded rows fail the validity
    # mask — they can never be emitted or counted as candidates.
    vp = _pad_const(vids.astype(jnp.int32).reshape(-1, 1), bv, 0, -1)
    wp = _pad_const(wids.astype(jnp.int32).reshape(-1, 1), bw, 0, -1)
    wcp = _pad_const(wcells.astype(jnp.int32).reshape(-1, 1), bw, 0, -1)
    pxp = pyp = None
    if px is not None:
        # Pivot coords ride un-normalized (they are distances, not payload);
        # zero row/column padding is exact for the L-inf max.
        pxp = _pad_to(_pad_to(px.astype(jnp.float32), bv, 0), _pairdist.CHUNK, 1)
        pyp = _pad_to(_pad_to(py.astype(jnp.float32), bw, 0), _pairdist.CHUNK, 1)
    pairs, counts = _compact.verify_compact_blocked(
        xp, yp, vp, wp, wcp, jnp.asarray(cell_id, jnp.int32).reshape(1, 1),
        pxp, pyp, metric=metric, delta=float(delta), capacity=capacity,
        delta_bound=None if delta_bound is None else float(delta_bound),
        cross=cross, bv=bv, bw=bw, bm=bm, interpret=_interpret(),
    )
    return pairs, counts[0, 0], counts[0, 1]


@functools.partial(
    jax.jit, static_argnames=("metric", "delta", "backend", "use_kernel")
)
def pairdist_count(
    x: Array,
    y: Array,
    delta: float,
    metric: str = "l2",
    *,
    backend: str = "auto",
    use_kernel: bool | None = None,
) -> Array:
    """Per-row join fan-out counts (a,) int32."""
    return pairdist_mask(
        x, y, delta, metric, backend=backend, use_kernel=use_kernel
    ).sum(-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("t", "bn", "bmm", "backend", "use_kernel"))
def histogram(
    u: Array,
    t: int,
    weights: Array | None = None,
    *,
    bn: int = 256,
    bmm: int = 128,
    backend: str = "auto",
    use_kernel: bool | None = None,
) -> Array:
    """Per-dimension histogram (m, t) of CDF-space values u: (n, m)."""
    if resolve_backend(backend, use_kernel=use_kernel) == "numpy":
        return ref.histogram(u, t, weights)
    n, m = u.shape
    w = jnp.ones((n, 1), jnp.float32) if weights is None else weights.reshape(n, 1)
    # Ragged n/m are padded (and masked via the weights column) by the
    # blocked kernel itself.
    return _histogram.histogram_blocked(
        u, w.astype(jnp.float32), t=t, bn=bn, bmm=bmm, interpret=_interpret()
    )


# ---------------------------------------------------------------------------
# Fused map phase: space map + kernel assign + packed whole membership
# ---------------------------------------------------------------------------

_ND_MULT = 8  # mapped-coordinate (anchor) axis padded to this multiple
_BIG = _mapassign.BIG


def _pad_const(x: Array, mult: int, axis: int, value: float) -> Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _prep_boxes(
    kernel_lo: Array, kernel_hi: Array, whole_lo: Array, whole_hi: Array, bp: int
):
    """Pad the (p, n) box edges for the blocked kernel.

    Padded DIMENSIONS get (-BIG, +BIG) edges — any finite coordinate
    satisfies them, so they never veto containment. Padded PARTITIONS get
    lo = +BIG — no finite coordinate reaches them, so they never match
    (neither half-open kernel nor closed whole)."""
    def dims(lo, hi):
        return (
            _pad_const(lo.astype(jnp.float32), _ND_MULT, 1, -_BIG),
            _pad_const(hi.astype(jnp.float32), _ND_MULT, 1, _BIG),
        )

    def parts(lo, hi):
        return _pad_const(lo, bp, 0, _BIG), _pad_const(hi, bp, 0, _BIG)

    klo, khi = parts(*dims(kernel_lo, kernel_hi))
    wlo, whi = parts(*dims(whole_lo, whole_hi))
    return klo, khi, wlo, whi


def _bp_eff(p: int, bp: int) -> int:
    """Concrete partition block: a WORD multiple no larger than needed."""
    if bp % _mapassign.WORD != 0:
        raise ValueError(f"bp={bp} must be a multiple of {_mapassign.WORD}")
    p_words = -(-p // _mapassign.WORD) * _mapassign.WORD
    return min(bp, p_words)


WANTS = ("both", "cells", "member")


def _want_flags(want: str) -> tuple[bool, bool]:
    if want not in WANTS:
        raise ValueError(f"unknown want {want!r}; expected one of {WANTS}")
    return want != "member", want != "cells"


@functools.partial(
    jax.jit,
    static_argnames=("metric", "bn", "bp", "bm", "backend", "use_kernel", "want"),
)
def map_assign(
    x: Array,
    anchors: Array,
    kernel_lo: Array,
    kernel_hi: Array,
    whole_lo: Array,
    whole_hi: Array,
    metric: str = "l2",
    *,
    bn: int = 128,
    bp: int = 128,
    bm: int | None = None,
    backend: str = "auto",
    use_kernel: bool | None = None,
    want: str = "both",
) -> tuple[Array, Array, Array]:
    """Fused map phase over one shard: one streamed pass computes the mapped
    coordinates ``xm = D(x, anchors)`` (N, n), the kernel cell id (N,) int32
    and the packed whole-membership bitmask (N, ⌈p/32⌉) uint32 — without the
    (N, p, n) / (N, p) HBM intermediates of the two-pass jnp path (unpack
    the bits with :func:`unpack_membership`). Kernel metrics only (callers
    with reference-only metrics map via ``core.mapping`` and use
    :func:`assign_membership` / the partition fallback).

    ``want``: "both" | "cells" | "member" — skip a containment side the
    caller will recompute anyway (e.g. membership against post-``tighten``
    boxes); the skipped output is zero-filled, never garbage."""
    n_rows = x.shape[0]
    n_dims = anchors.shape[0]
    p = kernel_lo.shape[0]
    words = -(-p // _mapassign.WORD)
    want_cells, want_member = _want_flags(want)
    if resolve_backend(backend, metric, use_kernel) == "numpy":
        xm = ref.pairdist(x, anchors, metric)
        cells, bits = _ref_assign(
            xm, kernel_lo, kernel_hi, whole_lo, whole_hi, want_cells, want_member
        )
        return xm, cells, bits
    if n_rows == 0:  # empty shard: nothing to grid over
        return (
            jnp.zeros((0, n_dims), jnp.float32),
            jnp.zeros((0,), jnp.int32),
            jnp.zeros((0, words), jnp.uint32),
        )
    bm = _feature_block(metric, x.shape[1], bm)
    xp, ap = _prep(x, anchors, metric, bn, _ND_MULT, bm)
    bpe = _bp_eff(p, bp)
    xm, cells, bits = _mapassign.map_assign_blocked(
        xp, ap, *_prep_boxes(kernel_lo, kernel_hi, whole_lo, whole_hi, bpe),
        metric=metric, bn=bn, bp=bpe, bm=bm, interpret=_interpret(),
        want_cells=want_cells, want_member=want_member,
    )
    return xm[:n_rows, :n_dims], cells[:n_rows, 0], bits[:n_rows, :words]


def _ref_assign(xm, kernel_lo, kernel_hi, whole_lo, whole_hi, want_cells, want_member):
    """numpy-backend assign with the same zero-fill contract as the kernel."""
    n_rows = xm.shape[0]
    words = -(-kernel_lo.shape[0] // _mapassign.WORD)
    cells = (
        ref.assign_kernel_cells(xm, kernel_lo, kernel_hi)
        if want_cells
        else jnp.zeros((n_rows,), jnp.int32)
    )
    bits = (
        ref.membership_bits(xm, whole_lo, whole_hi)
        if want_member
        else jnp.zeros((n_rows, words), jnp.uint32)
    )
    return cells, bits


@functools.partial(
    jax.jit, static_argnames=("bn", "bp", "backend", "use_kernel", "want")
)
def assign_membership(
    xm: Array,
    kernel_lo: Array,
    kernel_hi: Array,
    whole_lo: Array,
    whole_hi: Array,
    *,
    bn: int = 128,
    bp: int = 128,
    backend: str = "auto",
    use_kernel: bool | None = None,
    want: str = "both",
) -> tuple[Array, Array]:
    """Assign-only variant of :func:`map_assign`: the coordinates ``xm``
    (N, n) are already mapped (the ``metric=None`` path of the same fused
    kernel — metric-independent, so every backend request is honored).
    Returns (cells (N,) int32, bits (N, ⌈p/32⌉) uint32); ``want`` as in
    :func:`map_assign` (the unwanted output is zero-filled)."""
    n_rows = xm.shape[0]
    p = kernel_lo.shape[0]
    words = -(-p // _mapassign.WORD)
    want_cells, want_member = _want_flags(want)
    if resolve_backend(backend, None, use_kernel) == "numpy":
        return _ref_assign(
            xm, kernel_lo, kernel_hi, whole_lo, whole_hi, want_cells, want_member
        )
    if n_rows == 0:  # empty shard: nothing to grid over
        return jnp.zeros((0,), jnp.int32), jnp.zeros((0, words), jnp.uint32)
    xp = _pad_to(_pad_to(xm.astype(jnp.float32), bn, 0), _ND_MULT, 1)
    bpe = _bp_eff(p, bp)
    # bm = _ND_MULT: the coordinate width is an _ND_MULT multiple (not
    # necessarily a multiple of the metric-default 16), and metric=None
    # never chunks over it anyway.
    _, cells, bits = _mapassign.map_assign_blocked(
        xp, jnp.zeros((xp.shape[1], xp.shape[1]), jnp.float32),
        *_prep_boxes(kernel_lo, kernel_hi, whole_lo, whole_hi, bpe),
        metric=None, bn=bn, bp=bpe, bm=_ND_MULT, interpret=_interpret(),
        want_cells=want_cells, want_member=want_member,
    )
    return cells[:n_rows, 0], bits[:n_rows, :words]


@functools.partial(jax.jit, static_argnames=("p",))
def unpack_membership(bits: Array, p: int) -> Array:
    """(N, ⌈p/32⌉) packed words → (N, p) bool whole-membership mask."""
    return ref.unpack_membership(bits, p)
