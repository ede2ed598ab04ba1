"""Fused single-kernel reduce phase: on-device pair compaction certified by
an overflow/parity property suite.

Three layers, mirroring the dispatch triad:

* kernel contract — ``ref.compact_mask`` / ``ops.verify_compact``: prefix-sum
  compaction equals order-normalized ``np.nonzero``, the overflow sentinel
  reports the exact count, edge tiles (empty, all-pruned, exactly-full,
  single hit at the first/last flat cell) behave.
* engine parity — ``emit="compact"`` is byte-identical to ``emit="mask"``
  across the exact-metric set × backends × tile sizes × prune modes, and the
  verification/hit/prune telemetry is emission-invariant.
* overflow ladder — an undersized capacity prior (monkeypatched knobs) walks
  sentinel -> retry -> mask fallback, emits the identical pair set, and
  increments ``VerifyStats.n_overflow_retries`` (counter-regression style);
  same contract through the distributed executor
  (``DistJoinResult.n_overflow_retries``).
"""
import json
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import mapping, verify
from repro.kernels import ops as kops
from repro.kernels import ref as kref

TILE_SIZES = [(32, 64), (128, 128), (512, 512)]


def _norm(pairs: np.ndarray) -> np.ndarray:
    """Order-normalize a pair buffer (emission order is backend-dependent)."""
    pairs = np.asarray(pairs)
    if pairs.size == 0:
        return pairs.reshape(0, 2)
    return pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]


def _setup(metric, rng, n=240, m=6, p=5):
    """Clustered dataset + a random cell structure with overlap membership."""
    data = np.concatenate(
        [rng.normal(loc=c, scale=1.0, size=(n // 3, m)) for c in (0.0, 3.0, 7.0)]
    ).astype(np.float32)
    n = data.shape[0]
    d = np.asarray(kref.pairdist(jnp.asarray(data), jnp.asarray(data), metric))
    delta = float(np.quantile(d[np.triu_indices(n, 1)], 0.05))
    cells = rng.integers(0, p, n)
    member = np.zeros((n, p), bool)
    member[np.arange(n), cells] = True
    member[np.arange(n), rng.integers(0, p, n)] = True
    return data, cells, member, delta


# ---------------------------------------------------------------------------
# Kernel contract: prefix-sum compaction == order-normalized nonzero
# ---------------------------------------------------------------------------


@given(
    seed=st.integers(0, 2**31 - 1),
    a=st.integers(1, 9),
    b=st.integers(1, 9),
    density=st.floats(0.0, 1.0),
    slack=st.integers(0, 3),
)
@settings(deadline=None)
def test_compaction_matches_nonzero_order_normalized(seed, a, b, density, slack):
    """Property: compacting a random hit mask through the exclusive
    prefix-sum kernel yields exactly the ``np.nonzero`` pair set (order
    normalized), with -1 padding past the true count."""
    r = np.random.default_rng(seed)
    mask = r.random((a, b)) < density
    vids = r.permutation(64)[:a].astype(np.int32)
    wids = (64 + r.permutation(64)[:b]).astype(np.int32)
    count = int(mask.sum())
    capacity = max(count + slack, 1)
    pairs, cnt = kref.compact_mask(
        jnp.asarray(mask), jnp.asarray(vids), jnp.asarray(wids), capacity
    )
    pairs, cnt = np.asarray(pairs), int(cnt)
    assert cnt == count
    vi, wi = np.nonzero(mask)
    want = np.stack([vids[vi], wids[wi]], axis=1)
    assert np.array_equal(_norm(pairs[:count]), _norm(want))
    assert (pairs[count:] == -1).all()


@given(seed=st.integers(0, 2**31 - 1), capacity=st.integers(1, 4))
@settings(deadline=None)
def test_overflow_sentinel_reports_exact_count(seed, capacity):
    """count > capacity is the overflow sentinel: the buffer contents are
    unspecified but the count is exact, so one retry sizes the next bucket."""
    r = np.random.default_rng(seed)
    mask = r.random((6, 6)) < 0.8
    count = int(mask.sum())
    if count <= capacity:
        mask[:, :] = True
        count = mask.size
    ids = np.arange(6, dtype=np.int32)
    _, cnt = kref.compact_mask(
        jnp.asarray(mask), jnp.asarray(ids), jnp.asarray(ids), capacity
    )
    assert int(cnt) == count


@pytest.mark.parametrize("n,peak", [(40_000, 1), (2_000, 1 << 17), (2_000, 1 << 20)])
def test_prefix_sum_matches_cumsum(n, peak):
    """The matmul prefix sum equals ``np.cumsum`` for entries up to ``peak``,
    including where a level's row sums would pass 2^24 in f32."""
    counts = np.random.default_rng(peak).integers(0, peak + 1, n).astype(np.int32)
    got = np.asarray(kref.prefix_sum(jnp.asarray(counts), peak))
    assert np.array_equal(got, np.cumsum(counts, dtype=np.int32))


@pytest.mark.parametrize(
    "a,b,density,capacity",
    [
        (1, 1, 1.0, 1),  # one element
        (37, 5, 0.3, 64),  # narrower than a chunk
        (9, 256, 0.05, 200),  # whole chunks, as a one-chip serve batch's rows
        (6, 300, 0.1, 256),  # a ragged last chunk per row
        (300, 128, 0.02, 1024),  # many chunks, a multi-level prefix sum
        (17, 200, 0.5, 40),  # overflow: more hits than capacity
        (8, 512, 0.0, 16),  # no hit
    ],
)
def test_select_hits_matches_nonzero(a, b, density, capacity):
    """The two-level rank returns the first ``capacity`` hits in
    ``np.nonzero`` order and the exact count, for any width and density."""
    mask = np.random.default_rng(a * b).random((a, b)) < density
    rows, cols, cnt = kref.select_hits(jnp.asarray(mask), capacity)
    rows, cols, cnt = np.asarray(rows), np.asarray(cols), int(cnt)
    vi, wi = np.nonzero(mask)
    assert cnt == vi.size
    n = min(cnt, capacity)
    assert np.array_equal(rows[:n], vi[:n]) and np.array_equal(cols[:n], wi[:n])
    assert rows.shape == cols.shape == (capacity,)
    assert ((0 <= rows) & (rows < a) & (0 <= cols) & (cols < b)).all()


def test_select_hits_empty_mask():
    rows, cols, cnt = kref.select_hits(jnp.zeros((0, 4), bool), 3)
    assert int(cnt) == 0 and np.asarray(rows).shape == np.asarray(cols).shape == (3,)


def test_compaction_edge_tiles():
    """Edge tiles: empty, all-pruned, exactly-full, and a single hit at flat
    index 0 / at the last flat cell landing in buffer slot 0 / capacity-1."""
    ids4 = np.arange(4, dtype=np.int32)
    # Empty tile (either side zero-width): count 0, all padding.
    for shape in [(0, 4), (4, 0)]:
        pairs, cnt = kref.compact_mask(
            jnp.zeros(shape, bool), jnp.asarray(ids4[: shape[0]]),
            jnp.asarray(ids4[: shape[1]]), 3
        )
        assert int(cnt) == 0 and (np.asarray(pairs) == -1).all()
    # All-pruned tile (mask present but all False).
    pairs, cnt = kref.compact_mask(
        jnp.zeros((4, 4), bool), jnp.asarray(ids4), jnp.asarray(ids4), 3
    )
    assert int(cnt) == 0 and (np.asarray(pairs) == -1).all()
    # Exactly-full buffer: capacity == count, no sentinel, no padding.
    mask = np.zeros((4, 4), bool)
    mask[0, 1] = mask[2, 3] = mask[3, 0] = True
    pairs, cnt = kref.compact_mask(
        jnp.asarray(mask), jnp.asarray(ids4), jnp.asarray(ids4), 3
    )
    pairs = np.asarray(pairs)
    assert int(cnt) == 3
    assert np.array_equal(_norm(pairs), _norm(np.array([[0, 1], [2, 3], [3, 0]])))
    # Single pair at flat index 0 -> buffer slot 0.
    mask = np.zeros((4, 4), bool)
    mask[0, 0] = True
    pairs, cnt = kref.compact_mask(
        jnp.asarray(mask), jnp.asarray(ids4), jnp.asarray(ids4), 2
    )
    assert int(cnt) == 1 and tuple(np.asarray(pairs)[0]) == (0, 0)
    # Single pair at the LAST flat cell: the searchsorted inversion must not
    # clamp it away; with capacity 1 it lands in slot capacity-1 == 0.
    mask = np.zeros((4, 4), bool)
    mask[3, 3] = True
    pairs, cnt = kref.compact_mask(
        jnp.asarray(mask), jnp.asarray(ids4), jnp.asarray(ids4), 1
    )
    assert int(cnt) == 1 and tuple(np.asarray(pairs)[0]) == (3, 3)


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_dispatch_triad_verify_compact_matches_mask(backend, rng):
    """ops.verify_compact (both backends) returns the same pair set as the
    mask path on a real tile, including the in-band candidate count."""
    x = rng.normal(size=(17, 5)).astype(np.float32)
    y = rng.normal(size=(23, 5)).astype(np.float32)
    vids = jnp.arange(17)
    wids = jnp.arange(100, 123)
    wcells = jnp.zeros((23,), jnp.int32)
    pairs, count, n_cand = kops.verify_compact(
        jnp.asarray(x), jnp.asarray(y), vids, wids, wcells, 0,
        delta=2.0, metric="l2", capacity=512, cross=True, backend=backend,
    )
    mask = np.asarray(
        kref.pairdist_mask(jnp.asarray(x), jnp.asarray(y), 2.0, "l2")
    )
    vi, wi = np.nonzero(mask)
    want = np.stack([vi, 100 + wi], axis=1)
    assert int(count) == vi.size
    assert int(n_cand) == 17 * 23
    assert np.array_equal(_norm(np.asarray(pairs)[: int(count)]), _norm(want))


# ---------------------------------------------------------------------------
# Engine parity: emit="compact" == emit="mask", metrics x backends x tiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tile_v,tile_w", TILE_SIZES)
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("metric", list(kref.METRICS))
def test_engine_compact_mask_identity(metric, backend, tile_v, tile_w, rng):
    """Fixed-seed pair sets are byte-identical between the emission paths on
    every exact metric, backend, and tile size; the verification and hit
    telemetry is emission-invariant."""
    data, cells, member, delta = _setup(metric, rng)
    base = base_stats = None
    for emit in ["mask", "compact"]:
        cfg = verify.EngineConfig(
            backend=backend, tile_v=tile_v, tile_w=tile_w, emit=emit
        )
        pairs, stats = verify.verify_pairs(
            data, cells, member, delta, metric, config=cfg
        )
        assert stats.emit == emit  # exact metrics: no capability fallback
        if base is None:
            base, base_stats = pairs, stats
        else:
            assert pairs.tobytes() == base.tobytes(), (metric, backend, tile_v)
            assert stats.n_hits == base_stats.n_hits
            assert stats.n_verifications == base_stats.n_verifications


@pytest.mark.parametrize("prune", ["pivot", "window"])
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_engine_compact_mask_identity_pruned(prune, backend, rng):
    """Same identity with the pivot filter / host-side windows engaged: the
    pruned compacted pair set matches the unpruned mask run byte for byte."""
    metric = "l1"
    data, cells, member, delta = _setup(metric, rng)
    anchors = data[rng.choice(data.shape[0], 4, replace=False)]
    coords = np.asarray(mapping.SpaceMap(anchors, metric)(data), np.float32)
    ref_cfg = verify.EngineConfig(backend=backend, tile_v=64, tile_w=128)
    base, base_stats = verify.verify_pairs(
        data, cells, member, delta, metric, config=ref_cfg
    )
    for emit in ["mask", "compact"]:
        cfg = verify.EngineConfig(
            backend=backend, tile_v=64, tile_w=128, prune=prune, emit=emit
        )
        pairs, stats = verify.verify_pairs(
            data, cells, member, delta, metric, config=cfg, coords=coords
        )
        assert pairs.tobytes() == base.tobytes(), (prune, backend, emit)
        assert stats.n_hits == base_stats.n_hits
        assert stats.n_verifications == base_stats.n_verifications
        assert stats.n_exact + stats.n_pruned == stats.n_verifications


def test_engine_compact_empty_and_degenerate_cells(rng):
    """Compact emission through degenerate cell structures: empty V or W
    lists, singleton cells, and a cell whose window prunes everything."""
    data = rng.normal(size=(40, 4)).astype(np.float32)
    anchors = data[:3]
    coords = np.asarray(mapping.SpaceMap(anchors, "l2")(data), np.float32)
    cells = np.zeros((40,), np.int64)
    v_lists = [np.arange(20), np.array([], np.int64), np.array([39])]
    w_lists = [np.arange(20, 40), np.arange(5), np.array([], np.int64)]
    for prune in ["none", "window"]:
        base = None
        for emit in ["mask", "compact"]:
            cfg = verify.EngineConfig(backend="numpy", tile_v=8, tile_w=8,
                                      prune=prune, emit=emit)
            pairs, stats = verify.verify_cell_lists(
                data, cells, v_lists, w_lists, 0.9, "l2", config=cfg,
                coords=coords if prune != "none" else None,
            )
            if base is None:
                base = pairs
            else:
                assert pairs.tobytes() == base.tobytes()


# ---------------------------------------------------------------------------
# Overflow ladder: sentinel -> retry -> fallback, counted
# ---------------------------------------------------------------------------


def _force_undercapacity(monkeypatch):
    """Shrink the capacity prior so the first bucket always overflows."""
    monkeypatch.setattr(verify, "DEFAULT_EMIT_RATE", 1e-9)
    monkeypatch.setattr(verify, "EMIT_SLACK", 1e-9)
    monkeypatch.setattr(verify, "_EMIT_FLOOR", 1)
    monkeypatch.setattr(verify, "_estimate_emit_rate", lambda *a, **k: 1e-9)


@pytest.mark.parametrize(
    "backend,prune", [("numpy", "pivot"), ("pallas", "none")]
)
def test_overflow_retry_ladder_engine(backend, prune, rng, monkeypatch):
    """Counter-regression: an undersized prior forces the sentinel->retry
    ladder on every buffered tile; the emitted pairs stay identical and
    n_overflow_retries records the walk. (Buffered tiles only: the jnp
    window/none path lowers compact emission to the mask dispatch and can
    never overflow.)"""
    metric = "l1"
    data, cells, member, delta = _setup(metric, rng, n=90)
    coords = None
    if prune == "pivot":
        anchors = data[rng.choice(data.shape[0], 4, replace=False)]
        coords = np.asarray(mapping.SpaceMap(anchors, metric)(data), np.float32)
    cfg_m = verify.EngineConfig(backend=backend, tile_v=32, tile_w=32,
                                prune=prune, emit="mask")
    base, _ = verify.verify_pairs(
        data, cells, member, delta, metric, config=cfg_m, coords=coords
    )
    _force_undercapacity(monkeypatch)
    cfg_c = verify.EngineConfig(backend=backend, tile_v=32, tile_w=32,
                                prune=prune, emit="compact")
    pairs, stats = verify.verify_pairs(
        data, cells, member, delta, metric, config=cfg_c, coords=coords
    )
    assert pairs.tobytes() == base.tobytes()
    assert stats.n_overflow_retries >= 1


def test_overflow_fallback_to_mask_is_identical(rng, monkeypatch):
    """Exhausting the bounded retries lands on the mask-path rung: still the
    identical pair set, retries still counted."""
    metric = "l2"
    data, cells, member, delta = _setup(metric, rng, n=90)
    anchors = data[rng.choice(data.shape[0], 4, replace=False)]
    coords = np.asarray(mapping.SpaceMap(anchors, metric)(data), np.float32)
    cfg_m = verify.EngineConfig(backend="numpy", tile_v=32, tile_w=32,
                                prune="pivot", emit="mask")
    base, _ = verify.verify_pairs(
        data, cells, member, delta, metric, config=cfg_m, coords=coords
    )
    _force_undercapacity(monkeypatch)
    monkeypatch.setattr(verify, "_MAX_OVERFLOW_RETRIES", 0)
    cfg_c = verify.EngineConfig(backend="numpy", tile_v=32, tile_w=32,
                                prune="pivot", emit="compact")
    pairs, stats = verify.verify_pairs(
        data, cells, member, delta, metric, config=cfg_c, coords=coords
    )
    assert pairs.tobytes() == base.tobytes()
    assert stats.n_overflow_retries >= 1


def test_overflow_retry_grows_capacity_monotonically(rng, monkeypatch):
    """The retry ladder sizes the next bucket from the sentinel's exact
    count: one retry should suffice (no second overflow on the same tile)."""
    metric = "l1"
    data, cells, member, delta = _setup(metric, rng, n=90)
    anchors = data[rng.choice(data.shape[0], 4, replace=False)]
    coords = np.asarray(mapping.SpaceMap(anchors, metric)(data), np.float32)
    _force_undercapacity(monkeypatch)
    calls = []
    orig = verify.bucket_size

    def spy(n, cap, floor=8):
        out = orig(n, cap, floor)
        calls.append((n, out))
        return out

    monkeypatch.setattr(verify, "bucket_size", spy)
    cfg = verify.EngineConfig(backend="numpy", tile_v=32, tile_w=32,
                              prune="pivot", emit="compact")
    _, stats = verify.verify_pairs(
        data, cells, member, delta, metric, config=cfg, coords=coords
    )
    # Every dispatched tile overflowed exactly once: retries == tiles that
    # had any hit, never more than one walk per tile.
    assert 1 <= stats.n_overflow_retries <= stats.n_tiles


# ---------------------------------------------------------------------------
# Distributed executor: compacted pairs ride the existing exchange
# ---------------------------------------------------------------------------


def test_distributed_compact_identity_single_device():
    """1-device mesh in-process: distributed emit="compact" returns the same
    pairs as emit="mask", self-join and RxS, and the overflow counter rides
    the result."""
    import jax
    from jax.sharding import Mesh

    from repro.core import distributed as D

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rng = np.random.default_rng(1)
    data = rng.normal(size=(240, 6)).astype(np.float32)
    s = rng.normal(size=(130, 6)).astype(np.float32)
    for cross in [False, True]:
        kw = dict(mesh=mesh, delta=4.0, metric="l1", k=96, n_dims=4,
                  emit_pairs=True, backend="numpy", seed=3,
                  s=s if cross else None)
        r_mask = D.distributed_join(np.asarray(data), emit="mask", **kw)
        r_comp = D.distributed_join(np.asarray(data), emit="compact", **kw)
        assert r_comp.emit == "compact"
        assert r_comp.pairs.tobytes() == r_mask.pairs.tobytes()
        assert r_comp.n_hits == r_mask.n_hits
        assert r_comp.n_overflow_retries == 0


def test_distributed_overflow_retry_counter(monkeypatch):
    """Forced undercapacity through the distributed stage: identical pairs,
    DistJoinResult.n_overflow_retries >= 1 (counter-regression)."""
    import jax
    from jax.sharding import Mesh

    from repro.core import distributed as D

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rng = np.random.default_rng(1)
    data = rng.normal(size=(240, 6)).astype(np.float32)
    kw = dict(mesh=mesh, delta=4.0, metric="l1", k=96, n_dims=4,
              emit_pairs=True, backend="numpy", seed=3)
    r_ref = D.distributed_join(np.asarray(data), emit="mask", **kw)
    _force_undercapacity(monkeypatch)
    r_of = D.distributed_join(np.asarray(data), emit="compact", **kw)
    assert r_of.pairs.tobytes() == r_ref.pairs.tobytes()
    assert r_of.n_overflow_retries >= 1


@pytest.mark.slow
def test_distributed_compact_identity_8dev():
    """8 simulated devices (subprocess, test_distributed harness): compact
    emission through the real shard_map exchange is byte-identical to mask
    emission, including under a forced-overflow prior."""
    prog = (
        "import os\n"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'\n"
        + textwrap.dedent("""
        import json, numpy as np, jax, jax.numpy as jnp
        mesh = jax.make_mesh((8,), ("data",))
        from repro.core import distributed, verify
        rng = np.random.default_rng(0)
        data = np.concatenate([
            rng.normal(loc=c, scale=1.0, size=(200, 8)) for c in (0., 4., 9., 14.)
        ]).astype(np.float32)
        kw = dict(mesh=mesh, delta=6.0, metric="l1", k=128, p=16, n_dims=4,
                  emit_pairs=True, seed=0)
        r_mask = distributed.distributed_join(jnp.asarray(data), emit="mask", **kw)
        r_comp = distributed.distributed_join(jnp.asarray(data), emit="compact", **kw)
        verify.DEFAULT_EMIT_RATE = 1e-9
        verify.EMIT_SLACK = 1e-9
        verify._EMIT_FLOOR = 1
        r_of = distributed.distributed_join(jnp.asarray(data), emit="compact", **kw)
        print(json.dumps(dict(
            identical=bool(r_mask.pairs.tobytes() == r_comp.pairs.tobytes()),
            of_identical=bool(r_mask.pairs.tobytes() == r_of.pairs.tobytes()),
            emit=r_comp.emit,
            n_pairs=int(r_comp.pairs.shape[0]),
            hits_match=bool(r_comp.n_hits == r_mask.n_hits),
            of_retries=int(r_of.n_overflow_retries),
        )))
        """)
    )
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["identical"] and res["of_identical"]
    assert res["emit"] == "compact" and res["hits_match"]
    assert res["n_pairs"] > 0
    assert res["of_retries"] >= 1
