"""The persistent metric index: build-once / query-forever semantics.

Covers the ISSUE-6 contract: query parity with the brute-force oracle on
every exact metric (including δ ≠ build-δ), save/load byte-identity of
pivots/coords/plan, loud failures on foreign or mismatched artifacts, the
no-rebuild-on-query regression (module-attribute call counters), and the
distributed serving path (1 device inline; 8 simulated devices under the
``slow`` marker, subprocess-isolated like tests/test_distributed.py)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import index as index_lib
from repro.core import mapping, partition, spjoin

EXACT_METRICS = ["l1", "l2", "linf", "angular", "jaccard_minhash"]
DELTAS = {"l1": 2.0, "l2": 1.0, "linf": 0.6, "angular": 0.15,
          "jaccard_minhash": 0.4}


def _dataset(rng, metric, n=260, n_q=70):
    if metric == "jaccard_minhash":
        r = rng.integers(0, 30, size=(n, 16)).astype(np.float32)
        # random signatures almost never collide — queries are perturbed
        # copies of indexed rows (3/16 coords flipped -> distance 0.1875)
        q = r[:n_q].copy()
        q[:, :3] = rng.integers(30, 60, size=(n_q, 3))
    else:
        r = rng.normal(size=(n, 5)).astype(np.float32)
        q = rng.normal(size=(n_q, 5)).astype(np.float32)
    return r, q


def _build(r, metric, delta, **kw):
    cfg = spjoin.JoinConfig(delta=delta, metric=metric, k=64, p=8, n_dims=3,
                            **kw)
    return index_lib.build_index(r, cfg)


# ---------------------------------------------------------------------------
# Query parity vs the brute-force oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", EXACT_METRICS)
def test_query_batch_parity_all_exact_metrics(metric, rng):
    r, q = _dataset(rng, metric)
    delta = DELTAS[metric]
    idx = _build(r, metric, delta)
    truth = index_lib.brute_force_query(r, q, delta, metric)
    assert truth.shape[0] > 0, "degenerate dataset: oracle found nothing"
    pairs = idx.query_batch(q)
    assert pairs.tobytes() == truth.tobytes()


def test_query_delta_differs_from_build_delta(rng):
    """The stored boxes are pre-expansion: any query radius answers exactly,
    below or above the build default."""
    r, q = _dataset(rng, "l2")
    idx = _build(r, "l2", 1.0)
    for delta in (0.4, 1.0, 1.7):
        truth = index_lib.brute_force_query(r, q, delta, "l2")
        np.testing.assert_array_equal(idx.query_batch(q, delta), truth)


def test_single_query_and_stats(rng):
    r, q = _dataset(rng, "l1")
    idx = _build(r, "l1", 2.0)
    truth = index_lib.brute_force_query(r, q[:1], 2.0, "l1")
    np.testing.assert_array_equal(idx.query(q[0]), np.sort(truth[:, 0]))
    with pytest.raises(ValueError):
        idx.query(q)  # a batch is not a point
    pairs, stats = idx.query_batch(q, with_stats=True)
    assert stats.n_queries == q.shape[0]
    assert stats.n_routed >= stats.n_queries  # every in-box query owns >=1 cell
    assert 0 < stats.n_cells_touched <= idx.p
    assert stats.duplication == stats.n_routed / stats.n_queries


def test_empty_results_and_out_of_box_queries(rng):
    r, _ = _dataset(rng, "l2")
    idx = _build(r, "l2", 0.5)
    far = np.full((6, 5), 500.0, np.float32)  # outside every δ-expanded box
    assert idx.query_batch(far).shape == (0, 2)
    assert idx.query(far[0]).shape == (0,)
    assert idx.query_batch(np.zeros((0, 5), np.float32)).shape == (0, 2)
    _, stats = idx.query_batch(far, with_stats=True)
    assert stats.n_routed == 0 and stats.n_cells_touched == 0


def test_query_batch_fused_on_off_byte_identical(rng):
    r, q = _dataset(rng, "l2")
    on = _build(r, "l2", 1.0, map_fused=True)
    off = _build(r, "l2", 1.0, map_fused=False)
    assert on.query_batch(q).tobytes() == off.query_batch(q).tobytes()


# ---------------------------------------------------------------------------
# Save / load
# ---------------------------------------------------------------------------


def test_save_load_roundtrip_byte_identity(rng, tmp_path):
    r, q = _dataset(rng, "l2")
    idx = _build(r, "l2", 1.0)
    path = idx.save(str(tmp_path / "idx"))
    idx2 = index_lib.MetricIndex.load(path)
    for name in index_lib._ARRAYS:
        assert getattr(idx, name).tobytes() == getattr(idx2, name).tobytes(), name
    for name in index_lib._PLAN_ARRAYS:
        a = np.asarray(getattr(idx.placement, name))
        b = np.asarray(getattr(idx2.placement, name))
        assert a.tobytes() == b.tobytes(), name
    assert idx2.metric == idx.metric and idx2.delta == idx.delta
    assert idx.query_batch(q).tobytes() == idx2.query_batch(q).tobytes()


def test_load_accepts_matching_expectations(rng, tmp_path):
    r, _ = _dataset(rng, "l1")
    path = _build(r, "l1", 2.0).save(str(tmp_path / "idx"))
    idx = index_lib.MetricIndex.load(path, metric="l1", delta=2.0, k=64)
    assert idx.metric == "l1"


def test_load_rejects_mismatched_config(rng, tmp_path):
    r, _ = _dataset(rng, "l1")
    path = _build(r, "l1", 2.0).save(str(tmp_path / "idx"))
    with pytest.raises(index_lib.IndexMismatchError, match="metric"):
        index_lib.MetricIndex.load(path, metric="l2")
    with pytest.raises(index_lib.IndexMismatchError, match="delta"):
        index_lib.MetricIndex.load(path, delta=9.0)
    with pytest.raises(index_lib.IndexMismatchError, match="pivots"):
        index_lib.MetricIndex.load(path, k=999)


def test_load_rejects_foreign_or_corrupt_artifacts(rng, tmp_path):
    with pytest.raises(index_lib.IndexFormatError, match="manifest"):
        index_lib.MetricIndex.load(str(tmp_path / "nowhere"))

    r, _ = _dataset(rng, "l1")
    path = _build(r, "l1", 2.0).save(str(tmp_path / "idx"))
    mpath = os.path.join(path, "manifest.json")
    man = json.load(open(mpath))

    json.dump({**man, "format": "something-else"}, open(mpath, "w"))
    with pytest.raises(index_lib.IndexFormatError, match="format"):
        index_lib.MetricIndex.load(path)

    json.dump({**man, "version": index_lib.FORMAT_VERSION + 1}, open(mpath, "w"))
    with pytest.raises(index_lib.IndexFormatError, match="version"):
        index_lib.MetricIndex.load(path)

    # manifest-vs-npz shape disagreement (artifact mixed between saves)
    man2 = dict(man)
    man2["arrays"] = {**man["arrays"], "pivots": [1, 1]}
    json.dump(man2, open(mpath, "w"))
    with pytest.raises(index_lib.IndexFormatError, match="corrupt|shape"):
        index_lib.MetricIndex.load(path)


def test_manifest_carries_incremental_counters(rng, tmp_path):
    r, _ = _dataset(rng, "l2")
    idx = _build(r, "l2", 1.0)
    idx.insert_batch(rng.normal(size=(40, 5)).astype(np.float32))
    man = idx.manifest()
    inc = man["incremental"]
    assert inc["n_base"] == 260 and inc["n_inserted"] == 40
    assert inc["n_base"] + inc["n_inserted"] == man["n_rows"]
    assert inc["n_batches"] == 1


def test_save_insert_load_insert_byte_identity(rng, tmp_path):
    """The ISSUE-8 round trip: save mid-stream, load, keep inserting — the
    loaded index's continuation is byte-identical to the uninterrupted one
    (arrays, observed_w drift state, emitted pairs, final answers)."""
    r = rng.normal(size=(200, 5)).astype(np.float32)
    d1 = rng.normal(size=(50, 5)).astype(np.float32)
    d2 = rng.normal(size=(30, 5)).astype(np.float32)
    live = _build(r, "l2", 1.0)
    p1_live, _ = live.insert_batch(d1)
    path = live.save(str(tmp_path / "stream"))

    loaded = index_lib.MetricIndex.load(path)
    assert (loaded.n_base, loaded.n_inserted, loaded.n_batches) == (200, 50, 1)
    for name in index_lib._ARRAYS:  # observed_w included since format v2
        assert getattr(live, name).tobytes() == getattr(loaded, name).tobytes(), name

    p2_live, s_live = live.insert_batch(d2)
    p2_loaded, s_loaded = loaded.insert_batch(d2)
    assert p2_live.tobytes() == p2_loaded.tobytes()
    assert np.isclose(s_live.drift, s_loaded.drift)
    assert s_live.action == s_loaded.action
    full = np.concatenate([r, d1, d2])
    q = rng.normal(size=(40, 5)).astype(np.float32)
    truth = index_lib.brute_force_query(full, q, 1.0, "l2")
    assert loaded.query_batch(q).tobytes() == truth.tobytes()


def test_load_rejects_manifest_without_incremental_block(rng, tmp_path):
    r, _ = _dataset(rng, "l1")
    path = _build(r, "l1", 2.0).save(str(tmp_path / "idx"))
    mpath = os.path.join(path, "manifest.json")
    man = json.load(open(mpath))
    man.pop("incremental")
    json.dump(man, open(mpath, "w"))
    with pytest.raises(index_lib.IndexFormatError, match="incremental"):
        index_lib.MetricIndex.load(path)


def test_load_rejects_inconsistent_stream_counters(rng, tmp_path):
    r, _ = _dataset(rng, "l1")
    path = _build(r, "l1", 2.0).save(str(tmp_path / "idx"))
    mpath = os.path.join(path, "manifest.json")
    man = json.load(open(mpath))
    man["incremental"]["n_inserted"] = 7  # n_base + n_inserted != n_rows
    json.dump(man, open(mpath, "w"))
    with pytest.raises(index_lib.IndexMismatchError, match="stream"):
        index_lib.MetricIndex.load(path)


# ---------------------------------------------------------------------------
# Regression: queries never re-enter the build control plane
# ---------------------------------------------------------------------------


def test_second_query_performs_no_sampling_or_partitioning(rng, monkeypatch):
    counts = {"fit": 0, "draw": 0, "anchors": 0, "partition": 0}
    wrap = lambda key, fn: (lambda *a, **k: (counts.__setitem__(key, counts[key] + 1), fn(*a, **k))[1])
    monkeypatch.setattr(spjoin, "fit_node_stats", wrap("fit", spjoin.fit_node_stats))
    monkeypatch.setattr(spjoin, "draw_pivots", wrap("draw", spjoin.draw_pivots))
    monkeypatch.setattr(mapping, "select_anchors", wrap("anchors", mapping.select_anchors))
    monkeypatch.setattr(partition, "build_partition", wrap("partition", partition.build_partition))

    r, q = _dataset(rng, "l2")
    idx = _build(r, "l2", 1.0)
    after_build = dict(counts)
    assert all(v == 1 for v in after_build.values()), after_build

    idx.query_batch(q)
    idx.query_batch(q, delta=0.5)  # different radius: still no rebuild
    idx.query(q[0])
    assert counts == after_build, f"query phase re-entered the build: {counts}"


# ---------------------------------------------------------------------------
# Distributed serving
# ---------------------------------------------------------------------------


def test_dist_index_parity_1dev(rng):
    r, q = _dataset(rng, "l2", n=300, n_q=90)
    idx = _build(r, "l2", 1.0)
    mesh = jax.make_mesh((1,), ("data",))
    didx = idx.to_distributed(mesh)
    truth = index_lib.brute_force_query(r, q, 1.0, "l2")
    assert didx.query_batch(q).tobytes() == truth.tobytes()
    # δ override flows through the distributed stage cache too
    truth_wide = index_lib.brute_force_query(r, q, 1.6, "l2")
    np.testing.assert_array_equal(didx.query_batch(q, 1.6), truth_wide)


@pytest.mark.parametrize("case", ["overflow", "no_hits", "repeat"])
def test_dist_index_compacted_pairs(case, rng, monkeypatch):
    """The serve stage hands back compacted pairs, byte-identical to the host
    index and the brute-force oracle: when the first run overflows the pair
    capacity (one rerun at a grown capacity), with no hit at all, and on a
    repeated batch that reuses the compiled stage."""
    from repro.core import distances, tracing

    r, q = _dataset(rng, "l2", n=300, n_q=90)
    if case == "no_hits":
        q = q + 100.0  # far from every indexed row
    idx = _build(r, "l2", 1.0)
    didx = idx.to_distributed(jax.make_mesh((1,), ("data",)))
    if case == "overflow":
        didx._pair_cap = 1
    if case == "repeat":
        didx.query_batch(q)
    cap_before = didx._pair_cap

    made: list = []

    class Recorded(tracing.span):  # keeps its counters, traced or not
        def __init__(self, name, **counts):
            super().__init__(name, **counts)
            self.name, self.counts = name, dict(counts)
            made.append(self)

        def add(self, **counts):
            super().add(**counts)
            self.counts.update(counts)

    monkeypatch.setattr(tracing, "span", Recorded)
    got = didx.query_batch(q)
    oracle = np.argwhere(np.asarray(distances.brute_force_join(r, q, 1.0, "l2")))
    assert got.dtype == np.int64
    assert got.tobytes() == idx.query_batch(q).tobytes() == oracle.astype(np.int64).tobytes()

    stages = [s.counts for s in made if s.name == "serve.stage"]
    readbacks = [s.counts for s in made if s.name == "serve.readback"]
    (unpack,) = [s.counts for s in made if s.name == "serve.unpack"]
    assert unpack["n_pairs"] == got.shape[0] and unpack["n_hits"] >= got.shape[0]
    assert readbacks[-1]["pair_cap"] >= unpack["n_hits"]
    if case == "overflow":
        assert got.shape[0] > 1
        assert sum(s["pair_retries"] for s in stages) >= 1 and len(stages) == 2
        assert didx._pair_cap > cap_before and readbacks[-1]["pair_cap"] > 1
    else:
        assert [s["pair_retries"] for s in stages] == [0]
        assert didx._pair_cap == cap_before
    if case == "no_hits":
        assert got.shape == (0, 2) and unpack["n_hits"] == 0
    if case == "repeat":
        assert stages[0]["compiled"] == 0


def test_dist_index_rejects_kernel_less_metrics(rng):
    r, _ = _dataset(rng, "angular")
    idx = _build(r, "angular", 0.15)
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="metric"):
        idx.to_distributed(mesh)


@pytest.mark.slow
def test_dist_index_parity_8dev_subprocess():
    """Serve on an 8-device mesh an index whose stored plan targets 4
    devices — exercises the cheap re-plan path. Subprocess-isolated so the
    device-count flag never leaks (tests/test_distributed.py pattern)."""
    prog = "import os\nos.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'\n" + textwrap.dedent("""
    import json, numpy as np, jax
    from repro.core import index as index_lib, spjoin
    rng = np.random.default_rng(0)
    r = rng.normal(size=(800, 6)).astype(np.float32)
    q = rng.normal(size=(160, 6)).astype(np.float32)
    cfg = spjoin.JoinConfig(delta=1.0, metric="l2", k=128, p=16, n_dims=4)
    idx = index_lib.build_index(r, cfg, n_devices=4)
    mesh = jax.make_mesh((8,), ("data",))
    didx = idx.to_distributed(mesh)
    truth = index_lib.brute_force_query(r, q, 1.0, "l2")
    got = didx.query_batch(q)
    print(json.dumps({
        "exact": bool(np.array_equal(got, truth)),
        "host_exact": bool(np.array_equal(idx.query_batch(q), truth)),
        "n_pairs": int(truth.shape[0]),
    }))
    """)
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["exact"] and res["host_exact"]
    assert res["n_pairs"] > 0
