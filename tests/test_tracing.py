"""Program spans (``repro.core.tracing``): the phase timers read them, and a
profiler trace holds them nested as the program opened them, with their
counters, on the host thread's line."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import distributed, spjoin, tracing
from repro.core import index as index_lib

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import dist_spans, program_spans  # noqa: E402

JOIN_CHILDREN = ["spjoin.sample", "spjoin.map", "spjoin.reduce", "spjoin.report"]
SERVE_CHILDREN = ["serve.put", "serve.route", "serve.stage", "serve.readback", "serve.unpack"]
DIST_CHILDREN = ["dist.put", "dist.sample", "dist.plan", "dist.stage", "dist.readback", "dist.merge"]


def _bits(rng, n, m=16):
    return (rng.random((n, m)) > 0.5).astype(np.float32)


def _join_cfg(emit="mask"):
    return spjoin.JoinConfig(delta=float(np.sqrt(4.5)), metric="l2", p=8, k=128,
                             tile_v=64, tile_w=128, prune="pivot", emit=emit)


def _traced(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    return out, program_spans.read_dir(tmp_path)


def _names(spans, parent):
    return [s.name for s in spans.spans if s.parent == parent]


def test_span_times_itself_and_roots_count_requests():
    with tracing.span("test.outer", rows=3) as outer:
        with tracing.span("test.inner") as inner:
            inner.add(n_hits=1)  # untraced: no counters, no error
    assert outer.seconds >= inner.seconds > 0.0
    with tracing.root("test.a") as a, tracing.root("test.b") as b:
        pass
    assert a.seconds >= b.seconds >= 0.0


@pytest.mark.parametrize("emit", ["mask", "compact"])
def test_join_span_tree_and_counters(rng, tmp_path, emit):
    x = _bits(rng, 600)
    res, spans = _traced(tmp_path, lambda: spjoin.join(x, _join_cfg(emit)))
    vs = res.verify_stats
    assert vs.emit == emit and vs.n_tiles > 0
    roots = [i for i, s in enumerate(spans.spans) if s.parent == -1]
    assert [spans.spans[i].name for i in roots] == ["spjoin.join"]
    root = spans.spans[roots[0]]
    assert set(root.counts) == {"request", "rows"} and root.counts["rows"] == 600
    assert _names(spans, roots[0]) == JOIN_CHILDREN
    reduce_i = next(i for i, s in enumerate(spans.spans) if s.name == "spjoin.reduce")
    assert set(_names(spans, reduce_i)) == {"verify.cell", "verify.finalize"}
    by_parent = {"verify.cell": "spjoin.reduce", "verify.w_tiles": "verify.cell",
                 "verify.tile": "verify.cell", "verify.prepass": "verify.tile",
                 "verify.dispatch": "verify.tile", "verify.readback": "verify.tile",
                 "verify.emit": "verify.tile"}
    for s in spans.spans:
        if s.name in by_parent:
            assert spans.spans[s.parent].name == by_parent[s.name], s.name
    cells = spans.named("verify.cell")
    assert len(cells) == vs.n_cells and all({"v", "w"} <= set(c.counts) for c in cells)
    tiles = [i for i, s in enumerate(spans.spans) if s.name == "verify.tile"]
    for i in tiles:
        assert {"cap_v", "cap_w", "n_valid", "n_cand", "n_hits", "retries"} <= set(
            spans.spans[i].counts)
    assert sum(spans.spans[i].counts["n_hits"] for i in tiles) == vs.n_hits
    dispatched = [i for i in tiles if "verify.dispatch" in _names(spans, i)]
    assert len(dispatched) == vs.n_tiles
    assert bool(spans.named("verify.prepass")) == (emit == "mask")
    # the survivors a tile reports are what the engine counted as not pruned
    # (the window and the bounding-box skips prune outside any tile span)
    n_cand = sum(spans.spans[i].counts["n_cand"] for i in tiles)
    n_valid = sum(spans.spans[i].counts["n_valid"] for i in tiles)
    assert 0 < n_cand <= n_valid


def test_phase_times_are_their_spans_seconds(rng, monkeypatch):
    made: dict[str, list] = {}

    class Recorded(tracing.span):
        def __init__(self, name, **counts):
            super().__init__(name, **counts)
            made.setdefault(name, []).append(self)

    monkeypatch.setattr(tracing, "span", Recorded)
    x = _bits(rng, 300)
    res = spjoin.join(x, _join_cfg())
    (sample,), (map_,) = made["spjoin.sample"], made["spjoin.map"]
    (reduce_,) = made["spjoin.reduce"]
    assert res.sample_time_s == sample.seconds > 0
    assert res.map_time_s == map_.seconds > 0
    assert res.verify_time_s == reduce_.seconds > 0

    idx = index_lib.build_index(x[:200], _join_cfg())
    assert idx.build_s == made["index.build"][-1].seconds > 0
    _, qs = idx.query_batch(x[200:], with_stats=True)
    assert qs.route_s == made["index.route"][-1].seconds
    assert qs.verify_s == made["index.verify"][-1].seconds
    _, st = idx.insert_batch(x[200:260])
    assert st.route_s == made["index.route"][-1].seconds
    assert st.verify_s == made["index.verify"][-1].seconds
    assert st.update_s == made["index.update"][-1].seconds > 0


def test_serve_batch_span_tree_and_counters(rng, tmp_path):
    r = rng.normal(size=(300, 5)).astype(np.float32)
    q = rng.normal(size=(90, 5)).astype(np.float32)
    cfg = spjoin.JoinConfig(delta=1.0, metric="l2", k=64, p=8, n_dims=3)
    didx = index_lib.build_index(r, cfg).to_distributed(jax.make_mesh((1,), ("data",)))
    (first, second), spans = _traced(tmp_path, lambda: (didx.query_batch(q), didx.query_batch(q)))
    roots = [i for i, s in enumerate(spans.spans) if s.parent == -1]
    assert [spans.spans[i].name for i in roots] == ["serve.query_batch"] * 2
    requests = [spans.spans[i].counts["request"] for i in roots]
    assert len(set(requests)) == 2
    for i, pairs, compiled in zip(roots, (first, second), (1, 0)):
        assert spans.spans[i].counts["n_queries"] == 90
        kids = {s.name: s for s in spans.spans if s.parent == i}
        assert [s.name for s in spans.spans if s.parent == i] == SERVE_CHILDREN
        assert kids["serve.route"].counts["cap_w"] >= 2
        assert kids["serve.route"].counts["n_routed"] >= 90
        assert kids["serve.stage"].counts["compiled"] == compiled
        assert kids["serve.stage"].counts["pair_retries"] == 0
        elems = kids["serve.readback"].counts["mask_elems"]
        assert elems == didx.pl.n_slots * didx.cap_v * kids["serve.route"].counts["cap_w"]
        assert kids["serve.unpack"].counts["n_pairs"] == pairs.shape[0]
        assert kids["serve.unpack"].counts["n_hits"] >= pairs.shape[0] > 0
        assert kids["serve.readback"].counts["pair_cap"] >= kids["serve.unpack"].counts["n_hits"]
    np.testing.assert_array_equal(first, second)


def test_serve_batch_rerun_on_pair_overflow(rng, tmp_path):
    """A batch with more hits than the pair capacity runs the stage and its
    readback again at a grown capacity, and says so in ``pair_retries``."""
    r = rng.normal(size=(300, 5)).astype(np.float32)
    q = rng.normal(size=(90, 5)).astype(np.float32)
    cfg = spjoin.JoinConfig(delta=1.0, metric="l2", k=64, p=8, n_dims=3)
    didx = index_lib.build_index(r, cfg).to_distributed(jax.make_mesh((1,), ("data",)))
    didx._pair_cap = 2
    pairs, spans = _traced(tmp_path, lambda: didx.query_batch(q))
    (root,) = [i for i, s in enumerate(spans.spans) if s.parent == -1]
    kids = [s for s in spans.spans if s.parent == root]
    assert [s.name for s in kids] == [
        "serve.put", "serve.route", "serve.stage", "serve.readback", "serve.stage",
        "serve.readback", "serve.unpack"]
    assert [s.counts["pair_retries"] for s in kids if s.name == "serve.stage"] == [0, 1]
    caps = [s.counts["pair_cap"] for s in kids if s.name == "serve.readback"]
    n_hits = kids[-1].counts["n_hits"]
    assert caps[0] == 2 < n_hits <= caps[1] == didx._pair_cap
    assert kids[-1].counts["n_pairs"] == pairs.shape[0]


@pytest.mark.parametrize("emit", ["mask", "compact"])
def test_distributed_join_span_tree_and_counters(rng, tmp_path, emit):
    x = _bits(rng, 400)
    mesh = jax.make_mesh((1,), ("data",))
    kw = dict(mesh=mesh, delta=float(np.sqrt(4.5)), metric="l2", p=8, k=128,
              emit_pairs=True, emit=emit)
    with jax.profiler.trace(str(tmp_path)):
        res = distributed.distributed_join(x, **kw)
    spans = dist_spans.read_dir(tmp_path)
    roots = [i for i, s in enumerate(spans.spans) if s.parent == -1]
    assert [spans.spans[i].name for i in roots] == ["dist.join"]
    root = spans.spans[roots[0]]
    assert set(root.counts) == {"request", "rows", "devices", "p"}
    assert (root.counts["rows"], root.counts["devices"], root.counts["p"]) == (400, 1, 8)
    assert _names(spans, roots[0]) == DIST_CHILDREN
    (plan,) = spans.named("dist.plan")
    assert set(plan.counts) == {"cap_v", "cap_w", "pair_cap", "duplication", "makespan_ratio"}
    assert plan.counts["duplication"] == pytest.approx(res.duplication)
    assert plan.counts["makespan_ratio"] == pytest.approx(res.makespan_ratio)
    assert (plan.counts["pair_cap"] > 0) == (emit == "compact")
    assert spans.named("dist.stage")[0].counts == {"overflow_retries": res.n_overflow_retries}
    assert spans.named("dist.readback")[0].counts == {"n_hits": res.n_hits}
    assert spans.named("dist.merge")[0].counts == {"n_pairs": res.pairs.shape[0]}
    assert res.pairs.shape[0] > 0
