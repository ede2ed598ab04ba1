"""§Serving: build-once-query-millions QPS / latency benchmark.

The serving claim of docs/SERVING.md, measured: ``core.index.build_index``
pays the control plane (sampling → anchors → partition boxes → placement
plan) EXACTLY ONCE, and every ``query_batch`` after that performs zero
sampling/anchor/partition calls — enforced here with module-attribute call
counters around the build entry points (the same technique as the
regression test in ``tests/test_index.py``), not just asserted by eye.

Arms:

  host  — the single-host ``MetricIndex.query_batch`` path: one warm-up
          batch (compile), then ≥1000 timed queries in fixed-size batches.
          Reports QPS (queries / total timed seconds), p50/p99 per-batch
          latency, routing duplication, and byte-identity of one batch
          against ``distances.brute_force_join``.
  dist  — the same index pinned on a 1-device mesh via ``to_distributed``
          (the ``DistIndex`` slot machinery end-to-end: W dispatch,
          all_to_all, per-slot verify against resident V buffers), same
          metrics + parity. CI exercises the full path without a real mesh.
  load  — save → load → one parity batch (the lifecycle round trip).

Emits ``runs/bench_serve_qps.csv`` + ``runs/serve_qps.json`` (the CI
serving-smoke contract: ``build_count == 1``,
``build_calls_during_queries == 0``, ``parity_ok`` true on every arm,
``n_queries >= 1000``, positive ``qps``).

Run:
    PYTHONPATH=src python benchmarks/serve_qps.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/serve_qps.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))

import numpy as np

from benchmarks.common import Csv, OUT_DIR
from repro.core import index as index_lib
from repro.core import mapping, partition, spjoin
from repro.data import synthetic
from repro.launch.mesh import use_compile_cache

# Control-plane entry points the BUILD phase owns. Each is patched at its
# defining module, and every call site reaches it through module-attribute
# access, so a query that re-enters any of them is counted.
BUILD_CALLS = (
    (spjoin, "fit_node_stats"),
    (spjoin, "draw_pivots"),
    (mapping, "select_anchors"),
    (partition, "build_partition"),
)


class BuildCallCounter:
    """Context manager counting calls to the build-phase entry points."""

    def __init__(self):
        self.counts: dict[str, int] = {}
        self._orig: list[tuple] = []

    def __enter__(self) -> "BuildCallCounter":
        for mod, name in BUILD_CALLS:
            fn = getattr(mod, name)
            key = f"{mod.__name__.rsplit('.', 1)[-1]}.{name}"
            self.counts[key] = 0

            def wrapper(*a, _fn=fn, _key=key, **kw):
                self.counts[_key] += 1
                return _fn(*a, **kw)

            self._orig.append((mod, name, fn))
            setattr(mod, name, wrapper)
        return self

    def __exit__(self, *exc) -> None:
        for mod, name, fn in self._orig:
            setattr(mod, name, fn)
        self._orig.clear()

    @property
    def total(self) -> int:
        return sum(self.counts.values())


def _timed_queries(query_fn, batches: list[np.ndarray]):
    """Warm up on batch 0 (compile), then time every batch."""
    query_fn(batches[0])  # warm-up: stage compile + bucket traces
    lat, n_pairs = [], 0
    for b in batches:
        t0 = time.perf_counter()
        pairs = query_fn(b)
        lat.append(time.perf_counter() - t0)
        n_pairs += int(pairs.shape[0])
    lat_ms = np.array(lat) * 1e3
    total_s = float(np.array(lat).sum())
    n_q = sum(b.shape[0] for b in batches)
    return {
        "n_queries": int(n_q),
        "n_batches": len(batches),
        "batch_size": int(batches[0].shape[0]),
        "n_pairs": n_pairs,
        "qps": float(n_q / max(total_s, 1e-9)),
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "total_s": total_s,
    }


def run(
    n: int = 20_000,
    m: int = 16,
    n_queries: int = 4096,
    batch: int = 256,
    smoke: bool = False,
) -> dict:
    if smoke:
        n, m, n_queries, batch = 3000, 12, 1024, 128
    assert n_queries >= 1000, "acceptance: build once across >= 1000 queries"

    data = synthetic.mixture(n, m, n_clusters=6, spread=6.0, skew=0.3, seed=0)
    queries = synthetic.mixture(
        n_queries, m, n_clusters=6, spread=6.0, skew=0.3, seed=7
    )
    # δ at a small quantile of the R↔Q cross distances, so results are
    # non-empty but selective (the serving regime).
    from repro.core import distances
    import jax.numpy as jnp

    d = np.asarray(
        distances.pairwise(jnp.asarray(data[:512]), jnp.asarray(queries[:512]), "l2")
    )
    delta = float(np.quantile(d, 0.001))

    cfg = spjoin.JoinConfig(
        delta=delta, metric="l2", k=min(1024, n // 4), p=16,
        n_dims=8, seed=0,
    )

    # ---- build phase: exactly once, counted -------------------------------
    counter = BuildCallCounter()
    with counter:
        idx = index_lib.build_index(data, cfg)
    build_calls = dict(counter.counts)
    assert counter.total > 0, "build must exercise the control plane"

    batches = [
        queries[i : i + batch]
        for i in range(0, n_queries, batch)
        if queries[i : i + batch].shape[0] == batch
    ]

    # ---- query phase: zero build calls, measured --------------------------
    with counter:  # re-enter: counters reset to 0
        host = _timed_queries(idx.query_batch, batches)
    build_calls_during_queries = counter.total
    assert build_calls_during_queries == 0, (
        f"query phase re-entered the build control plane: {counter.counts}"
    )

    oracle = index_lib.brute_force_query(data, batches[0], delta, cfg.metric)
    host["parity_ok"] = bool(np.array_equal(idx.query_batch(batches[0]), oracle))
    _, qstats = idx.query_batch(batches[0], with_stats=True)
    host["duplication"] = qstats.duplication
    host["cells_touched"] = qstats.n_cells_touched

    # ---- distributed arm: the slot machinery end-to-end (1 device) --------
    from repro.launch import mesh as mesh_lib

    dist_idx = idx.to_distributed(mesh_lib.make_host_mesh(1))
    with counter:
        dist = _timed_queries(dist_idx.query_batch, batches[: max(4, len(batches) // 4)])
    assert counter.total == 0, "distributed query phase re-entered the build"
    dist["parity_ok"] = bool(np.array_equal(dist_idx.query_batch(batches[0]), oracle))

    # ---- lifecycle round trip: save -> load -> query ----------------------
    path = os.path.join(OUT_DIR, "serve_qps_index")
    t0 = time.perf_counter()
    idx.save(path)
    save_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx2 = index_lib.MetricIndex.load(path, metric=cfg.metric)
    load_s = time.perf_counter() - t0
    load_parity = bool(np.array_equal(idx2.query_batch(batches[0]), oracle))

    report = {
        "smoke": smoke,
        "n_index": n,
        "m": m,
        "delta": delta,
        "metric": cfg.metric,
        "build_count": 1,  # build_index invoked exactly once above
        "build_s": idx.build_s,
        "build_calls": build_calls,
        "build_calls_during_queries": build_calls_during_queries,
        "host": host,
        "distributed": dist,
        "lifecycle": {"save_s": save_s, "load_s": load_s, "parity_ok": load_parity},
    }

    csv = Csv(
        "bench_serve_qps.csv",
        ["arm", "n_index", "n_queries", "batch", "build_s", "qps",
         "p50_ms", "p99_ms", "n_pairs", "parity_ok"],
    )
    for arm, r in (("host", host), ("dist-1dev", dist)):
        csv.row(
            arm, n, r["n_queries"], r["batch_size"], f"{idx.build_s:.3f}",
            f"{r['qps']:.1f}", f"{r['p50_ms']:.2f}", f"{r['p99_ms']:.2f}",
            r["n_pairs"], r["parity_ok"],
        )
    csv.close()

    out_path = os.path.join(OUT_DIR, "serve_qps.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report))
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing: 3k index rows, 1024 queries")
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--n-queries", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args()
    use_compile_cache()
    run(n=args.n, n_queries=args.n_queries, batch=args.batch, smoke=args.smoke)


if __name__ == "__main__":
    main()
