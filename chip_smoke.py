"""Run the join's main path once on a TPU and check every result exactly.

    python chip_smoke.py               # one chip: join, distributed, serve
    python chip_smoke.py --four-chips  # four chips: distributed_join only

Data: a near-duplicate self-join over SIFT-shaped embeddings (1,048,576 ×
128 integer-valued f32, ``repro.data.synthetic.near_duplicates``, made from
``--seed``), L2, with δ set so that a row has about ten neighbours on a
sample. δ² sits halfway between two integers, so no pair lies within
rounding of the threshold. Phases, each through the entry points a user
calls, cold (compile included) and warm:

  join         ``spjoin.join`` with the default backend
  join-compact ``spjoin.join`` with ``emit="compact"`` (``ops.verify_compact``:
               Pallas distances, compaction on the device), first 2^19 rows
  distributed  ``distributed_join`` on ``make_host_mesh()``, first 2^19 rows
  serve        ``build_index`` → ``to_distributed(make_host_mesh())`` →
               ``query_batch`` of 4,096 held-out queries, several times

``--four-chips`` runs only ``distributed_join`` over the same 2^19 rows on
a 4-chip mesh, and prints the rows each device holds after the shuffle. Its
warm run reuses the cold run's compiled stages. The measured form of the
four-chip join is the benchmark cell ``hamming256-dist4-join`` (PERF.md).

Every phase's pairs must be byte-identical to the blocked device oracle
(``spjoin.brute_force_pairs`` / ``index.brute_force_query``), and its
result must report the Pallas backend and the fused map kernel. Any failure
raises; the script exits 0 only when every phase passed, and then prints
one JSON line last. It refuses to run where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1 << 20
# The one-shot distributed executor verifies each slot as one dense padded
# V×W tile on one device. At 2^20 of these rows with 64 cells the largest
# slot is 39,752 × 107,768 on one chip and 40,588 × 108,468 on four: more
# pairs than the int32 flat index of its compaction can address, so the
# verify stage does not trace on either mesh. At 2^19 it compiles for a
# v5e in 11.80 GB of temporaries (one chip) and 9.84 GB per chip (four), of
# 15.75 GB. Its phases therefore join the first 2^19 rows.
DIST_ROWS = 1 << 19
NEIGHBOURS = 10.0
PARTITIONS = 64  # kernel cells p, in every phase
QUERY_BATCH = 4096
N_BATCHES = 3


class PhaseError(RuntimeError):
    pass


def _peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _check_pairs(phase: str, got: np.ndarray, truth: np.ndarray) -> None:
    if got.dtype != truth.dtype or got.shape != truth.shape or got.tobytes() != truth.tobytes():
        g = {tuple(r) for r in got.tolist()}
        t = {tuple(r) for r in truth.tolist()}
        raise PhaseError(
            f"{phase}: {got.shape[0]} pairs vs the oracle's {truth.shape[0]} "
            f"({len(g - t)} extra, {len(t - g)} missing, e.g. "
            f"{sorted(g - t)[:3]} / {sorted(t - g)[:3]})"
        )


def _require(phase: str, what: str, ok: bool) -> None:
    if not ok:
        raise PhaseError(f"{phase}: {what}")


def choose_delta(data: np.ndarray, seed: int, sample: int = 256) -> tuple[float, float]:
    """δ with δ² = K + ½ for the least integer K at which ``sample`` random
    rows have on average ``NEIGHBOURS`` others within √K. Squared distances
    of integer rows are exact integers in f32. Returns (δ, mean neighbours
    measured on the sample)."""
    x = jnp.asarray(data)
    rows = np.random.default_rng(seed + 1).choice(data.shape[0], sample, replace=False)
    s = x[rows]
    sq = (x * x).sum(1)
    d2 = jnp.rint(
        sq[rows][:, None] + sq[None, :]
        - 2.0 * jnp.dot(s, x.T, precision=jax.lax.Precision.HIGHEST)
    )

    def mean_neighbours(k: int) -> float:
        return float((d2 <= k).sum()) / sample - 1.0  # each row is its own

    lo, hi = 0, 255 * 255 * data.shape[1]
    while lo < hi:
        mid = (lo + hi) // 2
        if mean_neighbours(mid) >= NEIGHBOURS:
            hi = mid
        else:
            lo = mid + 1
    return float(np.sqrt(lo + 0.5)), mean_neighbours(lo)


def _report(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body} peak_bytes_in_use={_peak_bytes()}", flush=True)


def phase_join(data, truth, cfg, phase="join") -> None:
    from repro.core import spjoin

    res, cold = _timed(lambda: spjoin.join(data, cfg))
    res, warm = _timed(lambda: spjoin.join(data, cfg))
    vs = res.verify_stats
    _report(
        phase, rows=data.shape[0], cold_s=cold, warm_s=warm, pairs=res.n_pairs,
        backend=vs.backend, emit=vs.emit, map_fused=res.map_fused,
        sample_s=res.sample_time_s, map_s=res.map_time_s, verify_s=res.verify_time_s,
        tiles=vs.n_tiles, tiles_pruned=vs.n_tiles_pruned, prune_rate=vs.prune_rate,
        overflow_retries=vs.n_overflow_retries,
    )
    _require(phase, f"verify ran on {vs.backend!r}, not pallas", vs.backend == "pallas")
    _require(phase, f"verify emitted by {vs.emit!r}, not {cfg.emit!r}", vs.emit == cfg.emit)
    _require(phase, "map phase did not run the fused kernel", res.map_fused)
    _check_pairs(phase, res.pairs, truth)


def phase_distributed(data, delta, truth, mesh, phase="distributed") -> None:
    from repro.core import distributed

    def run():
        # Compact emission: each slot's pairs are compacted on the device, so
        # only one slot's verification mask is live at a time.
        return distributed.distributed_join(
            data, mesh=mesh, delta=delta, metric="l2", p=PARTITIONS, emit_pairs=True,
            emit="compact",
        )

    res, cold = _timed(run)
    res, warm = _timed(run)
    _report(
        phase, rows=data.shape[0], devices=len(mesh.devices.flat), cold_s=cold,
        warm_s=warm, pairs=res.pairs.shape[0], backend=res.backend,
        map_fused=res.map_fused, p=PARTITIONS, device_rows=res.device_rows.tolist(),
        verifications=res.n_verifications, pruning_rate=res.pruning_rate,
        emit=res.emit, overflow_retries=res.n_overflow_retries,
    )
    _require(phase, f"stages ran on {res.backend!r}, not pallas", res.backend == "pallas")
    _require(phase, "stages did not run the fused map kernel", res.map_fused)
    _require(phase, f"dispatch overflowed {res.overflow} rows", res.overflow == 0)
    _check_pairs(phase, res.pairs, truth)


def phase_serve(data, queries, delta, cfg, mesh) -> None:
    from repro.core import index as index_lib

    idx, build = _timed(lambda: index_lib.build_index(data, cfg))
    didx, pin = _timed(lambda: idx.to_distributed(mesh))
    _require("serve", f"index mapped on {idx.backend!r}", idx.backend == "pallas")
    _require("serve", f"serving runs on {didx.backend!r}", didx.backend == "pallas")
    _require("serve", "index did not map with the fused kernel", idx.map_fused)
    times, n_pairs = [], 0
    for b in range(N_BATCHES):
        q = queries[b * QUERY_BATCH : (b + 1) * QUERY_BATCH]
        got, t = _timed(lambda: didx.query_batch(q))
        times.append(t)
        n_pairs += got.shape[0]
        _check_pairs(f"serve batch {b}", got, index_lib.brute_force_query(data, q, delta, "l2"))
    _report(
        "serve", rows=data.shape[0], queries=N_BATCHES * QUERY_BATCH,
        build_s=build, pin_s=pin, cold_s=times[0], warm_s=float(np.median(times[1:])),
        pairs=n_pairs, backend=didx.backend, map_fused=idx.map_fused,
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run distributed_join on a 4-chip mesh, and nothing else")
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform})", file=sys.stderr)
        return 2
    n_chips = 4 if args.four_chips else 1
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.core import spjoin
    from repro.data import synthetic
    from repro.launch import mesh as mesh_lib

    cache = mesh_lib.use_compile_cache()
    kind = devices[0].device_kind
    print(f"device_kind={kind} devices={len(devices)} compile_cache={cache}", flush=True)

    (everything, gen_s) = _timed(lambda: synthetic.near_duplicates(
        ROWS + N_BATCHES * QUERY_BATCH, seed=args.seed))
    data, queries = everything[:ROWS], everything[ROWS:]
    delta, neighbours = choose_delta(data, args.seed)
    print(f"data rows={ROWS} dims={data.shape[1]} dtype={data.dtype} gen_s={gen_s} "
          f"metric=l2 delta={delta!r} mean_neighbours_on_sample={neighbours}", flush=True)
    # The distributed phases join the first DIST_ROWS rows under the same δ.
    print(f"cut: distributed_join runs on the first {DIST_ROWS} of {ROWS} rows, "
          "on one chip and on four (at full size its largest slot holds more "
          "V×W pairs than its int32 compaction index addresses)", flush=True)
    rows = DIST_ROWS if args.four_chips else ROWS
    truth, oracle_s = _timed(lambda: spjoin.brute_force_pairs(data[:rows], delta, "l2"))
    print(f"oracle rows={rows} pairs={truth.shape[0]} time_s={oracle_s}", flush=True)
    dist_truth = truth[truth[:, 1] < DIST_ROWS]  # i < j: both ends inside the cut

    if args.four_chips:
        mesh = mesh_lib.make_host_mesh(4)
        phase_distributed(data[:DIST_ROWS], delta, dist_truth, mesh,
                          phase="distributed-4chip")
    else:
        cfg = spjoin.JoinConfig(delta=delta, metric="l2", p=PARTITIONS)
        mesh = mesh_lib.make_host_mesh()
        phase_join(data, truth, cfg)
        # The compact-emission path (ops.verify_compact), on the distributed rows.
        phase_join(data[:DIST_ROWS], dist_truth, dataclasses.replace(cfg, emit="compact"),
                   phase="join-compact")
        phase_distributed(data[:DIST_ROWS], delta, dist_truth, mesh)
        phase_serve(data, queries, delta, cfg, mesh)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
