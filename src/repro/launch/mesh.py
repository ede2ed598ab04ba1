"""Production mesh construction + TPU v5e hardware model.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — the dry-run sets
XLA_FLAGS before first jax init, smoke tests keep their single device.

Mesh semantics:
  single-pod (16, 16)    axes ("data", "model") — 256 chips
  multi-pod  (2, 16, 16) axes ("pod", "data", "model") — 512 chips

"data" (+"pod") carries batch/FSDP and is the SP-Join "local node" axis;
"model" carries TP/EP. The pod axis crosses DCN: only data-parallel
gradient all-reduces (and nothing latency-sensitive) traverse it.

Serving: ``make_host_mesh`` is the mesh entry point of the query-serving
path (docs/SERVING.md) — ``MetricIndex.to_distributed(make_host_mesh())``
pins the per-slot V buffers over the "data" axis and every
``query_batch`` moves only query bytes (one W-side all_to_all). Runnable:
``python -m repro.launch.serve range``. ``HardwareModel``/``V5E`` are the
roofline denominators ``benchmarks/roofline.py`` renders.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib

import jax
import numpy as np
from jax.sharding import Mesh

# Fixed, so that every process of a checkout finds what earlier ones cached.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and nothing is changed; otherwise the cache lives in
    ``<repo root>/.jax_cache``. Call it from ``main``, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, axis: str = "data") -> Mesh:
    """1-D mesh over whatever devices exist — the serving-path default
    (``MetricIndex.to_distributed`` shards V buffers over ``axis``) and the
    tests/examples mesh. ``n=None`` takes every visible device."""
    n = n or len(jax.devices())
    return jax.make_mesh((n,), (axis,))


def make_elastic_mesh(live_hosts: int, chips_per_host: int = 4) -> Mesh:
    """Elastic re-mesh: mesh shape as a function of the LIVE host set.

    The training driver calls this after membership changes; the data
    pipeline is step-addressed so the global batch is unchanged — only its
    sharding moves (launch/train.py)."""
    total = live_hosts * chips_per_host
    model = 1
    for cand in (16, 8, 4, 2, 1):
        if total % cand == 0 and cand <= total:
            model = cand
            break
    return jax.make_mesh((total // model, model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """TPU v5e per-chip constants (the roofline denominators)."""

    peak_flops: float = 197e12  # bf16 FLOP/s
    hbm_bw: float = 819e9  # bytes/s
    ici_bw: float = 50e9  # bytes/s per link direction
    hbm_bytes: float = 16e9  # capacity

    def roofline_seconds(
        self, flops: float, bytes_hbm: float, bytes_coll: float, chips: int
    ) -> dict:
        return {
            "compute_s": flops / (chips * self.peak_flops),
            "memory_s": bytes_hbm / (chips * self.hbm_bw),
            "collective_s": bytes_coll / (chips * self.ici_bw),
        }


V5E = HardwareModel()
