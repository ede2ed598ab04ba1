"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what Mosaic cannot lower (block
tiling, unsupported primitives, VMEM overruns) — which interpret mode never
checks. Each test compiles one raw ``*_blocked`` kernel with
``interpret=False`` at the block sizes the ``ops`` wrappers use, and checks
that the compiled program holds the kernel.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers all import
this file. Keep every such compile in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compact import verify_compact_blocked
from repro.kernels.histogram import histogram_blocked
from repro.kernels.mapassign import map_assign_blocked
from repro.kernels.pairdist import pairdist_blocked, pairdist_filtered_blocked


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler, or libtpu held by another process
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` — an abstract argument placed on one chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


def _compile(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo  # the Pallas kernel itself, not a fallback


@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_pairdist(spec, metric):
    _compile(
        lambda x, y: pairdist_blocked(x, y, metric=metric, interpret=False),
        spec((1024, 128)), spec((4096, 128)),
    )


@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_pairdist_filtered(spec, metric):
    _compile(
        lambda x, y, px, py: pairdist_filtered_blocked(
            x, y, px, py, metric=metric, delta=1.0, delta_bound=1.1, interpret=False
        ),
        spec((1024, 128)), spec((4096, 128)), spec((1024, 16)), spec((4096, 16)),
    )


@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_map_assign(spec, metric):
    # p = 64 partitions over 8 mapped dims: the ops wrapper's partition
    # block is then the whole (word-aligned) partition axis.
    boxes = [spec((64, 8)) for _ in range(4)]
    _compile(
        lambda x, a, *b: map_assign_blocked(
            x, a, *b, metric=metric, bp=64, interpret=False
        ),
        spec((4096, 128)), spec((8, 128)), *boxes,
    )


def test_histogram(spec):
    _compile(
        lambda u, w: histogram_blocked(u, w, t=8, interpret=False),
        spec((65536, 128)), spec((65536, 1)),
    )


@pytest.mark.parametrize("metric,prune", [("l2", False), ("l1", True)])
def test_verify_compact(spec, metric, prune):
    a, b = 1024, 1024
    ids = [spec((a, 1), jnp.int32), spec((b, 1), jnp.int32), spec((b, 1), jnp.int32),
           spec((1, 1), jnp.int32)]
    coords = [spec((a, 16)), spec((b, 16))] if prune else []
    _compile(
        lambda x, y, v, w, wc, c, *pxy: verify_compact_blocked(
            x, y, v, w, wc, c, *pxy, metric=metric, delta=1.0,
            delta_bound=1.1 if prune else None, capacity=1024, interpret=False,
        ),
        spec((a, 128)), spec((b, 128)), *ids, *coords,
    )


# The names a profile shows for the kernels and the serve stage: the
# benchmark reads device time by them (``bench/metrics/``).
KERNEL_NAMES = {
    "pairdist_filtered_blocked": (
        lambda x, y, px, py: pairdist_filtered_blocked(
            x, y, px, py, metric="l2", delta=1.0, delta_bound=1.1, interpret=False),
        [(1024, 128), (4096, 128), (1024, 16), (4096, 16)],
    ),
    "pairdist_blocked": (
        lambda x, y: pairdist_blocked(x, y, metric="l2", interpret=False),
        [(1024, 128), (4096, 128)],
    ),
    "map_assign_blocked": (
        lambda x, a, *b: map_assign_blocked(x, a, *b, metric="l2", bp=64, interpret=False),
        [(4096, 128), (8, 128)] + [(64, 8)] * 4,
    ),
    "histogram_blocked": (
        lambda u, w: histogram_blocked(u, w, t=8, interpret=False),
        [(65536, 128), (65536, 1)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_keeps_its_name(spec, name):
    fn, shapes = KERNEL_NAMES[name]
    hlo = jax.jit(fn).lower(*(spec(s) for s in shapes)).compile().as_text()
    assert re.search(rf"%{name}\.\d+ = [^\n]* custom-call\(", hlo), name


def test_serve_stage_program_name(topo, monkeypatch):
    """The compacting serve stage keeps its program name, and compiles for
    the described v5e with its Pallas kernels (not their interpreter)."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import index as index_lib
    from repro.core import spjoin
    from repro.kernels import ops as kops

    rng = np.random.default_rng(0)
    r = (rng.random((300, 16)) > 0.5).astype(np.float32)
    cfg = spjoin.JoinConfig(delta=2.0, metric="l2", k=64, p=8, n_dims=3)
    didx = index_lib.build_index(r, cfg).to_distributed(jax.make_mesh((1,), ("data",)))
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    on_chip = dataclasses.replace(didx, mesh=mesh, backend="pallas", _stages={})
    stage = on_chip._stage(2.0, 256, 2.5, on_chip._pair_cap)
    shard = NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shard) for a in (didx.fv, didx.fv_ids)]
    args += [jax.ShapeDtypeStruct(s, d, sharding=shard)
             for s, d in (((256, 16), jnp.float32), ((256,), jnp.float32), ((256,), jnp.int32))]
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    lowered = stage.lower(*args)
    assert re.search(r"module @jit_per_shard\b", lowered.as_text())
    assert "tpu_custom_call" in lowered.compile().as_text()
