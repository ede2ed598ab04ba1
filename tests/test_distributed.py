"""Multi-device tests: the shard_map distributed join on 8 simulated CPU
devices. Each test runs in a subprocess so the device-count flag never
leaks into the rest of the suite (smoke tests must see 1 device)."""
import json
import subprocess
import sys
import textwrap

import pytest

def _run(code: str, devices: int = 8) -> dict:
    prog = (f"import os\nos.environ['XLA_FLAGS']='--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(code))
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.slow
def test_distributed_join_exact_both_samplers():
    res = _run("""
    import json, numpy as np, jax, jax.numpy as jnp
    mesh = jax.make_mesh((8,), ("data",))
    from repro.core import distributed, spjoin
    rng = np.random.default_rng(0)
    data = np.concatenate([
        rng.normal(loc=c, scale=1.0, size=(250, 8)) for c in (0., 5., 10., 15.)
    ]).astype(np.float32)
    truth = spjoin.brute_force_pairs(data, 3.0, "l1")
    out = {}
    for sampler in ("generative", "random"):
        r = distributed.distributed_join(
            jnp.asarray(data), mesh=mesh, delta=3.0, metric="l1", k=256, p=16,
            n_dims=4, emit_pairs=True, sampler=sampler, seed=0)
        out[sampler] = dict(
            exact=bool(np.array_equal(r.pairs, truth)),
            hits=int(r.n_hits), overflow=int(r.overflow),
            padding=float(r.capacity_padding), verif=int(r.n_verifications))
    print(json.dumps(out))
    """)
    for sampler, r in res.items():
        assert r["exact"], (sampler, r)
        assert r["overflow"] == 0


# Near-duplicate 0/1 codes and a δ² halfway between two integers, so that
# every engine's distances land on the same side of δ.
CODES = """
import json, numpy as np, jax
from jax import monitoring
from repro.core import distances, distributed, spjoin
from repro.launch import mesh as mesh_lib
rng = np.random.default_rng(0)
centres = rng.random((16, 64)) > 0.5
x = (centres[rng.integers(0, 16, 800)] ^ (rng.random((800, 64)) < 0.05)).astype(np.float32)
delta = float(np.sqrt(4.5))
mesh = mesh_lib.make_host_mesh(4)
kw = dict(mesh=mesh, delta=delta, metric="l2", p=16, k=128, emit_pairs=True, emit="compact")
compiled = []
monitoring.register_event_duration_secs_listener(
    lambda event, _s, **_k: compiled.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)
"""


def _run_codes(code: str) -> dict:
    """``code`` on four devices, after ``CODES`` has set up the codes."""
    return _run(CODES + textwrap.dedent(code), devices=4)


def test_distributed_join_compact_4dev_matches_brute_force_and_spjoin():
    res = _run_codes("""
    r = distributed.distributed_join(x, **kw)
    truth = np.argwhere(np.asarray(distances.brute_force_join(x, delta, "l2")))
    host = spjoin.join(x, spjoin.JoinConfig(delta=delta, metric="l2", p=16, k=128)).pairs
    print(json.dumps(dict(
        n=int(r.pairs.shape[0]), emit=r.emit, devices=len(r.device_rows),
        brute=r.pairs.tobytes() == truth.astype(np.int64).tobytes(),
        spjoin=r.pairs.tobytes() == np.asarray(host, np.int64).tobytes())))
    """)
    assert res["brute"] and res["spjoin"], res
    assert res["n"] > 0 and res["emit"] == "compact" and res["devices"] == 4


def test_second_distributed_join_compiles_nothing():
    """The join's stages are kept by their static configuration: a second
    join of the same shapes compiles (or loads) no program, and returns the
    same pairs."""
    res = _run_codes("""
    first = distributed.distributed_join(x, **kw)
    n_first = len(compiled)
    second = distributed.distributed_join(x, **kw)
    n_second = len(compiled) - n_first
    held = distributed.clear_join_stages()
    third = distributed.distributed_join(x, **kw)
    print(json.dumps(dict(n_first=n_first, n_second=n_second, held=held,
                          n_third=len(compiled) - n_first - n_second,
                          same=first.pairs.tobytes() == second.pairs.tobytes() == third.pairs.tobytes())))
    """)
    assert res["n_first"] > 0 and res["n_second"] == 0, res
    # stats, counts and verify; once cleared, each is built and compiled again
    assert res["held"] == res["n_third"] == 3 and res["same"], res


def test_fitted_pair_capacity_is_kept_for_the_next_join():
    """A first pair capacity too small for the hits reruns the stage at a
    capacity sized from the exact count; the next join of the configuration
    starts there, so it neither reruns nor compiles."""
    res = _run_codes("""
    distributed._FIRST_PAIR_CAP = 8
    first = distributed.distributed_join(x, **kw)
    n_first = len(compiled)
    second = distributed.distributed_join(x, **kw)
    n_second = len(compiled) - n_first
    truth = np.argwhere(np.asarray(distances.brute_force_join(x, delta, "l2")))
    print(json.dumps(dict(
        retries=[first.n_overflow_retries, second.n_overflow_retries], n_second=n_second,
        exact=first.pairs.tobytes() == second.pairs.tobytes() == truth.astype(np.int64).tobytes())))
    """)
    assert res["retries"][0] >= 1 and res["retries"][1] == 0, res
    assert res["n_second"] == 0 and res["exact"], res


@pytest.mark.slow
def test_distributed_stats_match_host_fits():
    res = _run("""
    import json, numpy as np, jax, jax.numpy as jnp
    mesh = jax.make_mesh((8,), ("data",))
    from repro.core import distributed, gof
    from jax.sharding import NamedSharding, PartitionSpec as P
    rng = np.random.default_rng(1)
    data = jnp.asarray(rng.normal(3.0, 2.0, size=(800, 4)), jnp.float32)
    valid = jnp.ones((800,), jnp.float32)
    sh = NamedSharding(mesh, P("data"))
    fn = distributed.make_stage_stats(mesh, "data")
    packets, confs, counts = jax.tree.map(
        np.asarray, fn(jax.device_put(data, sh), jax.device_put(valid, sh)))
    # host-side fit of shard 0 must match packet 0
    shard0 = np.asarray(data[:100])
    params, res0 = gof.fit_best_family(jnp.asarray(shard0))
    from repro.core import expfam
    want = np.asarray(expfam.pack(params))
    print(json.dumps(dict(
        packet_close=bool(np.allclose(packets[0], want, rtol=1e-3, atol=1e-3)),
        conf_close=bool(abs(confs[0] - float(res0.confidence)) < 1e-3),
        counts_ok=bool((counts == 100).all()))))
    """)
    assert res["packet_close"] and res["conf_close"] and res["counts_ok"], res


@pytest.mark.slow
def test_distributed_join_skewed_data_padding_story():
    """Better sampling -> lower capacity padding (TPU skew metric)."""
    res = _run("""
    import json, numpy as np, jax, jax.numpy as jnp
    mesh = jax.make_mesh((8,), ("data",))
    from repro.core import distributed
    rng = np.random.default_rng(2)
    from repro.data import synthetic
    data = synthetic.mixture(1600, 6, n_clusters=5, skew=0.6, seed=3)
    out = {}
    for sampler in ("generative", "random"):
        r = distributed.distributed_join(
            jnp.asarray(data), mesh=mesh, delta=2.0, metric="l1", k=192,
            p=16, n_dims=4, sampler=sampler, seed=0)
        out[sampler] = dict(hits=int(r.n_hits), verif=int(r.n_verifications),
                            cap=int(r.exact_cap_w))
    print(json.dumps(out))
    """)
    # both exact joins must agree on hit count regardless of sampler
    assert res["generative"]["hits"] == res["random"]["hits"], res


@pytest.mark.slow
def test_two_step_dp_tp_training_on_mesh():
    """2-step DP x TP train loop on a (4, 2) mesh — grads/updates flow
    through sharded params + sharded batch."""
    res = _run("""
    import json, numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    from repro import configs
    from repro.models import base, transformer
    from repro.train import optimizer as opt_lib, train_step as ts
    from repro.models.config import ShapeConfig
    cfg = configs.get_reduced("stablelm-3b")
    defs = transformer.model_defs(cfg)
    params = base.init_params(jax.random.PRNGKey(0), defs)
    shard = base.make_shardings(defs, mesh)
    params = jax.tree.map(jax.device_put, params, shard)
    ocfg = opt_lib.OptConfig(total_steps=10, warmup_steps=1)
    opt = opt_lib.init_opt_state(params, ocfg)
    step = jax.jit(ts.make_train_step(cfg, ocfg, ts.StepConfig()))
    batch = configs.input_specs(cfg, ShapeConfig("s", 64, 8, "train"), abstract=False)["batch"]
    bsh = NamedSharding(mesh, P("data"))
    batch = {k: jax.device_put(v, bsh) for k, v in batch.items()}
    with base.use_mesh(mesh):
        losses = []
        for _ in range(3):
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["total"]))
    print(json.dumps(dict(losses=losses,
                          decreased=bool(losses[-1] < losses[0]),
                          finite=bool(np.isfinite(losses).all()))))
    """)
    assert res["decreased"] and res["finite"], res


@pytest.mark.slow
def test_shardmap_moe_matches_local_path():
    """H2's explicit expert-parallel shard_map must equal the local
    (single-device) MoE dispatch numerically."""
    res = _run("""
    import json, dataclasses, numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.models import base, moe as moe_lib
    cfg = dataclasses.replace(configs.get_reduced("deepseek-moe-16b"),
                              n_experts=8, top_k=2, n_shared_experts=2,
                              capacity_factor=8.0)
    params = base.init_params(jax.random.PRNGKey(0), moe_lib.moe_defs(cfg))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 32, cfg.d_model)), jnp.float32)
    y_local, _ = moe_lib.moe_block(params, x, cfg, group_size=16)
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    with base.use_mesh(mesh):
        y_sm, _ = jax.jit(lambda p, xx: moe_lib.moe_block(p, xx, cfg, group_size=16))(params, xs)
    close = bool(np.allclose(np.asarray(y_local), np.asarray(y_sm), rtol=2e-3, atol=2e-3))
    print(json.dumps(dict(close=close)))
    """)
    assert res["close"], res
