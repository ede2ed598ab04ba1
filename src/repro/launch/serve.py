"""Serving entry points: the metric-index range-query server + an LM demo.

Two subcommands:

``range`` — the REAL query-serving path of this repo (docs/SERVING.md):
build a persistent ``core.index.MetricIndex`` once, pin its per-slot V
buffers on a ``launch.mesh.make_host_mesh`` device mesh, then serve
δ-range query batches through the distributed verify-stage slot machinery
(one W-side all_to_all per batch, zero R bytes moved after build). Prints
build time, per-batch latency, QPS/p50/p99, and checks one batch against
the brute-force oracle.

    PYTHONPATH=src python -m repro.launch.serve range \\
        --n 20000 --m 16 --queries 4096 --batch 256

``lm`` — the batched LM prefill+decode demo (prefill-by-decode keeps
KV/SSM state layouts identical between phases, which is what makes the
decode_* dry-run cells representative):

    PYTHONPATH=src python -m repro.launch.serve lm --arch qwen1.5-0.5b \\
        --reduced --batch 4 --prompt-len 32 --gen 32

Bare ``--arch ...`` argv (no subcommand) is routed to ``lm`` so
``examples/serve_lm.py`` keeps working unchanged.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import use_compile_cache


# ---------------------------------------------------------------------------
# range: metric-index query serving (build once, query millions)
# ---------------------------------------------------------------------------


def serve_range(args) -> None:
    from repro.core import index as index_lib
    from repro.core import spjoin
    from repro.data import synthetic
    from repro.launch import mesh as mesh_lib

    # queries drawn near the indexed clusters (rs_mixture shares centers) so
    # the default δ actually produces hits
    data, queries = synthetic.rs_mixture(args.n, args.queries, args.m,
                                         n_clusters=6, spread=6.0, skew=0.3,
                                         shift=1.5, seed=0)
    cfg = spjoin.JoinConfig(delta=args.delta, metric=args.metric,
                            k=min(1024, args.n // 4), p=16, n_dims=8, seed=0)

    t0 = time.perf_counter()
    idx = index_lib.build_index(data, cfg)
    print(f"build: N={idx.n_rows} m={idx.n_features} p={idx.p} "
          f"in {time.perf_counter() - t0:.2f}s")

    mesh = mesh_lib.make_host_mesh(axis="data")
    didx = idx.to_distributed(mesh)
    print(f"pinned V buffers on {mesh.devices.size} device(s); serving")

    batches = [queries[i : i + args.batch]
               for i in range(0, args.queries, args.batch)]
    didx.query_batch(batches[0])  # warm-up (stage compile)

    lat, n_pairs = [], 0
    for i, b in enumerate(batches):
        t0 = time.perf_counter()
        pairs = didx.query_batch(b)
        lat.append(time.perf_counter() - t0)
        n_pairs += int(pairs.shape[0])
        if i < 3 or (i + 1) == len(batches):
            print(f"  batch {i + 1}/{len(batches)}: {b.shape[0]} queries, "
                  f"{pairs.shape[0]} pairs, {lat[-1] * 1e3:.1f} ms")

    lat_ms = np.asarray(lat) * 1e3
    n_q = sum(b.shape[0] for b in batches)
    print(f"served {n_q} queries, {n_pairs} pairs: "
          f"{n_q / lat_ms.sum() * 1e3:.0f} QPS, "
          f"p50 {np.percentile(lat_ms, 50):.1f} ms, "
          f"p99 {np.percentile(lat_ms, 99):.1f} ms")

    truth = index_lib.brute_force_query(data, batches[0], args.delta,
                                        args.metric)
    got = didx.query_batch(batches[0])
    assert np.array_equal(got, truth), "parity check vs brute force FAILED"
    print("parity vs brute force: ok")


# ---------------------------------------------------------------------------
# lm: batched prefill + streaming decode demo
# ---------------------------------------------------------------------------


def prefill_by_decode(params, tokens, cfg, state, serve_step):
    """Feed prompt tokens one step at a time (exact state, any family)."""
    B, T = tokens.shape
    for t in range(T):
        _, _, state = serve_step(params, tokens[:, t : t + 1], state, jnp.int32(t))
    return state


def serve_lm(args) -> None:
    from repro import configs
    from repro.models import base, transformer
    from repro.train import train_step as ts

    cfg = configs.get_reduced(args.arch) if args.reduced else configs.get(args.arch)
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode path")

    defs = transformer.model_defs(cfg)
    params = base.init_params(jax.random.PRNGKey(0), defs)
    max_len = args.prompt_len + args.gen
    state = transformer.init_state(cfg, args.batch, max_len)

    mode = "greedy" if args.temperature == 0.0 else "temp"
    serve_step = jax.jit(
        ts.make_serve_step(cfg, "greedy" if mode == "greedy" else "sample",
                           max(args.temperature, 1e-3))
    )

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32
    )

    t0 = time.time()
    state = prefill_by_decode(params, prompts, cfg, state, serve_step)
    t_prefill = time.time() - t0

    tok = prompts[:, -1:]
    out = []
    t0 = time.time()
    for i in range(args.gen):
        tok, _, state = serve_step(
            params, tok, state, jnp.int32(args.prompt_len + i)
        )
        out.append(np.asarray(tok)[:, 0])
    t_decode = time.time() - t0

    gen = np.stack(out, axis=1)
    print(f"prefill {args.batch}x{args.prompt_len} in {t_prefill:.2f}s; "
          f"decode {args.gen} steps in {t_decode:.2f}s "
          f"({args.batch * args.gen / max(t_decode, 1e-9):.1f} tok/s)")
    print("sample output ids:", gen[0][:16])
    assert gen.shape == (args.batch, args.gen)
    assert (gen >= 0).all() and (gen < cfg.vocab).all()
    print("ok")


def main() -> None:
    argv = sys.argv[1:]
    if argv and argv[0].startswith("-"):
        argv = ["lm"] + argv  # pre-subcommand compat: bare --arch means lm

    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    rp = sub.add_parser("range", help="metric-index δ-range query serving")
    rp.add_argument("--n", type=int, default=20_000, help="indexed rows")
    rp.add_argument("--m", type=int, default=16, help="features")
    rp.add_argument("--queries", type=int, default=4096)
    rp.add_argument("--batch", type=int, default=256)
    rp.add_argument("--delta", type=float, default=3.0)
    rp.add_argument("--metric", default="l2")
    rp.set_defaults(fn=serve_range)

    lp = sub.add_parser("lm", help="batched LM prefill + decode demo")
    lp.add_argument("--arch", required=True)
    lp.add_argument("--reduced", action="store_true")
    lp.add_argument("--batch", type=int, default=4)
    lp.add_argument("--prompt-len", type=int, default=32)
    lp.add_argument("--gen", type=int, default=32)
    lp.add_argument("--temperature", type=float, default=0.0)
    lp.set_defaults(fn=serve_lm)

    args = ap.parse_args(argv)
    use_compile_cache()
    args.fn(args)


if __name__ == "__main__":
    main()
