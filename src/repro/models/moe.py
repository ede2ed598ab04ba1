"""Mixture-of-Experts FFN with capacity-bounded dispatch.

The dispatch machinery is deliberately the same shape as SP-Join's partition
shuffle (DESIGN.md §2): tokens are objects, experts are cells, the router is
the partitioner, and static capacity + drop/overflow accounting replaces the
dynamic shuffle — skew costs padding, not stragglers. Both llama4-scout
(16e top-1 + shared) and deepseek-moe (64e top-6 + 2 shared, fine-grained)
are instances of this one module.

Execution layout (TP/EP over the "model" mesh axis):
  - activations between blocks are replicated across "model" (Megatron
    convention), so routing + dispatch-buffer construction are computed
    redundantly per rank — zero communication;
  - expert weights are sharded on the expert dim ("experts" -> "model"), so
    the expert einsum partitions on E: each rank slices its experts' rows of
    the (replicated) dispatch buffer — again no gather;
  - the combine scatter-add sums contributions across expert shards; XLA
    SPMD realizes it as the block's single all-reduce (same cost as a dense
    TP block).

Tokens are processed in groups of ~``group_size`` (scan) so the (E, C, d)
dispatch buffer stays ~100s of MiB regardless of sequence length.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import layers
from repro.models.base import current_act_rules, current_mesh, pdef, shard_act

Array = jnp.ndarray


def moe_defs(cfg) -> dict:
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    out = {
        "router": pdef((d, E), ("embed", None), init="scaled"),
        "gate": pdef((E, d, f), ("experts", "embed", "mlp"), init="scaled"),
        "up": pdef((E, d, f), ("experts", "embed", "mlp"), init="scaled"),
        "down": pdef((E, f, d), ("experts", "mlp", "embed"), init="scaled"),
    }
    if cfg.n_shared_experts:
        out["shared"] = layers.mlp_defs(cfg, cfg.n_shared_experts * cfg.d_ff_expert)
    return out


def _capacity(gs: int, cfg) -> int:
    c = int(np.ceil(gs * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(int(np.ceil(c / 8) * 8), 8)


def _dispatch_group(params, xg: Array, cfg):
    """One token group. xg: (B, gs, d) -> (y (B, gs, d), aux_loss scalar)."""
    B, gs, d = xg.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(gs, cfg)

    logits = (xg @ params["router"].astype(xg.dtype)).astype(jnp.float32)  # (B,gs,E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)  # (B, gs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)  # renormalize top-k

    # Switch-style load-balance loss: E * sum_e f_e * P_e.
    me = probs.mean((0, 1))
    ce = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(2).mean((0, 1))
    aux = E * (me * ce).sum()

    # ---- rank of each (token, choice) within its expert ------------------
    flat_e = idx.reshape(B, gs * k)  # (B, T') expert id per assignment
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (B, T', E)
    rank = jnp.cumsum(onehot, axis=1) - 1  # (B, T', E)
    rank_of = jnp.take_along_axis(rank, flat_e[..., None], axis=2)[..., 0]  # (B, T')
    keep = rank_of < C  # dropped assignments beyond capacity

    ee = jnp.where(keep, flat_e, E)  # E -> OOB -> dropped by scatter
    cc = jnp.clip(rank_of, 0, C - 1)
    tok = jnp.broadcast_to(jnp.arange(gs)[:, None], (gs, k)).reshape(gs * k)

    # ---- dispatch: (B, E, C, d), replicated over "model", sliced by XLA --
    def scatter_one(xb, eb, cb):
        buf = jnp.zeros((E + 1, C, d), xb.dtype)
        buf = buf.at[eb, cb].add(xb[tok], mode="drop")
        return buf[:E]

    buf = jax.vmap(scatter_one)(xg, ee, cc)  # (B, E, C, d)
    buf = shard_act(buf, ("act_batch", "act_model", None, None))

    # ---- expert FFN (E sharded over "model") ------------------------------
    g = jnp.einsum("becd,edf->becf", buf, params["gate"].astype(buf.dtype))
    u = jnp.einsum("becd,edf->becf", buf, params["up"].astype(buf.dtype))
    h = jax.nn.silu(g) * u
    o = jnp.einsum("becf,efd->becd", h, params["down"].astype(buf.dtype))
    o = shard_act(o, ("act_batch", "act_model", None, None))

    # ---- combine: weighted scatter-add back to token order ----------------
    def combine_one(ob, eb, cb, wb):
        gathered = ob[jnp.clip(eb, 0, E - 1), cb]  # (T', d)
        gathered = jnp.where((eb < E)[:, None], gathered, 0.0)
        y = jnp.zeros((gs, d), ob.dtype)
        return y.at[tok].add(gathered * wb.reshape(gs * k)[:, None].astype(ob.dtype))

    y = jax.vmap(combine_one)(o, ee, cc, w)  # (B, gs, d)

    if cfg.n_shared_experts:
        y = y + layers.mlp(params["shared"], xg, "swiglu")
    return y.astype(xg.dtype), aux


def _moe_groups(params, x, cfg, group_size, dispatch_fn):
    B, S, d = x.shape
    gs = min(group_size, S)
    assert S % gs == 0, (S, gs)
    nG = S // gs
    if nG == 1:
        return dispatch_fn(params, x, cfg)
    xr = x.reshape(B, nG, gs, d)

    def step(aux, g):
        y, a = dispatch_fn(params, xr[:, g], cfg)
        return aux + a, y

    aux, ys = jax.lax.scan(step, jnp.float32(0.0), jnp.arange(nG))
    return jnp.moveaxis(ys, 0, 1).reshape(B, S, d), aux / nG


def _dispatch_group_ep(params, xg: Array, cfg, e_offset, n_local: int):
    """Expert-parallel variant of _dispatch_group: this rank owns experts
    [e_offset, e_offset + n_local); routing is computed redundantly
    (replicated activations), non-local assignments are dropped into the
    scatter's OOB bucket, and the partial combine is psum'd by the caller.
    Identical math to the local path when summed over ranks."""
    B, gs, d = xg.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(gs, cfg)

    logits = (xg @ params["router"].astype(xg.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    me = probs.mean((0, 1))
    ce = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(2).mean((0, 1))
    aux = E * (me * ce).sum()

    flat_e = idx.reshape(B, gs * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=1) - 1
    rank_of = jnp.take_along_axis(rank, flat_e[..., None], axis=2)[..., 0]
    local = (flat_e >= e_offset) & (flat_e < e_offset + n_local)
    keep = local & (rank_of < C)

    ee = jnp.where(keep, flat_e - e_offset, n_local)  # OOB -> dropped
    cc = jnp.clip(rank_of, 0, C - 1)
    tok = jnp.broadcast_to(jnp.arange(gs)[:, None], (gs, k)).reshape(gs * k)

    def scatter_one(xb, eb, cb):
        buf = jnp.zeros((n_local + 1, C, d), xb.dtype)
        return buf.at[eb, cb].add(xb[tok], mode="drop")[:n_local]

    buf = jax.vmap(scatter_one)(xg, ee, cc)  # (B, n_local, C, d)

    g = jnp.einsum("becd,edf->becf", buf, params["gate"].astype(buf.dtype))
    u = jnp.einsum("becd,edf->becf", buf, params["up"].astype(buf.dtype))
    h = jax.nn.silu(g) * u
    o = jnp.einsum("becf,efd->becd", h, params["down"].astype(buf.dtype))

    def combine_one(ob, eb, cb, wb):
        gathered = ob[jnp.clip(eb, 0, n_local - 1), cb]
        gathered = jnp.where((eb < n_local)[:, None], gathered, 0.0)
        y = jnp.zeros((gs, d), ob.dtype)
        return y.at[tok].add(gathered * wb.reshape(gs * k)[:, None].astype(ob.dtype))

    y = jax.vmap(combine_one)(o, ee, cc, w)  # partial: local experts only
    return y.astype(xg.dtype), aux


def moe_block(params: dict, x: Array, cfg, group_size: int = 2048):
    """MoE FFN over (B, S, d). Returns (y, aux_loss).

    Under a mesh with a "model" axis that divides n_experts, dispatch runs
    as an explicit shard_map expert-parallel block (hillclimb H2,
    EXPERIMENTS.md §Perf): activations are replicated across "model"
    (Megatron convention), each rank routes all tokens but computes only its
    expert slice, and ONE psum combines — the same wire cost as a dense TP
    block. Left to SPMD propagation, the combine's gather-from-E-sharded
    forced involuntary full rematerialization (XLA warning) and a ~300x
    collective blow-up.
    """
    mesh = current_mesh()
    mdl = mesh is not None and "model" in mesh.axis_names
    # Under the FSDP profile "model" carries batch (act_model rule is None):
    # activations are NOT replicated across it, so the EP shard_map contract
    # doesn't hold — take the local path (experts FSDP'd like any weight).
    mdl = mdl and current_act_rules().get("act_model") is not None
    if mdl and cfg.n_experts % mesh.shape["model"] == 0 and cfg.n_experts >= mesh.shape["model"]:
        n_model = mesh.shape["model"]
        n_local = cfg.n_experts // n_model
        bd = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        bspec = bd if len(bd) > 1 else (bd[0] if bd else None)
        routed = {k_: params[k_] for k_ in ("router", "gate", "up", "down")}

        def body(xb, rp):
            e_off = jax.lax.axis_index("model") * n_local

            def dispatch(pp, xg, cfg_):
                return _dispatch_group_ep(pp, xg, cfg_, e_off, n_local)

            y, aux = _moe_groups(rp, xb, cfg, group_size, dispatch)
            if bd:
                aux = jax.lax.pmean(aux, bd)  # batch is sharded across bd
            return jax.lax.psum(y, "model"), aux

        in_specs = (
            P(bspec, None, None),
            {
                "router": P(None, None),
                "gate": P("model", None, None),
                "up": P("model", None, None),
                "down": P("model", None, None),
            },
        )
        y, aux = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs,
            out_specs=(P(bspec, None, None), P()),
            check_vma=False,
        )(x, routed)
        if cfg.n_shared_experts:
            y = y + layers.mlp(params["shared"], x, "swiglu")
        return y, aux

    return _moe_groups(params, x, cfg, group_size, _dispatch_group)
