"""Mixture-of-Experts layer with capacity-based, gather-only dispatch.

Expert parallelism: the expert axis is sharded on 'model'. Dispatch is
formulated entirely with sorts + gathers (no scatter), which GSPMD lowers
to an all-to-all between the token (data) and expert (model) shardings:

  1. top-k routing per token,
  2. stable argsort of the (N*k,) expert assignments,
  3. each expert slot (e, c) *gathers* the c-th token routed to expert e
     (tokens past the capacity C are dropped — 'dropping' implementation),
  4. batched per-expert FFN: einsum over the sharded expert axis,
  5. each (token, k) pair gathers its result back and scales by its gate.

FLOPs are exactly (active experts x capacity_factor), so cost_analysis in
the dry-run reflects the MoE compute honestly.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp

from repro.nn import init as inits
from repro.nn.mlp import ACTS
from repro.sharding.ctx import constrain


def init_moe(mk, cfg, name="moe"):
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    p = {
        "router": mk(f"{name}.router", (d, e), ("embed", "experts"),
                     inits.fan_in(), dtype=jnp.float32),
        "wi": mk(f"{name}.wi", (e, d, f), ("experts", "embed", "expert_mlp"),
                 inits.fan_in(in_axes=(1,))),
        "wg": mk(f"{name}.wg", (e, d, f), ("experts", "embed", "expert_mlp"),
                 inits.fan_in(in_axes=(1,))),
        "wo": mk(f"{name}.wo", (e, f, d), ("experts", "expert_mlp", "embed"),
                 inits.fan_in(in_axes=(1,))),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared_wi"] = mk(f"{name}.shared_wi", (d, fs), ("embed", "mlp"), inits.fan_in())
        p["shared_wg"] = mk(f"{name}.shared_wg", (d, fs), ("embed", "mlp"), inits.fan_in())
        p["shared_wo"] = mk(f"{name}.shared_wo", (fs, d), ("mlp", "embed"), inits.fan_in())
    if cfg.router_score == "sigmoid":
        # DeepSeek-V3 aux-loss-free balancing: a non-gradient bias only used
        # for ranking. Updated outside the gradient path (see optim docs).
        p["router_bias"] = mk(f"{name}.router_bias", (e,), ("experts",),
                              inits.zeros, dtype=jnp.float32)
    return p


def route(cfg, p, xf):
    """xf (N, d) fp32 -> gates (N, k), idx (N, k), aux_loss scalar."""
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    logits = xf @ p["router"]                                  # (N, E) fp32
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        ranked = scores + p["router_bias"]
        _, idx = jax.lax.top_k(ranked, k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        probs = scores / (scores.sum(-1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    # Switch-style load-balancing auxiliary loss.
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)  # (N, E)
    frac = onehot.mean(0)                                      # fraction per expert
    prob = probs.mean(0)
    aux = e * jnp.sum(frac * prob) * (1.0 / k)
    return gates, idx, aux


def _local_dispatch_ffn(cfg, p_local, xflat, gates, idx, e0, e_local, cap,
                        act, dt):
    """Capacity dispatch + FFN for the experts [e0, e0+e_local) owned by
    this shard, over the local tokens. Pure local compute (no collectives);
    returns the partial output (n, d) — summed over shards by the caller."""
    n = xflat.shape[0]
    k = cfg.num_experts_per_tok
    local_idx = idx - e0                                      # (n, k)
    mine = (local_idx >= 0) & (local_idx < e_local)
    flat_expert = jnp.where(mine, local_idx, e_local).reshape(-1)  # e_local = drop
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    start = jnp.searchsorted(sorted_expert, jnp.arange(e_local))
    end = jnp.searchsorted(sorted_expert, jnp.arange(e_local), side="right")
    pos_sorted = jnp.arange(n * k) - start[sorted_expert.clip(0, e_local - 1)]

    slot_e = jnp.repeat(jnp.arange(e_local), cap)
    slot_c = jnp.tile(jnp.arange(cap), e_local)
    sorted_idx = start[slot_e] + slot_c
    valid = sorted_idx < end[slot_e]
    sorted_idx = jnp.minimum(sorted_idx, n * k - 1)
    slot_token = order[sorted_idx] // k
    xb = (xflat[slot_token] * valid[:, None].astype(dt)).reshape(e_local, cap, -1)

    h = jnp.einsum("ecd,edf->ecf", xb, p_local["wi"].astype(dt))
    h = ACTS[act](h) * jnp.einsum("ecd,edf->ecf", xb, p_local["wg"].astype(dt))
    y = jnp.einsum("ecf,efd->ecd", h, p_local["wo"].astype(dt))
    y = y.reshape(e_local * cap, -1)

    inv = jnp.argsort(order, stable=True)
    pos_k = pos_sorted[inv]
    keep = ((pos_k < cap) & mine.reshape(-1)).astype(dt)
    slot_of = jnp.clip(flat_expert * cap + pos_k, 0, e_local * cap - 1)
    yk = y[slot_of] * keep[:, None]
    return jnp.sum(yk.reshape(n, k, -1) * gates.reshape(n, k, 1).astype(dt), axis=1)


def moe_ep(cfg, p, x, act="silu"):
    """Expert-parallel MoE via shard_map.

    Activations are sharded over the data axes and REPLICATED over 'model';
    experts are sharded over 'model'. Each shard routes its local tokens,
    dispatches (locally, gather-only) to its own expert slice, and the
    partial outputs are combined with ONE psum over 'model' — the same
    volume as a Megatron TP all-reduce, instead of the GSPMD gather
    lowering of the naive dispatch (which all-gathers the token buffer per
    expert shard: ~28x more bytes at qwen3-moe train_4k scale).
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding.ctx import current

    mesh, rules = current()
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # EP axes come from the 'experts' rule: ('model',) for training;
    # ('model','data') for serving ('full EP': one expert slice per chip, so
    # expert weights never move — only the tiny token batch does).
    ep_axes, _tot = [], 1
    for a in rules.get("experts", ("model",)):
        if a in mesh.axis_names and cfg.num_experts % (_tot * mesh.shape[a]) == 0:
            ep_axes.append(a)
            _tot *= mesh.shape[a]
    ep_axes = tuple(ep_axes) or ("model",)
    gather_axes = tuple(a for a in ep_axes if a in dp_axes)
    b, s, d = x.shape
    e = cfg.num_experts
    ep_size = 1
    for a in ep_axes:
        ep_size *= mesh.shape[a]
    e_local = e // ep_size
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    gather_size = 1
    for a in gather_axes:
        gather_size *= mesh.shape[a]
    n_local = (b * s) // dp_size
    n_routed = n_local * gather_size
    cap = int(math.ceil(n_routed * cfg.num_experts_per_tok / e
                        * cfg.capacity_factor))
    dt = x.dtype

    x_spec = P(dp_axes if dp_axes else None, None, None)
    w_spec = {
        "router": P(None, None),                 # gathered: routing is global
        "wi": P(ep_axes, None, None),            # expert slice per shard
        "wg": P(ep_axes, None, None),
        "wo": P(ep_axes, None, None),
    }
    if "shared_wi" in p:
        w_spec["shared_wi"] = P(None, "model")   # column-parallel
        w_spec["shared_wg"] = P(None, "model")
        w_spec["shared_wo"] = P("model", None)   # row-parallel -> psum
    if "router_bias" in p:
        w_spec["router_bias"] = P(None)

    def body(p_l, x_l):
        bl, sl, _ = x_l.shape
        xflat = x_l.reshape(bl * sl, d)
        x_routed = xflat
        if gather_axes:
            x_routed = jax.lax.all_gather(xflat, gather_axes, axis=0,
                                          tiled=True)
        gates, idx, aux = route(cfg, p_l, x_routed.astype(jnp.float32))
        rank = jnp.zeros((), jnp.int32)
        for a in ep_axes:
            rank = rank * mesh.shape[a] + jax.lax.axis_index(a)
        y = _local_dispatch_ffn(cfg, p_l, x_routed, gates, idx,
                                rank * e_local, e_local, cap, act, dt)
        sh = None
        if cfg.n_shared_experts:                 # on LOCAL tokens, TP over model
            hs = ACTS[act](xflat @ p_l["shared_wi"].astype(dt)) * \
                (xflat @ p_l["shared_wg"].astype(dt))
            sh = hs @ p_l["shared_wo"].astype(dt)
        if gather_axes:
            y = jax.lax.psum(y, ep_axes)
            gidx = jnp.zeros((), jnp.int32)      # keep my token slice
            for a in gather_axes:
                gidx = gidx * mesh.shape[a] + jax.lax.axis_index(a)
            y = jax.lax.dynamic_slice_in_dim(y, gidx * n_local, n_local, 0)
            if sh is not None:
                y = y + jax.lax.psum(sh, "model")
        else:
            y = jax.lax.psum(y + sh if sh is not None else y, ep_axes)
        aux = jax.lax.pmean(aux, ep_axes + tuple(a for a in dp_axes
                                                 if a not in ep_axes))
        return y.reshape(bl, sl, d), aux

    # replication checking is off: `body` produces per-shard partial sums
    # that only the trailing psum replicates
    fn = jax.shard_map(body, mesh=mesh, in_specs=(w_spec, x_spec),
                       out_specs=(x_spec, P()), check_vma=False)
    return fn(p, x)


def moe(cfg, p, x, act="silu"):
    """x (B,S,d) -> (y (B,S,d), aux loss). Dispatches to the shard_map EP
    implementation when a mesh is active and cfg.moe_impl == 'ep'."""
    from repro.sharding.ctx import current
    if cfg.moe_impl == "ep" and current() is not None:
        return moe_ep(cfg, p, x, act)
    b, s, d = x.shape
    n = b * s
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cap = int(math.ceil(n * k / e * cfg.capacity_factor))
    xflat = x.reshape(n, d)
    gates, idx, aux = route(cfg, p, xflat.astype(jnp.float32))

    flat_expert = idx.reshape(-1)                              # (N*k,)
    order = jnp.argsort(flat_expert, stable=True)              # (N*k,)
    sorted_expert = flat_expert[order]
    start = jnp.searchsorted(sorted_expert, jnp.arange(e))     # (E,)
    end = jnp.searchsorted(sorted_expert, jnp.arange(e), side="right")
    pos_sorted = jnp.arange(n * k) - start[sorted_expert]      # rank within expert

    # --- dispatch: slot (e, c) gathers its token (gather-only) ---
    slot_e = jnp.repeat(jnp.arange(e), cap)                    # (E*C,)
    slot_c = jnp.tile(jnp.arange(cap), e)
    sorted_idx = start[slot_e] + slot_c
    valid = sorted_idx < end[slot_e]
    sorted_idx = jnp.minimum(sorted_idx, n * k - 1)
    slot_token = order[sorted_idx] // k                        # (E*C,)
    xb = xflat[slot_token] * valid[:, None].astype(x.dtype)
    xb = constrain(xb.reshape(e, cap, d), "act_experts", None, None)

    # --- per-expert FFN (expert axis sharded on 'model') ---
    dt = x.dtype
    h = jnp.einsum("ecd,edf->ecf", xb, p["wi"].astype(dt))
    h = ACTS[act](h) * jnp.einsum("ecd,edf->ecf", xb, p["wg"].astype(dt))
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))
    y = constrain(y, "act_experts", None, None).reshape(e * cap, d)

    # --- combine: each (token, k) gathers its slot ---
    inv = jnp.argsort(order, stable=True)                      # flat -> sorted pos
    pos_k = pos_sorted[inv]                                    # (N*k,)
    keep = (pos_k < cap).astype(dt)
    slot_of = jnp.minimum(flat_expert * cap + pos_k, e * cap - 1)
    yk = y[slot_of] * keep[:, None]                            # (N*k, d)
    out = jnp.sum(yk.reshape(n, k, d) * gates[..., None].astype(dt), axis=1)

    if cfg.n_shared_experts:
        hs = ACTS[act](xflat @ p["shared_wi"].astype(dt)) * (xflat @ p["shared_wg"].astype(dt))
        out = out + hs @ p["shared_wo"].astype(dt)
    return out.reshape(b, s, d), aux
