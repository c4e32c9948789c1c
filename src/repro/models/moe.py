"""Mixture-of-Experts FFN: top-k softmax router, dropless-style scatter
dispatch into per-expert capacity buffers, expert-parallel (experts sharded
over `model`). Expert count is padded up to a multiple of the model-axis size;
padded experts' router logits are −inf (zero traffic, mathematically inert).

Arctic-style dense residual: an ordinary SwiGLU MLP runs in parallel with the
MoE FFN and its output is added (cfg.moe_dense_residual).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from .layers import ParamDef, constrain

__all__ = ["moe_defs", "moe_apply", "moe_apply_gathered", "moe_apply_ep"]


def moe_defs(cfg: ModelConfig, tp: int, dtype) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    e = cfg.padded_experts(tp)
    defs = {
        "router": ParamDef((d, e), P("data", None), jnp.float32),
        "w_gate": ParamDef((e, d, ff), P("model", "data", None), dtype),
        "w_up": ParamDef((e, d, ff), P("model", "data", None), dtype),
        "w_down": ParamDef((e, ff, d), P("model", None, "data"), dtype),
    }
    if cfg.moe_dense_residual:
        defs["dense"] = {
            "w_gate": ParamDef((d, ff), P("data", "model"), dtype),
            "w_up": ParamDef((d, ff), P("data", "model"), dtype),
            "w_down": ParamDef((ff, d), P("model", "data"), dtype),
        }
    return defs


def moe_apply(params: dict, x: jnp.ndarray, *, cfg: ModelConfig, tp: int,
              batch_axes=("data",)) -> jnp.ndarray:
    """x: (B, T, d) -> (B, T, d)."""
    B, T, d = x.shape
    e_pad = cfg.padded_experts(tp)
    e_real, k = cfg.n_experts, cfg.experts_top_k
    n_tok = B * T
    xt = x.reshape(n_tok, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    logits = jnp.where(jnp.arange(e_pad) < e_real, logits, -jnp.inf)
    top_vals, top_idx = jax.lax.top_k(logits, k)              # (n_tok, k)
    gates = jax.nn.softmax(top_vals, axis=-1)                 # renormalized

    # flatten (token, k) assignments and sort by expert
    expert_id = top_idx.reshape(-1)                           # (n_tok*k,)
    token_id = jnp.repeat(jnp.arange(n_tok), k)
    gate_flat = gates.reshape(-1)
    order = jnp.argsort(expert_id)
    expert_s, token_s, gate_s = expert_id[order], token_id[order], gate_flat[order]

    # capacity buffers: position within expert via exclusive segment offsets
    capacity = max(int(n_tok * k / max(e_real, 1) * cfg.capacity_factor), 8)
    counts = jnp.bincount(expert_id, length=e_pad)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(n_tok * k) - offsets[expert_s]
    keep = pos < capacity
    slot = jnp.where(keep, expert_s * capacity + pos, e_pad * capacity)

    buf = jnp.zeros((e_pad * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(xt[token_s] * keep[:, None].astype(x.dtype))
    buf = buf[:-1].reshape(e_pad, capacity, d)
    buf = constrain(buf, P("model", None, None))

    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    out_buf = constrain(out_buf, P("model", None, None))

    # combine: gather each assignment's output back to its token, weighted
    flat = out_buf.reshape(e_pad * capacity, d)
    contrib = flat[jnp.clip(slot, 0, e_pad * capacity - 1)]
    contrib = contrib * (gate_s * keep)[:, None].astype(x.dtype)
    y = jnp.zeros((n_tok, d), x.dtype).at[token_s].add(contrib)
    y = y.reshape(B, T, d)

    if cfg.moe_dense_residual:
        from .layers import swiglu
        dp = params["dense"]
        y = y + swiglu(dp["w_gate"], dp["w_up"], dp["w_down"], x)
    return constrain(y, P(batch_axes, None, None))


def _dispatch_local(xt, logits, *, e_pad, e_real, k, capacity, dtype):
    """Capacity-buffer dispatch for a LOCAL token shard (runs inside
    shard_map — no cross-device traffic). Returns (buf, combine closure)."""
    n_tok, d = xt.shape
    top_vals, top_idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_vals, axis=-1)
    expert_id = top_idx.reshape(-1)
    token_id = jnp.repeat(jnp.arange(n_tok), k)
    gate_flat = gates.reshape(-1)
    order = jnp.argsort(expert_id)
    expert_s, token_s, gate_s = (expert_id[order], token_id[order],
                                 gate_flat[order])
    counts = jnp.bincount(expert_id, length=e_pad)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(n_tok * k) - offsets[expert_s]
    keep = pos < capacity
    slot = jnp.where(keep, expert_s * capacity + pos, e_pad * capacity)
    buf = jnp.zeros((e_pad * capacity + 1, d), dtype)
    buf = buf.at[slot].set(xt[token_s] * keep[:, None].astype(dtype))
    buf = buf[:-1].reshape(e_pad, capacity, d)

    def combine(out_buf):
        flat = out_buf.reshape(e_pad * capacity, d)
        contrib = flat[jnp.clip(slot, 0, e_pad * capacity - 1)]
        contrib = contrib * (gate_s * keep)[:, None].astype(dtype)
        return jnp.zeros((n_tok, d), dtype).at[token_s].add(contrib)

    return buf, combine


def moe_apply_gathered(params, x, *, cfg: ModelConfig, mesh,
                       batch_axes=("data",), seq_axis: str = "model"):
    """Gathered-experts MoE (§Perf hillclimb — beyond paper).

    The scatter-dispatch path (moe_apply) makes the partitioner all-gather
    the FULL token buffer per layer (~439 s of collective per train step on
    granite-moe). When the per-layer expert weights are small (granite-moe:
    226 MB), the cheaper decomposition is the transpose: shard TOKENS over
    every mesh axis, all-gather the WEIGHTS (FSDP-style), and dispatch
    entirely device-locally — per-layer traffic drops from O(tokens·d) to
    O(expert_weights).

    x: (B, T, d) with batch over `batch_axes`; T divisible by the seq_axis
    extent. Capacity is enforced per token shard (more balanced than global).
    """
    B, T, d = x.shape
    e_pad = cfg.padded_experts(mesh.shape[seq_axis])
    e_real, k = cfg.n_experts, cfg.experts_top_k
    # Under fsdp_only the batch already occupies every axis: tokens are
    # fully local with full d and no transpose is needed. Otherwise x stays
    # in its native (batch, None, d-sharded) layout and the tokens<->features
    # transpose is an EXPLICIT all_to_all inside the region (the partitioner
    # otherwise lowers the boundary reshard as a full all-gather).
    fsdp_only = seq_axis in (batch_axes if isinstance(batch_axes, tuple)
                             else (batch_axes,))
    if fsdp_only:
        # tokens fully sharded with full d: batch over the data axes, T over
        # the model axis (works for any B; needs T % tp == 0)
        data_axes = tuple(a for a in batch_axes if a != seq_axis)
        x_spec = P(data_axes, seq_axis, None)
    else:
        x_spec = P(batch_axes, None, seq_axis)

    w_specs = {
        "router": P("data", None),
        "w_gate": P(seq_axis, "data", None),
        "w_up": P(seq_axis, "data", None),
        "w_down": P(seq_axis, None, "data"),
    }
    if cfg.moe_dense_residual:
        w_specs["dense"] = {"w_gate": P("data", seq_axis),
                            "w_up": P("data", seq_axis),
                            "w_down": P(seq_axis, "data")}
    p_in = {kk: params[kk] for kk in w_specs if kk in params}

    tok_shards = 1
    for a in dict.fromkeys((*batch_axes, seq_axis)):   # de-dup, keep order
        tok_shards *= mesh.shape[a]
    local_tok = (B * T) // tok_shards
    capacity = max(int(local_tok * k / max(e_real, 1) * cfg.capacity_factor),
                   8)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(w_specs, x_spec),
                       out_specs=x_spec, check_vma=False)
    def run(p, x_l):
        full = {}
        for name, spec in w_specs.items():
            if name == "dense":
                continue
            wv = p[name]
            for dim, entry in enumerate(spec):
                if entry is not None:
                    wv = jax.lax.all_gather(wv, entry, axis=dim, tiled=True)
            full[name] = wv
        if not fsdp_only:
            # (B_l, T, d/tp) -> (B_l, T/tp, d): token/feature transpose
            x_l = jax.lax.all_to_all(x_l, seq_axis, split_axis=1,
                                     concat_axis=2, tiled=True)
        Bl, Tl, _ = x_l.shape
        xt = x_l.reshape(Bl * Tl, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            full["router"])
        logits = jnp.where(jnp.arange(e_pad) < e_real, logits, -jnp.inf)
        buf, combine = _dispatch_local(xt, logits, e_pad=e_pad,
                                       e_real=e_real, k=k,
                                       capacity=capacity, dtype=x_l.dtype)
        g = jnp.einsum("ecd,edf->ecf", buf, full["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, full["w_up"])
        out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                             full["w_down"])
        y = combine(out_buf).reshape(Bl, Tl, d)
        if cfg.moe_dense_residual:
            dp = p["dense"]
            wg = jax.lax.all_gather(jax.lax.all_gather(
                dp["w_gate"], "data", axis=0, tiled=True),
                seq_axis, axis=1, tiled=True)
            wu = jax.lax.all_gather(jax.lax.all_gather(
                dp["w_up"], "data", axis=0, tiled=True),
                seq_axis, axis=1, tiled=True)
            wd = jax.lax.all_gather(jax.lax.all_gather(
                dp["w_down"], seq_axis, axis=0, tiled=True),
                "data", axis=1, tiled=True)
            from .layers import swiglu
            y = y + swiglu(wg, wu, wd, x_l)
        if fsdp_only:
            return y
        # back to (B_l, T, d/tp)
        return jax.lax.all_to_all(y, seq_axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    return run(p_in, x)


def moe_apply_ep(params, x, *, cfg: ModelConfig, mesh,
                 batch_axes=("data",), seq_axis: str = "model"):
    """True expert-parallel MoE dispatch (§Perf — beyond paper): experts stay
    RESIDENT (sharded over `seq_axis`), tokens travel.

    For big-expert models (arctic: 26.8 GB of expert weights per layer) the
    gathered-experts path still moves the weights every layer; the cheaper
    direction is the classic EP all-to-all: each device top-k routes its
    local tokens, buckets them by destination rank (expert // e_per_rank),
    exchanges fixed-capacity buffers with one `lax.all_to_all`, runs its OWN
    experts on what arrives, and reverses the exchange. Per-layer traffic is
    O(local_tokens · k · d), independent of expert-weight size.

    x: (B, T, d), batch over `batch_axes`, T divisible by the seq_axis
    extent. Router replicated (gathered once — it is (d, e), tiny).
    """
    B, T, d = x.shape
    tp = mesh.shape[seq_axis]
    e_pad = cfg.padded_experts(tp)
    e_real, k = cfg.n_experts, cfg.experts_top_k
    e_loc = e_pad // tp                      # experts resident per rank
    data_axes = tuple(a for a in batch_axes if a != seq_axis)
    x_spec = P(data_axes, seq_axis, None)

    w_specs = {
        "router": P("data", None),
        "w_gate": P(seq_axis, "data", None),
        "w_up": P(seq_axis, "data", None),
        "w_down": P(seq_axis, None, "data"),
    }
    if cfg.moe_dense_residual:
        w_specs["dense"] = {"w_gate": P("data", seq_axis),
                            "w_up": P("data", seq_axis),
                            "w_down": P(seq_axis, "data")}
    p_in = {kk: params[kk] for kk in w_specs if kk in params}

    tok_shards = 1
    for a in dict.fromkeys((*batch_axes, seq_axis)):
        tok_shards *= mesh.shape[a]
    local_tok = (B * T) // tok_shards
    # per-destination-rank send capacity and per-expert compute capacity
    cap_send = max(int(local_tok * k / tp * cfg.capacity_factor), 8)
    cap_exp = max(int(local_tok * k * tp / max(e_real, 1)
                      * cfg.capacity_factor), 8)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(w_specs, x_spec),
                       out_specs=x_spec, check_vma=False)
    def run(p, x_l):
        # weights: experts resident (dim 0 already sharded over seq_axis);
        # only their FSDP ('data') dim is gathered — e_loc × that slice
        def fsdp(w, dim):
            return jax.lax.all_gather(w, "data", axis=dim, tiled=True)
        w_gate = fsdp(p["w_gate"], 1)
        w_up = fsdp(p["w_up"], 1)
        w_down = fsdp(p["w_down"], 2)
        router = fsdp(p["router"], 0)

        Bl, Tl, _ = x_l.shape
        xt = x_l.reshape(Bl * Tl, d)
        n_tok = xt.shape[0]
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        logits = jnp.where(jnp.arange(e_pad) < e_real, logits, -jnp.inf)
        top_vals, top_idx = jax.lax.top_k(logits, k)          # (n_tok, k)
        gates = jax.nn.softmax(top_vals, axis=-1)

        # ---- bucket assignments by destination rank
        expert_id = top_idx.reshape(-1)                        # (n_tok*k,)
        dest = expert_id // e_loc                              # (n_tok*k,)
        token_id = jnp.repeat(jnp.arange(n_tok), k)
        gate_flat = gates.reshape(-1)
        order = jnp.argsort(dest)
        dest_s, tok_s, gate_s, exp_s = (dest[order], token_id[order],
                                        gate_flat[order], expert_id[order])
        counts = jnp.bincount(dest, length=tp)
        offsets = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                   jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(n_tok * k) - offsets[dest_s]
        keep = pos < cap_send
        slot = jnp.where(keep, dest_s * cap_send + pos, tp * cap_send)

        send_x = jnp.zeros((tp * cap_send + 1, d), x_l.dtype)
        send_x = send_x.at[slot].set(xt[tok_s] * keep[:, None]
                                     .astype(x_l.dtype))[:-1]
        # metadata: local expert id (+1, 0 = invalid) rides along
        send_m = jnp.zeros((tp * cap_send + 1,), jnp.int32)
        send_m = send_m.at[slot].set(
            jnp.where(keep, exp_s % e_loc + 1, 0))[:-1]

        # ---- exchange: (tp, cap, ...) -> rows now indexed by SOURCE rank
        recv_x = jax.lax.all_to_all(send_x.reshape(tp, cap_send, d),
                                    seq_axis, 0, 0, tiled=False)
        recv_m = jax.lax.all_to_all(send_m.reshape(tp, cap_send),
                                    seq_axis, 0, 0, tiled=False)
        rx = recv_x.reshape(tp * cap_send, d)
        rm = recv_m.reshape(tp * cap_send)

        # ---- local dispatch into my experts' capacity buffers
        valid = rm > 0
        my_exp = jnp.where(valid, rm - 1, e_loc)               # e_loc = drop
        order2 = jnp.argsort(my_exp)
        exp2, src2 = my_exp[order2], jnp.arange(tp * cap_send)[order2]
        counts2 = jnp.bincount(my_exp, length=e_loc + 1)
        off2 = jnp.concatenate([jnp.zeros(1, counts2.dtype),
                                jnp.cumsum(counts2)[:-1]])
        pos2 = jnp.arange(tp * cap_send) - off2[exp2]
        keep2 = (pos2 < cap_exp) & (exp2 < e_loc)
        slot2 = jnp.where(keep2, exp2 * cap_exp + pos2, e_loc * cap_exp)

        buf = jnp.zeros((e_loc * cap_exp + 1, d), x_l.dtype)
        buf = buf.at[slot2].set(rx[src2] * keep2[:, None]
                                .astype(x_l.dtype))[:-1]
        buf = buf.reshape(e_loc, cap_exp, d)

        g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
        u = jnp.einsum("ecd,edf->ecf", buf, w_up)
        out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)

        # ---- un-dispatch to recv slots, reverse exchange, combine
        flat = out_buf.reshape(e_loc * cap_exp, d)
        back = jnp.zeros((tp * cap_send, d), x_l.dtype)
        back = back.at[src2].set(
            flat[jnp.clip(slot2, 0, e_loc * cap_exp - 1)]
            * keep2[:, None].astype(x_l.dtype))
        ret = jax.lax.all_to_all(back.reshape(tp, cap_send, d),
                                 seq_axis, 0, 0, tiled=False)
        ret = ret.reshape(tp * cap_send, d)

        contrib = ret[jnp.clip(slot, 0, tp * cap_send - 1)]
        contrib = contrib * (gate_s * keep)[:, None].astype(x_l.dtype)
        y = jnp.zeros((n_tok, d), x_l.dtype).at[tok_s].add(contrib)
        y = y.reshape(Bl, Tl, d)
        if cfg.moe_dense_residual:
            dp = p["dense"]
            wg = jax.lax.all_gather(jax.lax.all_gather(
                dp["w_gate"], "data", axis=0, tiled=True),
                seq_axis, axis=1, tiled=True)
            wu = jax.lax.all_gather(jax.lax.all_gather(
                dp["w_up"], "data", axis=0, tiled=True),
                seq_axis, axis=1, tiled=True)
            wd = jax.lax.all_gather(jax.lax.all_gather(
                dp["w_down"], seq_axis, axis=0, tiled=True),
                "data", axis=1, tiled=True)
            from .layers import swiglu
            y = y + swiglu(wg, wu, wd, x_l)
        return y

    return run(p_in, x)
