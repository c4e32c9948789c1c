"""Sequence-parallel RWKV-6 layer stack (§Perf hillclimb — beyond paper).

Motivation: with the stock layout (batch on `data`, d_model on `model`) the
partitioner re-gathers the (B, T, d) residual stream for every token-shift
projection — ~6 × 1 GiB per layer at prefill_32k. Linear-attention recurrence
makes a better decomposition possible: shard the TIME axis over `model`.
Then every projection, norm, lerp and the intra-shard WKV recurrence is
device-local, and the only cross-device traffic per layer is

  * FSDP-style weight all-gathers (the weights are small: ~450 MB/layer),
  * a 1-token boundary exchange for token-shift (ppermute),
  * a log2(tp)-round associative PREFIX SCAN of the (decay, state) pair —
    the WKV recurrence `S' = diag(D)·S + K` is an affine map, and affine
    maps compose associatively: (D2,K2)∘(D1,K1) = (D2·D1, D2·K1+K2).
    This is the linear-attention analogue of flash-decoding's split-K.

Used for train/prefill (T > 1, fresh state); decode keeps the stock path.
Exactness vs the sequential stack is tested in tests/test_rwkv_sp.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from .layers import rms_norm
from .rwkv6 import LORA_R, RWKVState, _wkv_chunked

__all__ = ["rwkv_stack_sp", "sp_param_specs"]


def sp_param_specs(specs_tree):
    """in_specs for the stacked layer params: exactly their storage specs."""
    return specs_tree


def _gather_full(x, spec):
    """Reassemble a full parameter from its shard inside shard_map."""
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            x = jax.lax.all_gather(x, a, axis=dim, tiled=True)
    return x


def _shift_from_left(x_l, axis_name: str):
    """prev-token sequence for a T-sharded (B, T_l, d) block: within-shard
    shift + the previous rank's last token via ppermute (rank 0 gets zeros,
    which is the sequence-start convention)."""
    tp = jax.lax.axis_size(axis_name)
    boundary = jax.lax.ppermute(x_l[:, -1:], axis_name,
                                perm=[(i, i + 1) for i in range(tp - 1)])
    return jnp.concatenate([boundary, x_l[:, :-1]], axis=1)


def _state_prefix_scan(D, K, axis_name: str):
    """Exclusive prefix scan of affine maps (D, K) over the sequence shards.
    D: (B, H, N) total decay of the shard; K: (B, H, N, N) state injected by
    the shard. Returns each rank's incoming state (zeros at rank 0).
    Hillis–Steele doubling: log2(tp) ppermute rounds."""
    tp = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    step = 1
    while step < tp:
        perm = [(i, i + step) for i in range(tp - step)]
        Dr = jax.lax.ppermute(D, axis_name, perm=perm)
        Kr = jax.lax.ppermute(K, axis_name, perm=perm)
        has = rank >= step           # ranks with an incoming partner
        # compose: earlier (Dr, Kr) then current (D, K)
        D, K = (jnp.where(has, D * Dr, D),
                jnp.where(has, D[..., None] * Kr + K, K))
        step *= 2
    # exclusive: shift the inclusive scan right by one rank
    s_in = jax.lax.ppermute(K, axis_name,
                            perm=[(i, i + 1) for i in range(tp - 1)])
    return s_in


def _time_mix_sp(p, x_l, *, cfg: ModelConfig, chunk: int, axis_name: str):
    B, Tl, d = x_l.shape
    n = cfg.rwkv_head_dim
    h = d // n
    dt = x_l.dtype

    prev = _shift_from_left(x_l, axis_name)
    mu = p["mu"].astype(dt)
    xr, xk, xv, xg, xw = (x_l + (prev - x_l) * mu[i] for i in range(5))

    r = jnp.einsum("btd,de->bte", xr, p["w_r"]).reshape(B, Tl, h, n)
    k = jnp.einsum("btd,de->bte", xk, p["w_k"]).reshape(B, Tl, h, n)
    v = jnp.einsum("btd,de->bte", xv, p["w_v"]).reshape(B, Tl, h, n)
    g = jax.nn.silu(jnp.einsum("btd,de->bte", xg, p["w_g"]))

    lora = jnp.einsum("btd,dr,re->bte", jnp.tanh(xw.astype(jnp.float32)),
                      p["decay_a"].astype(jnp.float32),
                      p["decay_b"].astype(jnp.float32))
    logw = -jnp.exp(p["decay0"] + lora).reshape(B, Tl, h, n)   # log decay ≤ 0
    w = jnp.exp(logw)
    u = p["bonus"].reshape(h, n)

    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    s0 = jnp.zeros((B, h, n, n), jnp.float32)
    y0, s_loc = _wkv_chunked(rf, kf, vf, w, u, s0, min(chunk, Tl))

    # cross-shard recurrence: affine-map prefix scan
    cum = jnp.cumsum(logw, axis=1)                       # (B,Tl,h,n)
    D_tot = jnp.exp(cum[:, -1])                          # (B,h,n)
    s_in = _state_prefix_scan(D_tot, s_loc, axis_name)
    excl = cum - logw                                    # exclusive cumsum
    r_dec = rf * jnp.exp(jnp.clip(excl, -80.0, 0.0))
    y = y0 + jnp.einsum("blhi,bhij->blhj", r_dec, s_in)

    y = (y.reshape(B, Tl, d).astype(dt)) * g
    out = jnp.einsum("btd,de->bte", y, p["w_o"])
    # global final state (for the prefill cache): lives on the last rank
    tp = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    s_fin = D_tot[..., None] * s_in + s_loc
    s_fin = jax.lax.psum(jnp.where(rank == tp - 1, s_fin, 0.0), axis_name)
    return out, s_fin


def _channel_mix_sp(p, x_l, *, axis_name: str):
    prev = _shift_from_left(x_l, axis_name)
    mu = p["mu"].astype(x_l.dtype)
    xk = x_l + (prev - x_l) * mu[0]
    xr = x_l + (prev - x_l) * mu[1]
    k = jnp.einsum("btd,df->btf", xk, p["w_k"])
    kk = jnp.square(jax.nn.relu(k))
    v = jnp.einsum("btf,fd->btd", kk, p["w_v"])
    r = jax.nn.sigmoid(jnp.einsum("btd,de->bte", xr, p["w_r"]))
    return r * v


def rwkv_stack_sp(params_stacked, specs_stacked, x, *, cfg: ModelConfig,
                  mesh, chunk: int, batch_axes=("data",), remat: bool = True,
                  seq_axis: str = "model", want_cache: bool = False):
    """Run the whole RWKV layer stack sequence-parallel.

    x: (B, T, d) global, batch sharded over `batch_axes`; T must divide by
    the `seq_axis` extent. Fresh state only (train / first prefill).
    Returns (x_out, per-layer RWKVState stacked or None).
    """
    tp = mesh.shape[seq_axis]
    layer_specs = jax.tree.map(
        lambda s: P(*s), specs_stacked,
        is_leaf=lambda s: isinstance(s, P))
    x_spec = P(batch_axes, seq_axis, None)
    out_state_spec = RWKVState(P(None, batch_axes, None, None, None),
                               P(None, batch_axes, None),
                               P(None, batch_axes, None))

    # per-layer specs with the leading (scanned) layer dim dropped
    spec_leaves = [tuple(s)[1:] for s in jax.tree.leaves(
        specs_stacked, is_leaf=lambda s: isinstance(s, P))]

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(layer_specs, x_spec),
        out_specs=(x_spec, out_state_spec) if want_cache else x_spec,
        check_vma=False)
    def run(params_l, x_l):
        rank = jax.lax.axis_index(seq_axis)

        def layer(x_l, p_shard):
            leaves, tdef = jax.tree.flatten(p_shard)
            p = jax.tree.unflatten(tdef, [
                _gather_full(a, s) for a, s in zip(leaves, spec_leaves)])
            xn1 = rms_norm(p["ln1"], x_l, cfg.norm_eps)
            h, s_fin = _time_mix_sp(p["time"], xn1, cfg=cfg, chunk=chunk,
                                    axis_name=seq_axis)
            x1 = x_l + h
            xn2 = rms_norm(p["ln2"], x1, cfg.norm_eps)
            x2 = x1 + _channel_mix_sp(p["channel"], xn2, axis_name=seq_axis)
            if want_cache:
                # cache stores the NORMED last token of each mix input
                last = jax.lax.psum(
                    jnp.where(rank == tp - 1, xn1[:, -1], 0.0), seq_axis)
                last2 = jax.lax.psum(
                    jnp.where(rank == tp - 1, xn2[:, -1], 0.0), seq_axis)
                st = RWKVState(s_fin, last.astype(x_l.dtype),
                               last2.astype(x_l.dtype))
            else:
                st = 0.0
            return x2, st

        body = jax.checkpoint(layer) if remat else layer
        x_l, states = jax.lax.scan(body, x_l, params_l)
        return (x_l, states) if want_cache else x_l

    return run(params_stacked, x)
