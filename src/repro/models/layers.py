"""Reusable model layers: norms, RoPE, GQA attention, SwiGLU MLP, MoE.

Conventions:
  * params are plain dict pytrees; every init_* takes an rng key,
  * compute dtype is config-driven (bf16 default), params stored in the
    param dtype (bf16) with fp32 master copies living in the optimizer,
  * layer stacks are built with jax.vmap(init) and applied with lax.scan —
    O(1) HLO size in depth (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Logical sharding axis names (resolved by distributed/sharding.py)
AX_BATCH = "batch"
AX_SEQ = "seq"
AX_HEADS = "heads"
AX_KV = "kv_heads"
AX_EMBED = "embed"
AX_MLP = "mlp"
AX_VOCAB = "vocab"
AX_EXPERT = "expert"


def _norm_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype) -> Dict[str, Any]:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0):
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    return inv  # [half]


def apply_rope(x, positions, theta: float = 10000.0):
    """x: [..., seq, heads, head_dim]; positions: broadcastable [..., seq]."""
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta)
    ang = positions[..., :, None].astype(jnp.float32) * inv[None, :]  # [.., seq, half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., :, None, :]  # broadcast over heads
    cos = cos[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qkv_bias: bool, dtype) -> Dict[str, Any]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d_model)
    p = {
        "wq": _norm_init(k1, (d_model, n_heads, head_dim), s, dtype),
        "wk": _norm_init(k2, (d_model, n_kv, head_dim), s, dtype),
        "wv": _norm_init(k3, (d_model, n_kv, head_dim), s, dtype),
        "wo": _norm_init(k4, (n_heads, head_dim, d_model),
                         1.0 / math.sqrt(n_heads * head_dim), dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((n_kv, head_dim), dtype)
        p["bv"] = jnp.zeros((n_kv, head_dim), dtype)
    return p


def _qkv(params, x, positions, rope_theta):
    q = jnp.einsum("bld,dhk->blhk", x, params["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, params["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def causal_attention(params, x, positions, rope_theta: float = 10000.0,
                     q_chunk: int = 0, score_shard=None) -> jax.Array:
    """Full causal self-attention (train / prefill). positions: [l] int32.

    GQA is computed in grouped form — q reshaped to [b, l, kv, rep, hd] and
    contracted against kv-sized k/v directly, so k/v are NEVER materialized
    at n_heads width (repeat_kv would cost n_rep× memory AND bandwidth).

    ``q_chunk`` > 0 activates query-chunked attention (lax.scan over query
    blocks): O(q_chunk · L) score memory instead of O(L²) — the memory lever
    for 32k prefill.

    ``score_shard=(batch_axes, key_axis)`` pins the score tensor's key dim to
    ``key_axis`` (context-parallel attention): when the head count doesn't
    divide the model axis (llava's 56, qwen2's 12), GSPMD would otherwise
    replicate the [b, h, q, l] scores — the softmax runs on sharded stripes
    with all-reduced max/sum instead.
    """
    b, l, _ = x.shape
    q, k, v = _qkv(params, x, positions[None, :], rope_theta)
    n_heads, n_kv = q.shape[2], k.shape[2]
    n_rep = n_heads // n_kv
    hd = q.shape[-1]
    qg = q.reshape(b, l, n_kv, n_rep, hd)
    scale = 1.0 / math.sqrt(hd)

    def attend(qi, qpi):
        # qi: [b, qc, kv, rep, hd]; scores [b, kv, rep, qc, l]
        s = jnp.einsum("bqgrk,blgk->bgrql", qi, k) * scale
        if score_shard is not None:
            from jax.sharding import PartitionSpec as P
            s = jax.lax.with_sharding_constraint(
                s, P(score_shard[0], None, None, None, score_shard[1]))
        mask = qpi[:, None] >= positions[None, :]  # [qc, l]
        s = jnp.where(mask[None, None, None], s.astype(jnp.float32), -jnp.inf)
        a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return jnp.einsum("bgrql,blgk->bqgrk", a, v)

    if q_chunk and l > q_chunk and l % q_chunk == 0:
        nchunks = l // q_chunk
        qc = jnp.moveaxis(qg.reshape(b, nchunks, q_chunk, n_kv, n_rep, hd), 1, 0)
        qp = positions.reshape(nchunks, q_chunk)

        # checkpoint the chunk body: without it the chunk-scan's backward
        # stacks every chunk's softmax residuals — the full O(L^2) scores
        # reappear and q-chunking saves nothing at train time
        @jax.checkpoint
        def chunk_body(carry, inp):
            qi, qpi = inp
            return carry, attend(qi, qpi)

        _, o = lax.scan(chunk_body, 0, (qc, qp))
        o = jnp.moveaxis(o, 0, 1).reshape(b, l, n_heads, hd)
    else:
        o = attend(qg, positions).reshape(b, l, n_heads, hd)

    return jnp.einsum("bqhk,hkd->bqd", o, params["wo"])


def attention_decode(params, x, cache_k, cache_v, pos, rope_theta: float = 10000.0):
    """One-token decode against a KV cache.

    x: [b, 1, d]; cache_k/v: [b, S, n_kv, hd]; pos: scalar current position.
    Returns (out [b,1,d], new_k, new_v).
    """
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q = jnp.einsum("bld,dhk->blhk", x, params["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, params["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, params["wv"])
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    cache_k = lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                       (0, pos, 0, 0))
    cache_v = lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                       (0, pos, 0, 0))
    n_heads, n_kv = q.shape[2], cache_k.shape[2]
    n_rep = n_heads // n_kv
    S = cache_k.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    # grouped attention without materializing repeated KV: fold rep into heads
    qg = q.reshape(b, 1, n_kv, n_rep, -1)
    s = jnp.einsum("bqgrk,bsgk->bgrqs", qg, cache_k) * scale
    valid = jnp.arange(S)[None, None, None, None, :] <= pos
    s = jnp.where(valid, s.astype(jnp.float32), -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bgrqs,bsgk->bqgrk", a, cache_v)
    o = o.reshape(b, 1, n_heads, -1)
    out = jnp.einsum("bqhk,hkd->bqd", o, params["wo"])
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "w_gate": _norm_init(k1, (d_model, d_ff), s_in, dtype),
        "w_up": _norm_init(k2, (d_model, d_ff), s_in, dtype),
        "w_down": _norm_init(k3, (d_ff, d_model), s_out, dtype),
    }


def mlp(params, x):
    g = jnp.einsum("bld,df->blf", x, params["w_gate"])
    u = jnp.einsum("bld,df->blf", x, params["w_up"])
    return jnp.einsum("blf,fd->bld", jax.nn.silu(g) * u, params["w_down"])


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k routing, capacity dispatch)
# ---------------------------------------------------------------------------


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype,
             n_padded: int = 0) -> Dict[str, Any]:
    """``n_padded`` >= n_experts pads the expert stacks with phantom
    zero-weight experts (EP divisibility, like vocab padding — granite's 40
    experts pad to 48 on a 16-way model axis). The router stays at
    n_experts, so phantom experts are never routed to and their (zero)
    weights receive exactly zero gradient."""
    n_padded = max(n_padded, n_experts)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)

    def padded(k, shape, scale):
        w = _norm_init(k, (n_experts,) + shape[1:], scale, dtype)
        if n_padded == n_experts:
            return w
        return jnp.concatenate(
            [w, jnp.zeros((n_padded - n_experts,) + shape[1:], dtype)], 0)

    return {
        "router": _norm_init(k1, (d_model, n_experts), s_in, jnp.float32),
        "w_gate": padded(k2, (n_padded, d_model, d_ff), s_in),
        "w_up": padded(k3, (n_padded, d_model, d_ff), s_in),
        "w_down": padded(k4, (n_padded, d_ff, d_model), s_out),
    }


def _expert_rank(flat_expert: jax.Array) -> jax.Array:
    """Per-group rank of each (token,k) within its expert queue, via sort.

    flat_expert: [g, n] expert ids. Returns [g, n] exclusive rank among equal
    ids. Sort-based (2 argsorts + a max-scan) — O(n log n) work and O(n)
    memory, never materializing the [n, E] one-hot that makes the naive
    cumsum ranking blow up at 128 experts × 1M tokens.
    """
    g, n = flat_expert.shape
    order = jnp.argsort(flat_expert, axis=1, stable=True)  # [g, n]
    sorted_e = jnp.take_along_axis(flat_expert, order, axis=1)
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (g, n))
    change = jnp.concatenate(
        [jnp.ones((g, 1), jnp.bool_), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    run_start = jnp.where(change, idx, 0)
    run_start = lax.associative_scan(jnp.maximum, run_start, axis=1)
    rank_sorted = idx - run_start
    inv = jnp.argsort(order, axis=1)
    return jnp.take_along_axis(rank_sorted, inv, axis=1)


def _dispatch_combine_local(x, slot, gate, overflow, E, C, d, ffn):
    """Per-group dispatch -> ffn([g, E, C, d]) -> combine. vmapped over the
    group dim so the scatter/gather carry explicit batching dims (GSPMD
    shards those; flat-index formulations get replicated)."""
    b, l, _ = x.shape
    n = slot.shape[1]
    token_idx = jnp.repeat(jnp.arange(l, dtype=jnp.int32), n // l)

    def dispatch_one(x_g, slot_g):
        buf = jnp.zeros((overflow + 1, d), x_g.dtype)
        return buf.at[slot_g].set(x_g[token_idx])[:overflow]

    def combine_one(y_exp_g, slot_g, gate_g):
        y_pad = jnp.concatenate(
            [y_exp_g, jnp.zeros((1, d), y_exp_g.dtype)], axis=0)
        gathered = y_pad[slot_g] * gate_g[:, None].astype(y_exp_g.dtype)
        return jnp.zeros((l, d), y_exp_g.dtype).at[token_idx].add(gathered)

    x_disp = jax.vmap(dispatch_one)(x, slot).reshape(b, E, C, d)
    y_exp = ffn(x_disp).reshape(b, E * C, d)
    return jax.vmap(combine_one)(y_exp, slot, gate)


def _moe_mesh(expert_axis, cap_axis):
    """Active ambient mesh + model-axis size, if usable for shard_map: the
    abstract mesh installed by ``jax.sharding.set_mesh``."""
    axis = expert_axis or cap_axis
    if axis is None:
        return None, None, 1
    am = jax.sharding.get_abstract_mesh()
    if am.empty or axis not in am.axis_names:
        return None, None, 1
    return am, axis, am.shape[axis]


def moe(params, x, top_k: int, capacity_factor: float = 1.25,
        group_axes=None, expert_axis=None, cap_axis=None):
    """Grouped top-k MoE with per-expert capacity.

    Three execution paths (DESIGN.md §6):
      * **EP (all-to-all)** — shard_map over the active mesh when n_experts
        divides the model axis: tokens dispatch locally per (batch,
        seq-shard) sub-group, ``all_to_all`` exchanges expert queues so each
        device runs only its E/msz experts, reverse all_to_all + local
        combine. This is the production MoE dataflow; GSPMD cannot derive
        it from a scatter (it replicates the dispatch buffer instead).
      * **expert-TP (partial sums)** — when n_experts doesn't divide
        (granite's 40): every device keeps its d_ff slice of ALL experts,
        computes f-partial outputs for its local tokens, one psum over the
        model axis. No token exchange at all.
      * **local** — no mesh context (CPU smoke tests / 1-device).

    Tokens beyond an expert's capacity are dropped (Switch semantics);
    dropped entries go to a dedicated overflow slot (index E·C) — NOT
    ``(e+1)·C``, which would clobber the next expert's queue head.
    Returns (y, aux_loss).
    """
    b, l, d = x.shape
    n_experts = params["router"].shape[-1]
    d_ff = params["w_gate"].shape[-1]
    # bf16 dot with f32 accumulation: casting x to f32 would materialize an
    # f32 copy of the residual carry, which the layer-scan remat then SAVES
    # per layer ([L, b, l, d] f32 stack — 1.5 GiB/device at qwen3 scale)
    logits = jnp.einsum("bld,de->ble", x, params["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, top_k)  # [b, l, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch): E * Σ_e f_e · P_e
    f_frac = jnp.mean(jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.float32),
                      axis=(0, 1, 2)) * top_k
    aux = n_experts * jnp.sum(f_frac * jnp.mean(probs, axis=(0, 1)))

    e_pad = params["w_gate"].shape[0]  # >= n_experts (phantom experts)
    am, model_axis, msz = _moe_mesh(expert_axis, cap_axis)
    ep = (am is not None and msz > 1 and l % msz == 0
          and (l // msz) * top_k >= 1 and e_pad % msz == 0)
    n_sub = msz if ep else 1  # ranking sub-groups per sequence

    l_sub = l // n_sub
    capacity = max(1, int(l_sub * top_k * capacity_factor / n_experts))
    n = l * top_k
    flat_expert = expert_idx.reshape(b * n_sub, l_sub * top_k)
    my_rank = _expert_rank(flat_expert)
    keep = my_rank < capacity
    overflow = e_pad * capacity  # dedicated drop slot
    slot = jnp.where(keep, flat_expert * capacity + my_rank,
                     overflow).reshape(b, n)
    gate = gate_vals.reshape(b, n).astype(jnp.float32)

    from jax.sharding import PartitionSpec as P

    if ep:
        fsdp0 = "data" if "data" in am.axis_names else None

        def body(x_l, slot_l, gate_l, wg, wu, wd):
            # x_l [b_l, l_sub, d]; w* are this device's expert slices with
            # the FSDP ('data') dim gathered back per layer (ZeRO-3 flow)
            if "data" in am.axis_names:
                wg = lax.all_gather(wg, "data", axis=1, tiled=True)
                wu = lax.all_gather(wu, "data", axis=1, tiled=True)
                wd = lax.all_gather(wd, "data", axis=2, tiled=True)

            def ffn(x_disp):
                # [b_l, E_pad, C, d] -> exchange queues -> local experts
                xd = lax.all_to_all(x_disp, model_axis, split_axis=1,
                                    concat_axis=2, tiled=True)
                g_ = jnp.einsum("becd,edf->becf", xd, wg)
                u = jnp.einsum("becd,edf->becf", xd, wu)
                ye = jnp.einsum("becf,efd->becd", jax.nn.silu(g_) * u, wd)
                return lax.all_to_all(ye, model_axis, split_axis=2,
                                      concat_axis=1, tiled=True)

            y_l = _dispatch_combine_local(
                x_l, slot_l, gate_l, overflow, e_pad, capacity, d, ffn)
            return y_l.astype(x_l.dtype)

        w_specs = (P(model_axis, fsdp0, None), P(model_axis, fsdp0, None),
                   P(model_axis, None, fsdp0))
        sm = jax.shard_map(
            body, mesh=am,
            in_specs=(P(group_axes, model_axis, None),
                      P(group_axes, model_axis), P(group_axes, model_axis))
            + w_specs,
            out_specs=P(group_axes, model_axis, None))
        y = sm(x, slot, gate, params["w_gate"], params["w_up"],
               params["w_down"])
        return y, aux

    # local path (smoke tests / 1 device / decode with tiny buffers)
    def ffn(x_disp):
        if group_axes is not None or expert_axis is not None or cap_axis is not None:
            x_disp = jax.lax.with_sharding_constraint(
                x_disp, P(group_axes, expert_axis, cap_axis, None))
        g_ = jnp.einsum("becd,edf->becf", x_disp, params["w_gate"])
        u = jnp.einsum("becd,edf->becf", x_disp, params["w_up"])
        return jnp.einsum("becf,efd->becd", jax.nn.silu(g_) * u,
                          params["w_down"])

    y = _dispatch_combine_local(x, slot, gate, overflow, e_pad, capacity,
                                d, ffn).astype(x.dtype)
    return y, aux
