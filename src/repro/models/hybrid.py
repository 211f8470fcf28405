"""Zamba2-style hybrid: Mamba2 backbone + a *shared* attention block
[arXiv:2411.15242].

The backbone is ``n_layers`` Mamba2 mixers; one transformer block (GQA
attention + MLP) with a single set of weights is applied every
``cfg.attn_every`` backbone layers (weight re-use is the Zamba2 trick that
keeps the attention parameter cost of a 1.2B model negligible).

Layer schedule (n_layers=38, attn_every=6): segments of 6 mamba layers
separated by applications of the shared block — the segment loop is an
unrolled python loop over ``lax.scan`` segments, keeping HLO size small.

State for serving = per-layer SSM states + ONE KV cache (the shared block
sees the sequence once per application; we cache per application slot).
For simplicity and memory-boundedness, the serve path applies the shared
attention block with a ring/linear cache per slot exactly like the dense
decode path.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T
from repro.sharding.partition import DistContext

PyTree = Any


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def n_segments(cfg: ModelConfig) -> int:
    return -(-cfg.n_layers // cfg.attn_every)


def init_params(rng, cfg: ModelConfig) -> PyTree:
    k_embed, k_layers, k_shared = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    return {
        **L.init_embed(k_embed, cfg, _dtype(cfg)),
        "layers": jax.vmap(lambda k: S.init_layer(k, cfg))(layer_keys),
        "shared": T.init_layer(k_shared, cfg),   # attention + MLP block
        "final_norm": jnp.ones((cfg.d_model,), _dtype(cfg)),
    }


def _segments(cfg: ModelConfig):
    """Static (start, length) list of backbone segments."""
    segs, start = [], 0
    while start < cfg.n_layers:
        ln = min(cfg.attn_every, cfg.n_layers - start)
        segs.append((start, ln))
        start += ln
    return segs


def _slice_layers(layers: PyTree, start: int, length: int) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: jax.lax.slice_in_dim(x, start, start + length, axis=0), layers)


def train_loss(params, batch, cfg: ModelConfig, ctx: DistContext, **_):
    h = L.embed_tokens(batch["tokens"], params, ctx)
    h = ctx.shard(h, "dp", None, None)
    Sq = batch["tokens"].shape[1]
    positions = jnp.arange(Sq)

    layer = S.remat_layer_fwd(cfg)

    def mamba_body(x, lp):
        return ctx.shard(layer(x, lp, cfg, ctx), "dp", ctx.tp, None), None

    shared_call = lambda x: T._layer_fwd(x, params["shared"], cfg, ctx,
                                         positions, window=0, q_chunk=1024,
                                         kv_chunk=1024)
    if cfg.remat:
        shared_call = jax.checkpoint(
            shared_call, policy=jax.checkpoint_policies.nothing_saveable)
    for (start, length) in _segments(cfg):
        h, _ = jax.lax.scan(mamba_body, h,
                            _slice_layers(params["layers"], start, length),
                            unroll=L.UNROLL_FOR_COSTING)
        h, _ = shared_call(h)
        h = ctx.shard(h, "dp", ctx.tp, None)
    h = L.rms_norm(h, params["final_norm"])
    mask = batch.get("mask", jnp.ones_like(batch["labels"], jnp.float32))
    return L.lm_loss_chunked(h, params, batch["labels"], mask, cfg, ctx)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_state(cfg: ModelConfig, batch: int, cache_len: int,
               ctx: DistContext) -> PyTree:
    nseg = n_segments(cfg)
    Hk, Dh = cfg.n_kv_heads, cfg.head_dim
    # batch-shardable shapes shard the cache on batch; long-context B=1
    # decode shards the cache *length* over the data axes instead
    # (sequence-parallel KV, see DESIGN.md)
    if ctx.batch_shardable:
        kv_spec = (None, "dp", None, ctx.tp, None)
    else:
        kv_spec = (None, None, ctx.raw_dp_spec, ctx.tp, None)
    return {
        "ssm": S.init_state(cfg, batch, ctx),
        # one KV cache per shared-block application slot
        "k": ctx.shard(jnp.zeros((nseg, batch, cache_len, Hk, Dh), _dtype(cfg)),
                       *kv_spec),
        "v": ctx.shard(jnp.zeros((nseg, batch, cache_len, Hk, Dh), _dtype(cfg)),
                       *kv_spec),
        "kpos": jnp.full((cache_len,), -1, jnp.int32),
        "pos": jnp.zeros((), jnp.int32),
    }


def decode_step(params, state, tokens, cfg: ModelConfig, ctx: DistContext,
                spec=None):
    x = L.embed_tokens(tokens, params, ctx)
    x = ctx.shard(x, "dp", None, None)
    pos = state["pos"]
    positions = pos[None] + jnp.zeros((1,), jnp.int32)
    cache_len = state["k"].shape[2]
    slot = pos % cache_len
    kpos = state["kpos"].at[slot].set(pos)
    ssm = state["ssm"]

    def mamba_body(x, xs):
        return S.layer_decode(x, *xs, cfg, ctx)

    new_h, new_conv, new_k, new_v = [], [], [], []
    lp_sh = params["shared"]
    for si, (start, length) in enumerate(_segments(cfg)):
        seg_layers = _slice_layers(params["layers"], start, length)
        seg_h = jax.lax.slice_in_dim(ssm["h"], start, start + length, axis=0)
        seg_c = jax.lax.slice_in_dim(ssm["conv"], start, start + length, axis=0)
        x, (hs, cs) = jax.lax.scan(mamba_body, x, (seg_layers, seg_h, seg_c),
                                   unroll=L.UNROLL_FOR_COSTING)
        new_h.append(hs)
        new_conv.append(cs)
        # shared attention block over this segment's cache slot
        xn = L.rms_norm(x, lp_sh["attn_norm"])
        q, k, v = L.qkv_project(xn, lp_sh["attn"], cfg, ctx, positions)
        kc = jax.lax.dynamic_update_slice_in_dim(state["k"][si], k, slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(state["v"][si], v, slot, axis=1)
        o = L.flash_attention(q, kc, vc, positions, kpos, causal=True,
                              window=0, q_chunk=1,
                              kv_chunk=min(1024, cache_len), ctx=ctx)
        a = jnp.einsum("bshk,hkd->bsd", o, lp_sh["attn"]["wo"])
        x = x + ctx.shard(a, "dp", None, None)
        x = x + L.mlp_block(L.rms_norm(x, lp_sh["mlp_norm"]), lp_sh["mlp"], ctx)
        new_k.append(kc)
        new_v.append(vc)

    h = L.rms_norm(x, params["final_norm"])
    logits = L.lm_logits(h, params, ctx)
    new_state = {
        "ssm": {"h": jnp.concatenate(new_h, axis=0),
                "conv": jnp.concatenate(new_conv, axis=0),
                "pos": ssm["pos"] + 1},
        "k": jnp.stack(new_k), "v": jnp.stack(new_v),
        "kpos": kpos, "pos": pos + 1,
    }
    return logits, new_state


def prefill(params, batch, cfg: ModelConfig, ctx: DistContext, spec=None):
    """Prefill: chunked SSD over the prompt + shared-block KV caches."""
    tokens = batch["tokens"]
    h = L.embed_tokens(tokens, params, ctx)
    h = ctx.shard(h, "dp", None, None)
    Sq = tokens.shape[1]
    positions = jnp.arange(Sq)

    def mamba_body(x, lp):
        return S.layer_prefill(x, lp, cfg, ctx)

    lp_sh = params["shared"]
    hs_all, conv_all, k_all, v_all = [], [], [], []
    for (start, length) in _segments(cfg):
        h, (hs, cs) = jax.lax.scan(mamba_body, h,
                                   _slice_layers(params["layers"], start, length))
        hs_all.append(hs)
        conv_all.append(cs)
        xn = L.rms_norm(h, lp_sh["attn_norm"])
        q, k, v = L.qkv_project(xn, lp_sh["attn"], cfg, ctx, positions)
        o = L.flash_attention(q, k, v, positions, positions, causal=True,
                              window=0, q_chunk=min(1024, Sq),
                              kv_chunk=min(1024, Sq), ctx=ctx)
        a = jnp.einsum("bshk,hkd->bsd", o, lp_sh["attn"]["wo"])
        h = h + ctx.shard(a, "dp", None, None)
        h = h + L.mlp_block(L.rms_norm(h, lp_sh["mlp_norm"]), lp_sh["mlp"], ctx)
        k_all.append(k.astype(_dtype(cfg)))
        v_all.append(v.astype(_dtype(cfg)))

    hfin = L.rms_norm(h, params["final_norm"])
    logits = L.lm_logits(hfin[:, -1:], params, ctx)
    slack = 64                 # room for subsequently generated tokens
    ks = jnp.stack(k_all)
    vs = jnp.stack(v_all)
    zk = jnp.zeros(ks.shape[:2] + (slack,) + ks.shape[3:], ks.dtype)
    ks = jnp.concatenate([ks, zk], axis=2)
    vs = jnp.concatenate([vs, zk], axis=2)
    kpos = jnp.concatenate([jnp.arange(Sq, dtype=jnp.int32),
                            jnp.full((slack,), -1, jnp.int32)])
    state = {
        "ssm": {"h": jnp.concatenate(hs_all, 0),
                "conv": jnp.concatenate(conv_all, 0),
                "pos": jnp.asarray(Sq, jnp.int32)},
        "k": ks, "v": vs,
        "kpos": kpos, "pos": jnp.asarray(Sq, jnp.int32),
    }
    return logits, state
