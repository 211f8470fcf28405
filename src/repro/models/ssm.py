"""Mamba2 (SSD — state-space duality) language model [arXiv:2405.21060].

The layer is the published Mamba2 block (``mamba_ssm``'s ``Mamba2`` with
its defaults, as ``state-spaces/mamba2-*`` use it): a pre-norm RMSNorm;
``in_proj`` (no bias) to [z, x, B, C, dt]; a causal depthwise convolution
of width ``cfg.conv_width`` with a bias over x‖B‖C, then SiLU; the SSD
recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t
+ D x_t with per-head ``A_log``, ``dt_bias`` and ``D``; the gated RMSNorm
rmsnorm(y · silu(z)) · w over d_inner (``norm_before_gate=False``); and
``out_proj`` (no bias). The LM keeps its residual stream in float32
(``residual_in_fp32``), ends in a final RMSNorm and ties the head to the
embedding when ``cfg.tie_embeddings``.

TPU adaptation of the SSD algorithm: the sequence is processed in chunks of
``cfg.ssm_chunk`` tokens. Within a chunk the recurrence is computed in its
*dual* quadratic (attention-like) matmul form — MXU-friendly, 128-aligned —
and chunk-to-chunk state is carried by a short ``lax.scan``. This is the
structure the paper's authors target at GPU tensor cores; it maps directly
onto the TPU MXU (see kernels/ssd_scan for the Pallas tile).

Simplifications vs. the reference CUDA implementation: a single B/C
group (n_groups=1), and the chunk is ``cfg.ssm_chunk`` (128; the published
kernel uses 256), which changes the tiling, not the result.

Decode is the O(1) recurrent form: h ← a·h + dt·B⊗x per layer.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding.partition import DistContext

PyTree = Any

# norm_epsilon of the published MixerModel and eps of Mamba2's gated norm
EPS = 1e-5


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def conv_channels(cfg: ModelConfig) -> int:
    """Channels of the causal convolution: x‖B‖C."""
    return cfg.d_inner + 2 * cfg.ssm_state


def init_mixer(rng, cfg: ModelConfig) -> PyTree:
    dt = _dtype(cfg)
    D, DI, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    K, C = cfg.conv_width, conv_channels(cfg)
    ks = jax.random.split(rng, 3)
    # standard deviations of the published init: PyTorch's uniform
    # defaults (bound 1/sqrt(fan_in), so 1/sqrt(3 fan_in)), out_proj's
    # further divided by sqrt(n_layers) (rescale_prenorm_residual)
    return {
        # in_proj -> [z (DI), x (DI), B (N), C (N), dt (H)]
        "in_proj": L.dense_init(ks[0], (D, 2 * DI + 2 * N + H), 3 * D, dt),
        # depthwise conv over x‖B‖C, (channels, width) as published
        "conv_w": L.dense_init(ks[1], (C, K), 3 * K, dt),
        "conv_b": jnp.zeros((C,), dt),
        "A_log": jnp.zeros((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "norm": jnp.ones((DI,), dt),            # the gated RMSNorm's weight
        "out_proj": L.dense_init(ks[2], (DI, D), 3 * DI * cfg.n_layers, dt),
    }


def init_layer(rng, cfg: ModelConfig) -> PyTree:
    return {"norm": jnp.ones((cfg.d_model,), _dtype(cfg)),
            "mixer": init_mixer(rng, cfg)}


def init_params(rng, cfg: ModelConfig) -> PyTree:
    k_embed, k_layers = jax.random.split(rng)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    return {
        **L.init_embed(k_embed, cfg, _dtype(cfg)),
        "layers": jax.vmap(lambda k: init_layer(k, cfg))(layer_keys),
        "final_norm": jnp.ones((cfg.d_model,), _dtype(cfg)),
    }


# ---------------------------------------------------------------------------
# mixer forward pieces
# ---------------------------------------------------------------------------

def _split_proj(zxbcdt, cfg: ModelConfig):
    """in_proj's output -> (z, x‖B‖C, dt)."""
    DI = cfg.d_inner
    C = conv_channels(cfg)
    return zxbcdt[..., :DI], zxbcdt[..., DI:DI + C], zxbcdt[..., DI + C:]


def _split_xbc(xbc, cfg: ModelConfig):
    DI, N = cfg.d_inner, cfg.ssm_state
    return xbc[..., :DI], xbc[..., DI:DI + N], xbc[..., DI + N:]


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv + bias + SiLU, in float32. x: (B,S,C);
    w: (C,K); b: (C,); state: (B,K-1,C), the K-1 inputs before ``x``.
    Returns (out (B,S,C) f32, new state (B,K-1,C))."""
    K = w.shape[-1]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    out = sum(xp[:, i:i + x.shape[1]].astype(jnp.float32) * wf[:, i]
              for i in range(K)) + b.astype(jnp.float32)
    new_state = xp[:, -(K - 1):] if K > 1 else None
    return jax.nn.silu(out), new_state


def _gated_norm(y, z, w):
    """Mamba2's gated RMSNorm, one group: rmsnorm(y · silu(z)) · w, in
    float32. y: (..., DI) f32; z: (..., DI); w: (DI,)."""
    with jax.named_scope("gated_norm"):
        g = y * jax.nn.silu(z.astype(jnp.float32))
        var = jnp.mean(g * g, axis=-1, keepdims=True)
        return g * jax.lax.rsqrt(var + EPS) * w.astype(jnp.float32)


def ssd_chunked(x, dt, A, Bm, Cm, cfg: ModelConfig, ctx: DistContext,
                h0=None):
    """Chunked SSD scan (pure-JAX oracle for kernels/ssd_scan).

    x: (B,S,H,P); dt: (B,S,H) (post-softplus); A: (H,) negative;
    Bm, Cm: (B,S,N). Returns (y (B,S,H,P), h_final (B,H,P,N)).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(cfg.ssm_chunk, S)
    nc = S // Q
    assert nc * Q == S, f"seq {S} must be divisible by chunk {Q}"

    la = (dt * A).reshape(Bsz, nc, Q, H)                  # log a_t (negative)
    xc = x.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    cum = jnp.cumsum(la, axis=2)                           # (B,nc,Q,H)
    seg_total = cum[:, :, -1]                              # (B,nc,H)

    # intra-chunk (dual quadratic form): M[i,j] = exp(cum_i - cum_j)·dt_j·(C_i·B_j)
    # for j <= i. The segment sums are masked to -inf above the diagonal
    # BEFORE the exp: there cum_i - cum_j > 0 grows with the chunk, its exp
    # overflows, and a mask applied after would give 0·inf = NaN gradients.
    with jax.named_scope("ssd/intra"):
        scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)     # (B,nc,Q,Q)
        decay = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,Q,H)
        causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
        M = jnp.exp(jnp.where(causal, decay, -jnp.inf)) \
            * scores[..., None] * dtc[:, :, None, :, :]    # (B,nc,Q,Q,H)
        y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xc)

    # chunk summaries: S_c = Σ_j exp(cum_Q - cum_j)·dt_j·(B_j ⊗ x_j)
    with jax.named_scope("ssd/chunk_state"):
        w = jnp.exp(seg_total[:, :, None, :] - cum) * dtc  # (B,nc,Q,H)
        chunk_state = jnp.einsum("bcjh,bcjn,bcjhp->bchpn", w, Bc, xc)

    # inter-chunk recurrence over nc chunks
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def body(h, xs):
        seg, st = xs                                       # (B,H), (B,H,P,N)
        h_out = h                                          # state BEFORE chunk
        h = h * jnp.exp(seg)[:, :, None, None] + st
        return h, h_out

    with jax.named_scope("ssd/inter"):
        hs_final, h_prev = jax.lax.scan(
            body, h0, (jnp.moveaxis(seg_total, 1, 0),
                       jnp.moveaxis(chunk_state, 1, 0)))
        h_prev = jnp.moveaxis(h_prev, 0, 1)                # (B,nc,H,P,N)
        # inter-chunk contribution: y_inter[i] = exp(cum_i)·(C_i · h_prev)
        y_inter = jnp.einsum("bcin,bchpn->bcihp", Cc, h_prev) \
            * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y, hs_final


def mixer_prefill(x, p, cfg: ModelConfig, ctx: DistContext):
    """x: (B,S,D) -> (out (B,S,D), (h_final (B,H,P,N), conv state
    (B,K-1,C))). Training/prefill path."""
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xbc, dtr = _split_proj(zxbcdt, cfg)
    xbc, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    xi, Bm, Cm = _split_xbc(xbc, cfg)
    H, P = cfg.ssm_heads, cfg.ssm_headdim
    Bsz, S, _ = x.shape
    # SSD heads are independent -> shard H over the model axis so the
    # O(Q²)·H intra-chunk intermediates divide across TP
    xh = ctx.shard(xi.reshape(Bsz, S, H, P), "dp", None, ctx.tp, None)
    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])
    dt = ctx.shard(dt, "dp", None, ctx.tp)
    A = -jnp.exp(p["A_log"])
    y, h_fin = ssd_chunked(xh, dt, A, Bm, Cm, cfg, ctx)
    y = ctx.shard(y, "dp", None, ctx.tp, None)
    y = y + xh * p["D_skip"][:, None]
    y = _gated_norm(y.reshape(Bsz, S, cfg.d_inner), z, p["norm"])
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), p["out_proj"])
    return ctx.shard(out, "dp", None, None), (h_fin, conv_state)


def mixer_decode(x, p, state, cfg: ModelConfig, ctx: DistContext):
    """Single-token recurrent step. x: (B,1,D); state: dict(h, conv)."""
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xbc, dtr = _split_proj(zxbcdt, cfg)
    xbc, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                   state["conv"])
    xi, Bm, Cm = _split_xbc(xbc[:, 0], cfg)
    H, P = cfg.ssm_heads, cfg.ssm_headdim
    Bsz = x.shape[0]
    xh = xi.reshape(Bsz, H, P)
    dt = jax.nn.softplus(dtr[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt * A)                                     # (B,H)
    h = state["h"] * a[:, :, None, None] \
        + jnp.einsum("bh,bn,bhp->bhpn", dt, Bm, xh)
    y = jnp.einsum("bn,bhpn->bhp", Cm, h)
    y = y + xh * p["D_skip"][:, None]
    y = _gated_norm(y.reshape(Bsz, 1, cfg.d_inner), z, p["norm"])
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), p["out_proj"])
    return ctx.shard(out, "dp", None, None), {"h": h, "conv": conv_state}


# ---------------------------------------------------------------------------
# one layer on the residual stream (shared with models/hybrid.py)
# ---------------------------------------------------------------------------

def _pre_norm(x, lp, cfg: ModelConfig):
    return L.rms_norm(x, lp["norm"], EPS).astype(_dtype(cfg))


def layer_prefill(x, lp, cfg: ModelConfig, ctx: DistContext):
    """x + mixer(rmsnorm(x)) and the mixer's final state; the residual
    ``x`` keeps its own dtype."""
    out, state = mixer_prefill(_pre_norm(x, lp, cfg), lp["mixer"], cfg, ctx)
    return x + out.astype(x.dtype), state


def layer_fwd(x, lp, cfg: ModelConfig, ctx: DistContext):
    """Training path: ``layer_prefill`` without the state."""
    return layer_prefill(x, lp, cfg, ctx)[0]


def layer_decode(x, lp, h, conv, cfg: ModelConfig, ctx: DistContext):
    out, new = mixer_decode(_pre_norm(x, lp, cfg), lp["mixer"],
                            {"h": h, "conv": conv}, cfg, ctx)
    return x + out.astype(x.dtype), (new["h"], new["conv"])


def remat_layer_fwd(cfg: ModelConfig):
    """``layer_fwd``, recomputed in the backward pass when ``cfg.remat``:
    only the residual stream entering each layer is saved."""
    if not cfg.remat:
        return layer_fwd
    return jax.checkpoint(layer_fwd, static_argnums=(2, 3),
                          policy=jax.checkpoint_policies.nothing_saveable)


# ---------------------------------------------------------------------------
# model-level API
# ---------------------------------------------------------------------------

def _embed(tokens, params, ctx: DistContext):
    """The token embeddings as the float32 residual stream."""
    h = L.embed_tokens(tokens, params, ctx).astype(jnp.float32)
    return ctx.shard(h, "dp", None, None)


def _final_norm(h, params, cfg: ModelConfig):
    return L.rms_norm(h, params["final_norm"], EPS).astype(_dtype(cfg))


def train_loss(params, batch, cfg: ModelConfig, ctx: DistContext, **_):
    layer = remat_layer_fwd(cfg)

    def body(x, lp):
        # sequence-parallel residual stream (saved activations S-sharded)
        return ctx.shard(layer(x, lp, cfg, ctx), "dp", ctx.tp, None), None

    h, _ = jax.lax.scan(body, _embed(batch["tokens"], params, ctx),
                        params["layers"], unroll=L.UNROLL_FOR_COSTING)
    h = _final_norm(h, params, cfg)
    mask = batch.get("mask", jnp.ones_like(batch["labels"], jnp.float32))
    return L.lm_loss_chunked(h, params, batch["labels"], mask, cfg, ctx)


def init_state(cfg: ModelConfig, batch: int, ctx: DistContext) -> PyTree:
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    return {
        "h": jnp.zeros((cfg.n_layers, batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.conv_width - 1,
                           conv_channels(cfg)), jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(params, batch, cfg: ModelConfig, ctx: DistContext, spec=None):
    """Run the chunked scan over the prompt, carrying final SSM states."""
    tokens = batch["tokens"]
    h, (hs, convs) = jax.lax.scan(
        lambda x, lp: layer_prefill(x, lp, cfg, ctx),
        _embed(tokens, params, ctx), params["layers"],
        unroll=L.UNROLL_FOR_COSTING)
    hfin = _final_norm(h, params, cfg)
    logits = L.lm_logits(hfin[:, -1:], params, ctx)
    state = {"h": hs, "conv": convs,
             "pos": jnp.asarray(tokens.shape[1], jnp.int32)}
    return logits, state


def decode_step(params, state, tokens, cfg: ModelConfig, ctx: DistContext,
                spec=None):
    x, (hs, convs) = jax.lax.scan(
        lambda x, xs: layer_decode(x, *xs, cfg, ctx),
        _embed(tokens, params, ctx),
        (params["layers"], state["h"], state["conv"]),
        unroll=L.UNROLL_FOR_COSTING)
    h = _final_norm(x, params, cfg)
    logits = L.lm_logits(h, params, ctx)
    return logits, {"h": hs, "conv": convs, "pos": state["pos"] + 1}
