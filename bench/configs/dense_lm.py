"""Plain reference of a dense decoder-only LM with grouped-query attention
(the Qwen2 and Llama blocks): pre-norm RMSNorm, QKV projections (with a
bias where the configuration has ``qkv_bias``/``attention_bias``), rotary
positions on half-split head dims, causal softmax attention, SwiGLU MLP,
final RMSNorm and a head tied to the embedding.

Sizes come from the configuration file's published keys. The parameter
tree uses the names the program under test consumes; the weights are the
benchmark's own, made from the seed (``reflib.init_from_specs``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import reflib
from reflib import Spec


@dataclasses.dataclass(frozen=True)
class Arch:
    d_model: int
    n_layers: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    eps: float
    theta: float
    tied: bool
    qkv_bias: bool

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def arch(conf: dict) -> Arch:
    return Arch(d_model=conf["hidden_size"],
                n_layers=conf["num_hidden_layers"],
                n_heads=conf["num_attention_heads"],
                n_kv=conf["num_key_value_heads"],
                d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                eps=conf["rms_norm_eps"], theta=conf["rope_theta"],
                tied=conf["tie_word_embeddings"],
                qkv_bias=conf.get("qkv_bias", conf.get("attention_bias",
                                                       False)))


def param_specs(a: Arch, dtype: str = "bfloat16") -> dict:
    L, D, H, K, Dh, F = (a.n_layers, a.d_model, a.n_heads, a.n_kv,
                         a.head_dim, a.d_ff)
    layers = {
        "attn_norm": Spec((L, D), dtype, "ones"),
        "attn": {
            "wq": Spec((L, D, H, Dh), dtype, "normal", D),
            "wk": Spec((L, D, K, Dh), dtype, "normal", D),
            "wv": Spec((L, D, K, Dh), dtype, "normal", D),
            "wo": Spec((L, H, Dh, D), dtype, "normal", H * Dh),
        },
        "mlp_norm": Spec((L, D), dtype, "ones"),
        "mlp": {
            "w_gate": Spec((L, D, F), dtype, "normal", D),
            "w_up": Spec((L, D, F), dtype, "normal", D),
            "w_down": Spec((L, F, D), dtype, "normal", F),
        },
    }
    if a.qkv_bias:
        layers["attn"].update(bq=Spec((L, H, Dh), dtype, "zeros"),
                              bk=Spec((L, K, Dh), dtype, "zeros"),
                              bv=Spec((L, K, Dh), dtype, "zeros"))
    out = {"embed": Spec((a.vocab, D), dtype, "normal", D),
           "layers": layers, "final_norm": Spec((D,), dtype, "ones")}
    if not a.tied:
        out["lm_head"] = Spec((a.vocab, D), dtype, "normal", D)
    return out


def _rope(x, theta):
    """x: (B, S, H, Dh); rotate the two halves of each head."""
    S, Dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(a: Arch, num: reflib.Numerics, x, lp):
    at = lp["attn"]
    h = reflib.rms_norm(x, lp["attn_norm"], a.eps)
    q = num.dot("bsd,dhk->bshk", h, at["wq"])
    k = num.dot("bsd,dhk->bshk", h, at["wk"])
    v = num.dot("bsd,dhk->bshk", h, at["wv"])
    if a.qkv_bias:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q, k = _rope(q, a.theta), _rope(k, a.theta)
    g = a.n_heads // a.n_kv
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    S = x.shape[1]
    s = num.dot("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(a.head_dim))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = num.dot("bhqk,bkhd->bqhd", p, v)
    x = x + num.dot("bshk,hkd->bsd", o, at["wo"])
    m = lp["mlp"]
    h = reflib.rms_norm(x, lp["mlp_norm"], a.eps)
    u = jax.nn.silu(num.dot("bsd,df->bsf", h, m["w_gate"])) \
        * num.dot("bsd,df->bsf", h, m["w_up"])
    return x + num.dot("bsf,fd->bsd", u, m["w_down"])


def loss_fn(a: Arch, loss_chunk: int = 256):
    """``(params_f32, batch, numerics) -> mean next-token cross-entropy``."""

    def loss(params, batch, num):
        x = params["embed"][batch["tokens"]]

        @jax.checkpoint
        def body(x, lp):
            return _layer(a, num, x, lp), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = reflib.rms_norm(x, params["final_norm"], a.eps)
        head = params["embed"] if a.tied else params["lm_head"]
        chunk = min(loss_chunk, x.shape[1])
        return reflib.chunked_xent(x, head, batch["labels"], num, chunk)

    return loss


def flops_per_token(a: Arch, seq: int) -> float:
    """Model FLOPs per trained token, forward and backward (3x the
    forward), recomputation not counted: 6 x the matmul parameters a
    token passes through (the tied head counts, the embedding lookup does
    not) plus causal attention's two S x S products, of which a token
    needs on average the (seq + 1) / 2 keys up to its own position."""
    D, H, K, Dh, F, L = (a.d_model, a.n_heads, a.n_kv, a.head_dim, a.d_ff,
                         a.n_layers)
    per_layer = D * H * Dh + 2 * D * K * Dh + H * Dh * D + 3 * D * F
    matmul = L * per_layer + a.vocab * D
    keys = (seq + 1) / 2
    attention = L * 2 * (2 * H * Dh * keys)       # QK^T and PV, forward
    return 6.0 * matmul + 3.0 * attention
