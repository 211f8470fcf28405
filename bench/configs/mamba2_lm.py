"""Plain reference of a Mamba2 LM (SSD, arXiv:2405.21060) as the program
under test defines its layer: pre-norm RMSNorm, one input projection to
[z, x, B, C, dt], a causal depthwise convolution and SiLU on x, the SSD
recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t
+ D x_t with one B/C group shared by all heads, the gate y * silu(z), and
the output projection; final RMSNorm and a head tied to the embedding.

The recurrence is evaluated by the chunked state-space-dual form of the
paper's minimal listing (``ssd_minimal_discrete``), written out here; it
is exact in any chunk length. Where the layer departs from the published
Mamba2 block, the configuration file says so.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import reflib
from reflib import Spec

CHUNK = 64          # the reference's own SSD chunk: 2048 = 32 x 64


@dataclasses.dataclass(frozen=True)
class Arch:
    d_model: int
    n_layers: int
    vocab: int
    d_state: int
    d_conv: int
    expand: int
    headdim: int
    tied: bool
    eps: float = 1e-6

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.headdim


def arch(conf: dict) -> Arch:
    return Arch(d_model=conf["d_model"], n_layers=conf["n_layer"],
                vocab=conf["vocab_size"], d_state=conf["d_state"],
                d_conv=conf["d_conv"], expand=conf["expand"],
                headdim=conf["headdim"], tied=conf["tie_embeddings"])


def param_specs(a: Arch, dtype: str = "bfloat16") -> dict:
    L, D, DI, N, H, K = (a.n_layers, a.d_model, a.d_inner, a.d_state,
                         a.n_heads, a.d_conv)
    mixer = {
        "in_proj": Spec((L, D, 2 * DI + 2 * N + H), dtype, "normal", D),
        "conv_w": Spec((L, K, DI), dtype, "normal", K),
        "A_log": Spec((L, H), "float32", "zeros"),
        "dt_bias": Spec((L, H), "float32", "zeros"),
        "D_skip": Spec((L, H), "float32", "ones"),
        "out_proj": Spec((L, DI, D), dtype, "normal", DI),
    }
    out = {"embed": Spec((a.vocab, D), dtype, "normal", D),
           "layers": {"norm": Spec((L, D), dtype, "ones"), "mixer": mixer},
           "final_norm": Spec((D,), dtype, "ones")}
    if not a.tied:
        out["lm_head"] = Spec((a.vocab, D), dtype, "normal", D)
    return out


def _segsum(x):
    """x: (..., T) -> (..., T, T) with [i, j] = sum x[j+1..i], -inf above
    the diagonal."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), seg, -jnp.inf)


def ssd(X, A, B, C, num: reflib.Numerics, chunk: int = CHUNK):
    """X: (b, s, h, p) = x * dt; A: (b, s, h) = A * dt; B, C: (b, s, n).
    Returns y: (b, s, h, p) from a zero initial state."""
    b, s, h, p = X.shape
    c = s // chunk
    X = X.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, -1)
    C = C.reshape(b, c, chunk, -1)
    A = jnp.moveaxis(A.reshape(b, c, chunk, h), 3, 1)      # (b, h, c, l)
    A_cs = jnp.cumsum(A, axis=-1)
    # within chunks: the dual (attention-like) form
    L = jnp.exp(_segsum(A))                                # (b, h, c, l, l)
    CB = num.dot("bcln,bcsn->bcls", C, B)
    Y_diag = num.dot("bhcls,bcshp->bclhp", CB[:, None] * L, X)
    # each chunk's final state, then the chunk-to-chunk recurrence
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)          # (b, h, c, l)
    states = num.dot("bcln,bclhp->bchpn", B,
                     X * jnp.moveaxis(decay_states, 1, 3)[..., None])
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = num.dot("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    # states entering each chunk, carried to each position
    Y_off = num.dot("bcln,bchpn->bclhp", C, states) \
        * jnp.moveaxis(jnp.exp(A_cs), 1, 3)[..., None]
    return (Y_diag + Y_off).reshape(b, s, h, p)


def _mixer(a: Arch, num: reflib.Numerics, x, p):
    DI, N, H, P = a.d_inner, a.d_state, a.n_heads, a.headdim
    zxbcdt = num.dot("bsd,de->bse", x, p["in_proj"])
    z = zxbcdt[..., :DI]
    xs = zxbcdt[..., DI:2 * DI]
    Bm = zxbcdt[..., 2 * DI:2 * DI + N]
    Cm = zxbcdt[..., 2 * DI + N:2 * DI + 2 * N]
    dtr = zxbcdt[..., 2 * DI + 2 * N:]
    K, S = a.d_conv, x.shape[1]
    xp = jnp.pad(xs, ((0, 0), (K - 1, 0), (0, 0)))
    xs = jax.nn.silu(sum(xp[:, i:i + S] * p["conv_w"][i] for i in range(K)))
    dt = jax.nn.softplus(dtr + p["dt_bias"])               # (b, s, H)
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(x.shape[0], S, H, P)
    y = ssd(xh * dt[..., None], A * dt, Bm, Cm, num)
    y = y + xh * p["D_skip"][:, None]
    y = y.reshape(x.shape[0], S, DI) * jax.nn.silu(z)
    return num.dot("bse,ed->bsd", y, p["out_proj"])


def loss_fn(a: Arch, loss_chunk: int = 512):
    """``(params_f32, batch, numerics) -> mean next-token cross-entropy``."""

    def loss(params, batch, num):
        x = params["embed"][batch["tokens"]]

        @jax.checkpoint
        def body(x, lp):
            h = reflib.rms_norm(x, lp["norm"], a.eps)
            return x + _mixer(a, num, h, lp["mixer"]), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = reflib.rms_norm(x, params["final_norm"], a.eps)
        head = params["embed"] if a.tied else params["lm_head"]
        chunk = min(loss_chunk, x.shape[1])
        return reflib.chunked_xent(x, head, batch["labels"], num, chunk)

    return loss


def flops_per_token(a: Arch, seq: int, chunk: int = 128) -> float:
    """Model FLOPs per trained token, forward and backward (3x the
    forward), recomputation not counted: 6 x the projection parameters a
    token passes through (in_proj, out_proj, the tied head) plus the
    depthwise convolution and SSD's chunked dual form at the program's
    chunk Q: causal C.B^T scores and the masked (Q x Q) mix over heads,
    on average (Q + 1) / 2 positions per token, then the chunk state in
    and out (2 H P N each)."""
    D, DI, N, H, P, K, L = (a.d_model, a.d_inner, a.d_state, a.n_heads,
                            a.headdim, a.d_conv, a.n_layers)
    proj = D * (2 * DI + 2 * N + H) + DI * D
    matmul = L * proj + a.vocab * D
    q = min(chunk, seq)
    pos = (q + 1) / 2
    ssd_fwd = 2 * pos * N + 2 * pos * H * P + 2 * 2 * H * P * N
    conv_fwd = 2 * K * DI
    return 6.0 * matmul + 3.0 * L * (ssd_fwd + conv_fwd)
