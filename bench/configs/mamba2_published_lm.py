"""Plain reference of a Mamba2 LM (SSD, arXiv:2405.21060) as published in
``mamba_ssm`` (``MixerModel`` of ``Mamba2`` blocks with their defaults):
pre-norm RMSNorm; one input projection, without bias, to [z, x, B, C,
dt]; a causal depthwise convolution of width ``d_conv`` with a bias over
x‖B‖C, then SiLU; the SSD recurrence h_t = exp(dt_t A) h_{t-1} + dt_t
B_t x_t^T, y_t = C_t h_t + D x_t with one B/C group shared by all heads;
the gated RMSNorm rmsnorm(y * silu(z)) * w over d_inner
(``norm_before_gate=False``, one group); the output projection without
bias; final RMSNorm and a head tied to the embedding. Everything is
float32, so the residual stream is too (``residual_in_fp32``).

The recurrence is evaluated by the chunked state-space-dual form of the
paper's minimal listing (``ssd_minimal_discrete``), written out here; it
is exact in any chunk length.
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
    eps: float

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.headdim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, B and C."""
        return self.d_inner + 2 * self.d_state


def arch(conf: dict) -> Arch:
    return Arch(d_model=conf["d_model"], n_layers=conf["n_layer"],
                vocab=conf["vocab_size"], d_state=conf["d_state"],
                d_conv=conf["d_conv"], expand=conf["expand"],
                headdim=conf["headdim"], tied=conf["tie_embeddings"],
                eps=conf["norm_epsilon"])


def param_specs(a: Arch, dtype: str = "bfloat16") -> dict:
    """The program's weight tree. Normal weights take the standard
    deviations of the published initialization (``mamba_ssm``'s
    ``_init_weights`` over PyTorch's defaults): 1/sqrt(3 fan_in) for
    in_proj and the convolution (the uniform bound 1/sqrt(fan_in)),
    out_proj's divided by sqrt(n_layer) as ``rescale_prenorm_residual``
    does, and 0.02 for the embedding."""
    L, D, DI, N, H, K, C = (a.n_layers, a.d_model, a.d_inner, a.d_state,
                            a.n_heads, a.d_conv, a.conv_dim)
    mixer = {
        "in_proj": Spec((L, D, 2 * DI + 2 * N + H), dtype, "normal", 3 * D),
        "conv_w": Spec((L, C, K), dtype, "normal", 3 * K),
        "conv_b": Spec((L, C), dtype, "zeros"),
        "A_log": Spec((L, H), "float32", "zeros"),
        "dt_bias": Spec((L, H), "float32", "zeros"),
        "D_skip": Spec((L, H), "float32", "ones"),
        "norm": Spec((L, DI), dtype, "ones"),
        "out_proj": Spec((L, DI, D), dtype, "normal", 3 * DI * L),
    }
    out = {"embed": Spec((a.vocab, D), dtype, "normal", 2500),
           "layers": {"norm": Spec((L, D), dtype, "ones"), "mixer": mixer},
           "final_norm": Spec((D,), dtype, "ones")}
    if not a.tied:
        out["lm_head"] = Spec((a.vocab, D), dtype, "normal", 3 * D)
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
    DI, N, H, P, K = a.d_inner, a.d_state, a.n_heads, a.headdim, a.d_conv
    zxbcdt = num.dot("bsd,de->bse", x, p["in_proj"])
    z = zxbcdt[..., :DI]
    xbc = zxbcdt[..., DI:2 * DI + 2 * N]
    dtr = zxbcdt[..., 2 * DI + 2 * N:]
    S = x.shape[1]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, i:i + S] * p["conv_w"][:, i]
                          for i in range(K)) + p["conv_b"])
    xs, Bm, Cm = xbc[..., :DI], xbc[..., DI:DI + N], xbc[..., DI + N:]
    dt = jax.nn.softplus(dtr + p["dt_bias"])               # (b, s, H)
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(x.shape[0], S, H, P)
    y = ssd(xh * dt[..., None], A * dt, Bm, Cm, num)
    y = y + xh * p["D_skip"][:, None]
    y = y.reshape(x.shape[0], S, DI) * jax.nn.silu(z)
    y = reflib.rms_norm(y, p["norm"], a.eps)
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


def ssd_cost(a: Arch, seq: int, chunk: int = 128) -> tuple[float, float]:
    """SSD's forward FLOPs and least HBM bytes per token in one layer, in
    the chunked dual form at chunk Q. FLOPs: causal C.B^T scores (one
    group, N wide) and the masked (Q x Q) mix over heads, on average
    (Q + 1) / 2 positions per token, then the chunk state in and out
    (2 H P N each). Bytes: x, dt, B and C read and y written once in
    float32, as a fused kernel would; the jnp path moves far more (its
    (Q x Q x H) intermediates), which is what a share of this bound
    would show."""
    N, H, P = a.d_state, a.n_heads, a.headdim
    pos = (min(chunk, seq) + 1) / 2
    flops = 2 * pos * N + 2 * pos * H * P + 2 * 2 * H * P * N
    nbytes = 4 * (2 * H * P + H + 2 * N)
    return flops, nbytes


def flops_per_token(a: Arch, seq: int, chunk: int = 128) -> float:
    """Model FLOPs per trained token, forward and backward (3x the
    forward), recomputation not counted: 6 x the projection parameters a
    token passes through (in_proj, out_proj, the tied head) plus the
    depthwise convolution over x, B and C, and SSD at the program's chunk
    (``ssd_cost``)."""
    D, DI, N, H, K, L = (a.d_model, a.d_inner, a.d_state, a.n_heads,
                         a.d_conv, a.n_layers)
    proj = D * (2 * DI + 2 * N + H) + DI * D
    matmul = L * proj + a.vocab * D
    conv_fwd = 2 * K * a.conv_dim
    return 6.0 * matmul + 3.0 * L * (ssd_cost(a, seq, chunk)[0] + conv_fwd)
