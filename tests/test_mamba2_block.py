"""The Mamba2 LM against a plain float32 reference of the published block
(``mamba_ssm``'s ``Mamba2`` in a ``MixerModel``), at small widths on the CPU.

The reference here runs the recurrence token by token, h_t = exp(dt_t A)
h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t, so it shares nothing with
the program's chunked dual form but the parameters.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model
from repro.models import ssm as S
from repro.sharding import single_device_ctx

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5


@pytest.fixture(scope="module")
def ctx():
    return single_device_ctx()


def _small_cfg(**kw):
    cfg = get_config("mamba2-370m", reduced=True)
    return dataclasses.replace(cfg, d_model=64, ssm_state=16, ssm_headdim=16,
                               vocab=256, **kw)


def _random_params(cfg, seed):
    """Every leaf drawn at random, so that the biases, norm weights, A_log,
    dt_bias and D that initialize to constants are exercised too."""
    shapes = jax.eval_shape(lambda k: S.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = [0.5 * jax.random.normal(k, x.shape, jnp.float32)
           for k, x in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(tree, out)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * w


def _ssd_sequential(x, dt, A, Bm, Cm):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm, Cm: (B,S,N) -> y (B,S,H,P)."""
    Bsz, _, H, P = x.shape
    N = Bm.shape[-1]

    def step(h, xs):
        xt, dtt, bt, ct = xs
        h = jnp.exp(dtt * A)[..., None, None] * h \
            + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, ct, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((Bsz, H, P, N), jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


def _ref_mixer(u, p, cfg):
    DI, N, H, P, K = (cfg.d_inner, cfg.ssm_state, cfg.ssm_heads,
                      cfg.ssm_headdim, cfg.conv_width)
    Bsz, Sq, _ = u.shape
    zxbcdt = jnp.einsum("bsd,de->bse", u, p["in_proj"], precision=HIGHEST)
    z, xbc, dtr = (zxbcdt[..., :DI], zxbcdt[..., DI:2 * DI + 2 * N],
                   zxbcdt[..., 2 * DI + 2 * N:])
    # causal depthwise conv1d over x‖B‖C, weight (channels, width), bias
    xp = jnp.concatenate([jnp.zeros((Bsz, K - 1, xbc.shape[-1])), xbc], 1)
    conv = sum(xp[:, k:k + Sq] * p["conv_w"][:, k] for k in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x, Bm, Cm = xbc[..., :DI], xbc[..., DI:DI + N], xbc[..., DI + N:]
    dt = jax.nn.softplus(dtr + p["dt_bias"])
    xh = x.reshape(Bsz, Sq, H, P)
    y = _ssd_sequential(xh, dt, -jnp.exp(p["A_log"]), Bm, Cm)
    y = (y + xh * p["D_skip"][:, None]).reshape(Bsz, Sq, DI)
    y = _rms(y * jax.nn.silu(z), p["norm"])          # norm_before_gate=False
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"], precision=HIGHEST)


def _ref_loss(params, batch, cfg):
    x = params["embed"][batch["tokens"]]
    for i in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x = x + _ref_mixer(_rms(x, lp["norm"]), lp["mixer"], cfg)
    x = _rms(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"], precision=HIGHEST)
    ll = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - ll)


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------

def test_published_widths_and_parameter_count():
    """At published widths the tree is the Mamba2 block's tensors:
    in_proj 1,024 x 4,384, a 2,304-channel width-4 conv with bias, 32
    heads' A_log/dt_bias/D, the gated norm over 2,048, out_proj 2,048 x
    1,024, and a head tied to the 50,280-row embedding."""
    cfg = get_config("mamba2-370m")
    p = jax.eval_shape(lambda k: S.init_params(k, cfg), jax.random.PRNGKey(0))
    m = p["layers"]["mixer"]
    shapes = {k: v.shape for k, v in m.items()}
    assert shapes == {"in_proj": (48, 1024, 4384), "conv_w": (48, 2304, 4),
                      "conv_b": (48, 2304), "A_log": (48, 32),
                      "dt_bias": (48, 32), "D_skip": (48, 32),
                      "norm": (48, 2048), "out_proj": (48, 2048, 1024)}
    assert "lm_head" not in p and p["embed"].shape == (50280, 1024)
    per_layer = sum(int(np.prod(s[1:])) for s in shapes.values()) + 1024
    assert per_layer == 6_601_056
    assert sum(x.size for x in jax.tree_util.tree_leaves(p)) == 368_338_432


def test_loss_and_grads_match_the_published_block(ctx):
    """Loss and every leaf's gradient against the plain reference. Both
    sides compute in float32; they differ only in the order of float32
    sums (the chunked dual form against the token-by-token recurrence)
    and in exp(a)·exp(b) against exp(a + b), a few 1e-6 relative, so 1e-4
    of the loss and of each leaf's gradient norm leaves room on the CPU
    while a left-out part of the block (bias, gate, norm) moves both far
    more."""
    cfg = _small_cfg()
    ops = get_model(cfg)
    params = _random_params(cfg, 7)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 65), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    loss, g = jax.jit(jax.value_and_grad(
        lambda p: ops.train_loss(p, batch, cfg, ctx)))(params)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p: _ref_loss(p, batch, cfg)))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-4)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree_util.tree_leaves(g_ref)):
        err = float(jnp.linalg.norm(a - b)) / float(jnp.linalg.norm(b))
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_residual_stream_is_float32_in_a_bf16_model(ctx):
    """``residual_in_fp32``: the bf16 model's layers read and write a
    float32 residual stream; only the mixer's input is bf16."""
    cfg = _small_cfg(dtype="bfloat16")
    params = S.init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jnp.ones((1, cfg.ssm_chunk, cfg.d_model), jnp.float32)
    seen = []
    jaxpr = jax.make_jaxpr(lambda x: S.layer_fwd(x, lp, cfg, ctx))(x)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            seen.append(tuple(v.aval.dtype for v in eqn.invars))
    assert jaxpr.out_avals[0].dtype == jnp.float32
    assert seen[0] == (jnp.bfloat16, jnp.bfloat16)          # in_proj


def test_ssd_gradient_is_finite_at_large_decays():
    """With A·dt ≈ -2 per token a 128-token chunk's segment sums reach
    +254 above the diagonal; their exp overflows, and masking after the
    exp gave 0·inf = NaN in the backward pass. Masked first, the gradient
    of every input is finite and equals the sequential recurrence's."""
    cfg = dataclasses.replace(_small_cfg(), ssm_chunk=128)
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    Bsz, Sq, H, P, N = 2, 256, 4, 8, 16
    x = jax.random.normal(ks[0], (Bsz, Sq, H, P))
    dt = 2.0 + 0.1 * jax.random.normal(ks[1], (Bsz, Sq, H))
    A = -jnp.ones((H,)) + 0.05 * jax.random.normal(ks[2], (H,))
    Bm = jax.random.normal(ks[3], (Bsz, Sq, N))
    Cm = jax.random.normal(ks[4], (Bsz, Sq, N))
    r = jax.random.normal(ks[5], (Bsz, Sq, H, P))

    def prog(*a):
        return jnp.sum(S.ssd_chunked(*a, cfg, None)[0] * r)

    def ref(*a):
        return jnp.sum(_ssd_sequential(*a) * r)

    args = (x, dt, A, Bm, Cm)
    got = jax.jit(jax.grad(prog, argnums=range(5)))(*args)
    want = jax.jit(jax.grad(ref, argnums=range(5)))(*args)
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        err = float(jnp.linalg.norm(a - b)) / float(jnp.linalg.norm(b))
        assert err < 1e-4, (name, err)


def test_prefill_state_equals_token_by_token_decode(ctx):
    """Prefill's carried state (the SSM state and the last K-1 inputs of
    the conv over x‖B‖C) and its last logits equal those of decoding the
    same tokens one at a time from a fresh state."""
    cfg = _small_cfg()
    ops = get_model(cfg)
    params = _random_params(cfg, 5)
    Sq = cfg.ssm_chunk
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, Sq), 0, cfg.vocab)
    logits_p, state_p = jax.jit(lambda t: ops.prefill(
        params, {"tokens": t}, cfg, ctx))(toks)
    state = ops.init_cache(cfg, 1, Sq, ctx)
    assert state["conv"].shape[-1] == cfg.d_inner + 2 * cfg.ssm_state
    step = jax.jit(lambda s, t: ops.decode_step(params, s, t, cfg, ctx))
    for t in range(Sq):
        logits_d, state = step(state, toks[:, t:t + 1])
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_d),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state_p["h"]),
                               np.asarray(state["h"]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state_p["conv"]),
                               np.asarray(state["conv"]), rtol=1e-4, atol=1e-4)
