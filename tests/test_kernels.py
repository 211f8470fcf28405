"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (deliverable c).

Kernels run in interpret=True mode on CPU (the kernel body executes in
Python) — the TPU is the compile target, interpret validates semantics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.masked_restore.kernel import masked_restore_pallas
from repro.kernels.masked_restore.ref import masked_restore_ref
from repro.kernels.ssd_scan.kernel import ssd_intra_pallas
from repro.kernels.ssd_scan.ref import ssd_intra_ref
from repro.kernels.ssd_scan.ops import ssd_chunked_kernel
from repro.kernels.sw_attention.kernel import sw_attention_pallas
from repro.kernels.sw_attention.ref import sw_attention_ref

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# masked_restore
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(3, 64), (8, 512), (21, 1000), (64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_restore_sweep(shape, dtype):
    dst = jnp.asarray(RNG.normal(size=shape), dtype)
    src = jnp.asarray(RNG.normal(size=shape), dtype)
    mask = jnp.asarray(RNG.random(shape[0]) < 0.5)
    got = masked_restore_pallas(dst, src, mask, interpret=True)
    want = masked_restore_ref(dst, src, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_masked_restore_all_none():
    dst = jnp.asarray(RNG.normal(size=(9, 70)), jnp.float32)
    src = jnp.asarray(RNG.normal(size=(9, 70)), jnp.float32)
    all_m = jnp.ones((9,), bool)
    none_m = jnp.zeros((9,), bool)
    np.testing.assert_array_equal(
        np.asarray(masked_restore_pallas(dst, src, all_m, interpret=True)),
        np.asarray(src))
    np.testing.assert_array_equal(
        np.asarray(masked_restore_pallas(dst, src, none_m, interpret=True)),
        np.asarray(dst))


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [(2, 2, 16, 3, 8, 16), (1, 4, 32, 4, 16, 32),
                                  (2, 1, 8, 1, 4, 8), (1, 2, 64, 2, 32, 64)])
def test_ssd_intra_sweep(dims):
    B, nc, Q, H, P, N = dims
    la = -jnp.asarray(np.abs(RNG.normal(size=(B, nc, Q, H))), jnp.float32) * 0.1
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, nc, Q, H))), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(B, nc, Q, H, P)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, nc, Q, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, nc, Q, N)), jnp.float32)
    y1, s1 = ssd_intra_pallas(la, dt, x, Bm, Cm, interpret=True)
    y2, s2 = ssd_intra_ref(la, dt, x, Bm, Cm)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s1, s2, rtol=2e-4, atol=2e-4)


def test_ssd_full_matches_naive_recurrence():
    B, S, H, P, N, Q = 2, 48, 3, 8, 16, 16
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(k[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (B, S, N))
    Cm = jax.random.normal(k[4], (B, S, N))
    y, hf = ssd_chunked_kernel(x, dt, A, Bm, Cm, chunk=Q, interpret=True)
    h = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        a = jnp.exp(dt[:, t] * A)
        h = h * a[:, :, None, None] + jnp.einsum(
            "bh,bn,bhp->bhpn", dt[:, t], Bm[:, t], x[:, t])
        ys.append(jnp.einsum("bn,bhpn->bhp", Cm[:, t], h))
    np.testing.assert_allclose(y, jnp.stack(ys, 1), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(hf, h, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# sw_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [(2, 1, 64, 16, 16, 16, 16),
                                  (1, 2, 128, 32, 32, 32, 32),
                                  (2, 4, 96, 16, 24, 32, 16),
                                  (1, 1, 32, 8, 64, 16, 16)])
def test_sw_attention_sweep(dims):
    BH, G, S, Dh, W, qc, kc = dims
    q = jnp.asarray(RNG.normal(size=(BH, G, S, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(BH, S, Dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(BH, S, Dh)), jnp.float32)
    got = sw_attention_pallas(q, k, v, window=W, q_chunk=qc, kv_chunk=kc,
                              interpret=True)
    want = sw_attention_ref(q, k, v, window=W)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_sw_attention_bf16():
    BH, G, S, Dh, W = 1, 2, 64, 16, 16
    q = jnp.asarray(RNG.normal(size=(BH, G, S, Dh)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(BH, S, Dh)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(BH, S, Dh)), jnp.bfloat16)
    got = sw_attention_pallas(q, k, v, window=W, q_chunk=16, kv_chunk=16,
                              interpret=True)
    want = sw_attention_ref(q, k, v, window=W)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
