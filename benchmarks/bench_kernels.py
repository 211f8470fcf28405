"""Kernel micro-benchmarks: us_per_call for 3 Pallas kernels vs their
pure-jnp oracles (interpret mode on CPU — relative numbers demonstrate the
harness; absolute perf is a TPU question answered by §Roofline)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, timed
from repro.kernels.masked_restore.kernel import masked_restore_pallas
from repro.kernels.masked_restore.ref import masked_restore_ref
from repro.kernels.ssd_scan.kernel import ssd_intra_pallas
from repro.kernels.ssd_scan.ref import ssd_intra_ref
from repro.kernels.sw_attention.kernel import sw_attention_pallas
from repro.kernels.sw_attention.ref import sw_attention_ref


def run(trials: int = 3, quick: bool = False) -> list[str]:
    rng = np.random.default_rng(0)
    rows = []

    a = jnp.asarray(rng.normal(size=(64, 1024)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(64, 1024)), jnp.float32)
    m = jnp.asarray(rng.random(64) < 0.5)
    refm = jax.jit(masked_restore_ref)
    _, us_ref = timed(lambda: refm(a, b, m).block_until_ready(), repeats=trials)
    _, us_krn = timed(lambda: masked_restore_pallas(a, b, m, interpret=True
                                                    ).block_until_ready(),
                      repeats=trials)
    rows.append(csv_row("kernel_masked_restore_ref", us_ref, "shape=64x1024"))
    rows.append(csv_row("kernel_masked_restore_pallas_interp", us_krn,
                        "shape=64x1024"))

    B, nc, Q, H, P, N = 1, 4, 32, 4, 16, 32
    la = -jnp.abs(jnp.asarray(rng.normal(size=(B, nc, Q, H)), jnp.float32)) * .1
    dt = jnp.abs(jnp.asarray(rng.normal(size=(B, nc, Q, H)), jnp.float32))
    x = jnp.asarray(rng.normal(size=(B, nc, Q, H, P)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, nc, Q, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, nc, Q, N)), jnp.float32)
    refs = jax.jit(ssd_intra_ref)
    _, us_ref = timed(lambda: jax.block_until_ready(refs(la, dt, x, Bm, Cm)),
                      repeats=trials)
    _, us_krn = timed(lambda: jax.block_until_ready(
        ssd_intra_pallas(la, dt, x, Bm, Cm, interpret=True)), repeats=trials)
    rows.append(csv_row("kernel_ssd_intra_ref", us_ref,
                        f"B{B}nc{nc}Q{Q}H{H}P{P}N{N}"))
    rows.append(csv_row("kernel_ssd_intra_pallas_interp", us_krn,
                        f"B{B}nc{nc}Q{Q}H{H}P{P}N{N}"))

    BH, G, S, Dh, W = 2, 2, 128, 32, 32
    q = jnp.asarray(rng.normal(size=(BH, G, S, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(BH, S, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(BH, S, Dh)), jnp.float32)
    refa = jax.jit(lambda q, k, v: sw_attention_ref(q, k, v, window=W))
    _, us_ref = timed(lambda: refa(q, k, v).block_until_ready(), repeats=trials)
    _, us_krn = timed(lambda: sw_attention_pallas(
        q, k, v, window=W, q_chunk=32, kv_chunk=32,
        interpret=True).block_until_ready(), repeats=trials)
    rows.append(csv_row("kernel_sw_attention_ref", us_ref,
                        f"BH{BH}G{G}S{S}Dh{Dh}W{W}"))
    rows.append(csv_row("kernel_sw_attention_pallas_interp", us_krn,
                        f"BH{BH}G{G}S{S}Dh{Dh}W{W}"))
    return rows
