"""Pallas TPU kernel: per-block squared-L2 distance (SCAR priority scoring).

This is SCAR's checkpoint hot loop: every ``rC`` iterations the coordinator
scores *all* parameter blocks by ``Σ (θ_i − z_i)²`` against the running
checkpoint. The kernel fuses subtract/square/reduce so each element of θ
and z is read from HBM exactly once and no (θ − z) intermediate is ever
materialized — the operation is purely memory-bound, so one-pass streaming
through VMEM is the roofline-optimal schedule.

Layout: inputs are (n_blocks, E) with E = block_rows·row_width padded to a
multiple of 128 lanes. Grid is (⌈n_blocks/BB⌉, ⌈E/BE⌉); the j axis walks
element tiles and accumulates partial sums into the (BB, 1) output
column block, which lives in VMEM across the j sweep (revisiting grid
pattern; a rank-1 (BB,) block is not a legal TPU block shape).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BB = 8      # blocks per tile (sublane-friendly)
BE = 512    # elements per tile (lanes; multiple of 128)


def _block_dist_kernel(a_ref, b_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    d = a - b
    out_ref[...] += jnp.sum(d * d, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_dist_pallas(a: jnp.ndarray, b: jnp.ndarray,
                      interpret: bool = False) -> jnp.ndarray:
    """a, b: (n_blocks, E) → (n_blocks,) f32 squared distances.

    Pads both axes to tile multiples (zero padding contributes 0).
    """
    n, e = a.shape
    n_pad = -n % BB
    e_pad = -e % BE
    if n_pad or e_pad:
        a = jnp.pad(a, ((0, n_pad), (0, e_pad)))
        b = jnp.pad(b, ((0, n_pad), (0, e_pad)))
    np_, ep_ = a.shape
    grid = (np_ // BB, ep_ // BE)
    out = pl.pallas_call(
        _block_dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BB, BE), lambda i, j: (i, j)),
            pl.BlockSpec((BB, BE), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((BB, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(a, b)
    return out[:n, 0]
