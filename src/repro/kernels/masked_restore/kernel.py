"""Pallas TPU kernel: fused masked block restore (SCAR partial recovery).

On recovery, the lost blocks take the checkpoint's values and survivors
keep their live values: ``out[b] = mask[b] ? src[b] : dst[b]``. Fusing the
select avoids materializing a full-size expanded boolean mask (the jnp
path builds a (rows, 1)-broadcast bool per leaf) and performs exactly one
HBM read per input element and one write — memory-roofline optimal.

Grid/layout: (n_blocks, E) tiles of (BB, BE), the element axis inner;
the mask rides along the i axis as a (BB, 1) int32 column block (a
rank-1 (BB,) block is not a legal TPU block shape).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BB = 8
BE = 512


def _masked_restore_kernel(dst_ref, src_ref, mask_ref, out_ref):
    sel = mask_ref[...] > 0                  # (BB, 1)
    out_ref[...] = jnp.where(sel, src_ref[...], dst_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_restore_pallas(dst: jnp.ndarray, src: jnp.ndarray,
                          mask: jnp.ndarray,
                          interpret: bool = False) -> jnp.ndarray:
    """dst, src: (n_blocks, E); mask: (n_blocks,) bool → (n_blocks, E)."""
    n, e = dst.shape
    n_pad = -n % BB
    e_pad = -e % BE
    mask_i = mask.astype(jnp.int32).reshape(n, 1)
    if n_pad or e_pad:
        dst = jnp.pad(dst, ((0, n_pad), (0, e_pad)))
        src = jnp.pad(src, ((0, n_pad), (0, e_pad)))
        mask_i = jnp.pad(mask_i, ((0, n_pad), (0, 0)))
    np_, ep_ = dst.shape
    grid = (np_ // BB, ep_ // BE)
    out = pl.pallas_call(
        _masked_restore_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BB, BE), lambda i, j: (i, j)),
            pl.BlockSpec((BB, BE), lambda i, j: (i, j)),
            pl.BlockSpec((BB, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BB, BE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, ep_), dst.dtype),
        interpret=interpret,
    )(dst, src, mask_i)
    return out[:n, :e]
