"""Pure-numpy oracles for the fused_maintain kernel family."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def arena_maintain_ref(x2d: jnp.ndarray, z2d: jnp.ndarray,
                       tile_dest: np.ndarray, n_dest_tiles: int,
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for the arena sweep: per-tile score partials (natural tile
    order) and compact parity tiles (XOR of the f32 bit patterns of every
    ``(8, 128)`` tile routed to the same destination).

    ``tile_dest[t]`` is the compact parity tile index arena tile ``t``
    folds into (natural order, unlike the kernel's sorted ``perm``/
    ``dest`` encoding)."""
    from repro.core.arena import ARENA_LANES, ARENA_SUBLANES, ARENA_TILE
    words = x2d.shape[0] * x2d.shape[1]
    n_tiles = words // ARENA_TILE
    xt = np.asarray(x2d, np.float32).reshape(n_tiles, ARENA_TILE)
    zt = np.asarray(z2d, np.float32).reshape(n_tiles, ARENA_TILE)
    partials = ((xt - zt) ** 2).sum(axis=1)
    bits = xt.view(np.int32)
    par = np.zeros((n_dest_tiles, ARENA_TILE), np.int32)
    for t, d in enumerate(np.asarray(tile_dest)):
        par[int(d)] ^= bits[t]
    return (jnp.asarray(partials, jnp.float32),
            jnp.asarray(par.reshape(n_dest_tiles * ARENA_SUBLANES,
                                    ARENA_LANES)))


def arena_scatter_ref(dst2d: jnp.ndarray, src2d: jnp.ndarray,
                      tiles: np.ndarray) -> jnp.ndarray:
    """Oracle for the arena tile scatter."""
    from repro.core.arena import ARENA_SUBLANES as SL
    out = np.array(dst2d)
    src = np.asarray(src2d)
    for t in np.asarray(tiles):
        out[int(t) * SL:(int(t) + 1) * SL] = src[int(t) * SL:(int(t) + 1) * SL]
    return jnp.asarray(out)


def scatter_save_ref(dst: jnp.ndarray, src: jnp.ndarray,
                     rows: np.ndarray, block_rows: int) -> jnp.ndarray:
    """Oracle for the in-place block scatter: ``dst`` with the selected
    blocks' rows overwritten from ``src`` (row-matrix layout)."""
    out = np.array(dst)
    src = np.asarray(src)
    n_rows = out.shape[0]
    for b in np.asarray(rows):
        lo = int(b) * block_rows
        hi = min(lo + block_rows, n_rows)
        out[lo:hi] = src[lo:hi]
    return jnp.asarray(out)
