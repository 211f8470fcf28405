"""Pallas TPU kernels: single-pass redundancy maintenance over the arena.

The checkpoint fabric's hot loop once made three-plus independent full
passes over the live parameters every maintained step: a full-tree
replica copy, a pack-into-frames + gather + XOR parity encode (two
materialized full-model intermediates), and a third full read for PRIORITY
block scoring. The kernels here collapse that to the memory-roofline
floor over the flat parameter arena (``core/arena.py``):

``arena_maintain`` — ONE dispatch for the whole model. The grid walks the
arena's ``(8, 128)`` f32 tiles in an order sorted by parity destination,
driven by three scalar-prefetched routing tables: ``perm`` (the tile
visited at each step), ``dest`` (its compact parity tile) and ``first``
(seed vs fold). All tiles that XOR into one parity tile arrive on
consecutive grid steps, so the parity output tile is revisit-accumulated
in VMEM; each step also emits a squared-L2 score partial for PRIORITY
selection. No ``(total_blocks, frame_width)`` packed intermediate and no
``(n_groups, g, E)`` gather buffer ever exists. The kernel is an
aligned-tile f32 program: the fabric runs it only on uniform-f32 layouts
without a tail region and without a mesh, and the identical-output jnp
word sweep everywhere else (``ArenaMaintainProgram.sweep`` says which).

``arena_scatter`` — the whole-model partial save as one donated tile
scatter: the running-checkpoint arena is aliased as the output and the
grid walks only the selected tiles.

``scatter_save`` — the same in-place write over one leaf's raw row matrix
(the tree-path save): the grid walks only the ``k`` selected blocks
(scalar-prefetched row ids), so saving ``k`` blocks moves
``O(k · block_bytes)`` — never the full leaf. Unvisited rows are never
DMA'd and keep their previous contents (the §4.3 running checkpoint is a
mutable mix of iterations by construction).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BE = 512    # lanes per scatter_save column tile (multiple of 128)
LANES = 128
SCORE_SLOTS = 8 * LANES   # per-step scores held by one (8, 128) output tile
# grid steps per call of a sweep whose routing is scalar-prefetched: three
# int32 tables of this length take 384 KiB of the 1 MiB of SMEM
SMEM_STEPS = 1 << 15


def _put_score(sc_ref, s, val):
    """Store the scalar ``val`` of grid step ``s`` into the lane-dense
    ``(8, 128)`` score tile that holds steps ``[s - s % 1024, +1024)``.

    Consecutive steps revisit the same output tile, which stays in VMEM
    until the index map moves on; one 4-byte slot per step keeps the
    score output at 1/1024 of the swept words, where a ``(1, 1)`` block
    per step is not a legal TPU block and a ``(T, 1)`` array would pad
    every slot to a full 128-lane row in HBM."""
    pos = s % SCORE_SLOTS

    @pl.when(pos == 0)
    def _init():
        sc_ref[...] = jnp.zeros_like(sc_ref)

    rows = jax.lax.broadcasted_iota(jnp.int32, sc_ref.shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, sc_ref.shape, 1)
    hit = (rows == pos // LANES) & (lanes == pos % LANES)
    sc_ref[...] = jnp.where(hit, val, sc_ref[...])


def _score_rows(n_steps: int) -> int:
    """Rows of the lane-dense score output for ``n_steps`` grid steps."""
    return -(-max(n_steps, 1) // SCORE_SLOTS) * 8


# ---------------------------------------------------------------------------
# arena_maintain: parity XOR + priority scores over the flat arena,
# ONE dispatch for the whole model (not one per leaf)
# ---------------------------------------------------------------------------

# (8, 128) f32 sublane tile of the 2D-retiled arena — the single source
# of truth is the arena layout module; desyncing block shapes from the
# block table would corrupt routing silently
from repro.core.arena import ARENA_LANES, ARENA_SUBLANES  # noqa: E402


def _arena_maintain_kernel(perm_ref, dest_ref, first_ref, x_ref, z_ref,
                           sc_ref, par_ref):
    s = pl.program_id(0)
    x = x_ref[...]                               # (8, 128) f32 arena tile
    d = x - z_ref[...]
    _put_score(sc_ref, s, jnp.sum(d * d))        # per-tile score partial
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)

    @pl.when(first_ref[s] == 1)
    def _init():                                 # first member tile: seed
        par_ref[...] = bits

    @pl.when(first_ref[s] == 0)
    def _fold():                                 # later member tile: fold
        par_ref[...] ^= bits


def _arena_maintain_call(x2d, z2d, perm, dest, first, n_dest_tiles,
                         interpret):
    t = perm.shape[0]
    br = ARENA_SUBLANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((br, ARENA_LANES), lambda s, p, d, f: (p[s], 0)),
            pl.BlockSpec((br, ARENA_LANES), lambda s, p, d, f: (p[s], 0)),
        ],
        out_specs=[
            pl.BlockSpec((8, ARENA_LANES),
                         lambda s, p, d, f: (s // SCORE_SLOTS, 0)),
            pl.BlockSpec((br, ARENA_LANES), lambda s, p, d, f: (d[s], 0)),
        ],
    )
    sc, par = pl.pallas_call(
        _arena_maintain_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((_score_rows(t), ARENA_LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_dest_tiles * br, ARENA_LANES), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(perm), jnp.asarray(dest), jnp.asarray(first), x2d, z2d)
    return sc.reshape(-1)[:t], par


def _dest_chunks(first: np.ndarray) -> list[tuple[int, int]]:
    """Split ``[0, T)`` into runs of at most ``SMEM_STEPS`` steps that
    start where a destination starts, so no parity tile's contributors
    straddle two calls."""
    t = first.size
    starts = np.nonzero(first)[0]
    out, lo = [], 0
    while lo < t:
        hi = t
        if t - lo > SMEM_STEPS:
            hi = int(starts[np.searchsorted(starts, lo + SMEM_STEPS,
                                            side="right") - 1])
            assert hi > lo, "a parity tile has more contributors than fit"
        out.append((lo, hi))
        lo = hi
    return out


def arena_maintain_pallas(x2d: jnp.ndarray, z2d: jnp.ndarray,
                          perm: np.ndarray, dest: np.ndarray,
                          first: np.ndarray, n_dest_tiles: int,
                          interpret: bool = False,
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One maintenance sweep over the whole 2D-retiled arena.

    x2d, z2d: ``(R, 128)`` float32 — live (replica) arena and running-
    checkpoint arena, ``R`` a multiple of 8. The grid walks ``(8, 128)``
    sublane-aligned tiles in an order sorted by parity destination. The
    routing is host-resident (static per striping):

    perm:  (T,) int32 — arena tile visited at grid step ``s`` (all tiles
           XOR-ing into one parity tile arrive consecutively).
    dest:  (T,) int32 — compact parity output tile per sorted step.
    first: (T,) int32 — 1 at the first step of its destination (seed vs
           fold: the parity tile is revisit-accumulated in VMEM).

    The routing tables are scalar-prefetched into SMEM, which holds
    ``SMEM_STEPS`` steps of them; a longer sweep is cut at destination
    starts into several calls in the same program, each writing its own
    contiguous range of parity tiles.

    Returns ``(sc (T,) f32 per-step score partials, par
    (n_dest_tiles·8, 128) int32 compact parity tiles)``. The caller
    segment-sums ``sc`` by block id and scatters ``par`` into the
    ``(n_groups, frame_elems)`` codec layout (both O(output) epilogues).
    """
    perm, dest, first = (np.asarray(a, np.int32) for a in (perm, dest, first))
    if perm.size == 0:
        return (jnp.zeros((0,), jnp.float32),
                jnp.zeros((n_dest_tiles * ARENA_SUBLANES, ARENA_LANES),
                          jnp.int32))
    scs, pars = [], []
    for lo, hi in _dest_chunks(first):
        d0, d1 = int(dest[lo]), int(dest[hi - 1]) + 1
        sc, par = _arena_maintain_call(x2d, z2d, perm[lo:hi],
                                       dest[lo:hi] - d0, first[lo:hi],
                                       d1 - d0, interpret)
        scs.append(sc)
        pars.append(par)
    if len(scs) == 1:
        return scs[0], pars[0]
    return jnp.concatenate(scs), jnp.concatenate(pars)


# ---------------------------------------------------------------------------
# arena_scatter: in-place partial save over the flat arena, ONE dispatch
# ---------------------------------------------------------------------------

def _arena_scatter_kernel(tiles_ref, src_ref, dst_ref, out_ref):
    del tiles_ref, dst_ref                       # routing/alias only
    out_ref[...] = src_ref[...]


def arena_scatter_pallas(dst2d: jnp.ndarray, src2d: jnp.ndarray,
                         tiles: jnp.ndarray,
                         interpret: bool = False) -> jnp.ndarray:
    """Copy the selected ``(8, 128)`` tiles of ``src2d`` into ``dst2d``
    in place (``dst2d`` donated/aliased — unselected tiles are never
    DMA'd). ``tiles``: (k,) int32 tile indices, duplicates idempotent
    (bucket padding). The whole-model partial save is one program: the
    tile list is scalar-prefetched ``SMEM_STEPS`` at a time, each call
    aliasing the previous one's output — the per-leaf ``scatter_save``
    launched one program per touched leaf.
    """
    br = ARENA_SUBLANES
    out = dst2d
    for lo in range(0, tiles.shape[0], SMEM_STEPS):
        part = tiles[lo:lo + SMEM_STEPS]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(part.shape[0],),
            in_specs=[
                pl.BlockSpec((br, ARENA_LANES), lambda i, t: (t[i], 0)),
                pl.BlockSpec(memory_space=pl.ANY),     # aliased, untouched
            ],
            out_specs=pl.BlockSpec((br, ARENA_LANES), lambda i, t: (t[i], 0)),
        )
        out = pl.pallas_call(
            _arena_scatter_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(dst2d.shape, dst2d.dtype),
            input_output_aliases={2: 0},         # dst (after scalars) -> out
            interpret=interpret,
        )(part, src2d, out)
    return out


def _scatter_save_kernel(rows_ref, src_ref, dst_ref, out_ref):
    del rows_ref, dst_ref                        # routing/alias only
    out_ref[...] = src_ref[...]


def scatter_save_pallas(dst: jnp.ndarray, src: jnp.ndarray,
                        rows: jnp.ndarray, block_rows: int,
                        interpret: bool = False) -> jnp.ndarray:
    """In-place block scatter over a leaf's raw row matrix.

    dst, src: (R, W) — the leaf reshaped to (rows, row_width), NOT the
    zero-padded block view (padding would materialize a full copy and
    defeat the O(k) goal). rows: (k,) int32 selected *block* ids
    (duplicates are idempotent — callers pad short selections with
    repeats). Block ``b`` covers dst rows ``[b·block_rows, (b+1)·block_rows)``;
    the ragged tail block is handled by Pallas's partial-block masking.

    ``dst`` is donated and aliased to the output, so unselected rows are
    never read or written — saving ``k`` blocks moves ``O(k·block_bytes)``.
    """
    r, w = dst.shape
    k = rows.shape[0]
    br = min(block_rows, r)
    bw = min(BE, w)
    jt = -(-w // bw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, jt),
        in_specs=[
            pl.BlockSpec((br, bw), lambda i, j, rows: (rows[i], j)),
            pl.BlockSpec(memory_space=pl.ANY),     # aliased, untouched
        ],
        out_specs=pl.BlockSpec((br, bw), lambda i, j, rows: (rows[i], j)),
    )
    return pl.pallas_call(
        _scatter_save_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, w), dst.dtype),
        input_output_aliases={2: 0},             # dst (after scalars) -> out
        interpret=interpret,
    )(rows, src, dst)
