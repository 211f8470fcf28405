"""Deterministic block partition of a parameter PyTree.

The paper partitions model parameters across PS nodes "uniformly at random"
at row granularity (§5.1: rows of the MLR matrix, rows of L / columns of R
for MF, document-topic rows for LDA, layer/shard tensors for the CNN).

In the SPMD adaptation, the unit of loss/checkpoint/priority is a **block**:
``block_rows`` consecutive leading-dim rows of each leaf (TPU-aligned, 128 by
default). A ``BlockPartition`` is the static (host-side) description of that
blocking; every runtime operation over blocks (distance scoring, masked
restore, failure injection) is a pure jittable function parameterized by it.

Layout per leaf ``x`` of shape ``(d0, d1, ..., dn)``:
  rows      = d0              (ndim ≥ 1; scalars are treated as 1 row)
  row_width = prod(d1..dn)
  n_blocks  = ceil(rows / block_rows)
Blocks of a leaf are contiguous row groups; global block ids concatenate
leaves in flatten order. Padding rows (to fill the last block) are zeros on
both sides of any distance computation, so they never affect scores.

A leaf whose natural block would exceed ``MAX_BLOCK_ELEMS`` (a
layer-stacked ``(n_layers, d_ff, d_model)`` weight has fewer rows than
``block_rows``, so its one block is the whole stack) is viewed with
narrower rows instead: ``row_width`` becomes the largest divisor ``w`` of
``prod(d1..dn)`` with ``block_rows · w <= MAX_BLOCK_ELEMS``, and ``rows``
grows to match (:func:`leaf_view_shape`). Each view row lies inside one
natural row, so blocks stay contiguous. Parity frames are as wide as the
widest block (``fabric/parity.py``), so one whole-stack block would size
every group's frame to the stack.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LeafMeta:
    name: str
    shape: tuple[int, ...]
    dtype: Any
    rows: int
    row_width: int
    n_blocks: int
    offset: int            # global block-id offset of this leaf's first block


@dataclasses.dataclass(frozen=True)
class BlockPartition:
    block_rows: int
    leaves: tuple[LeafMeta, ...]
    treedef: Any

    @property
    def total_blocks(self) -> int:
        # colocated leaves share offsets, so count by extent not by sum
        return max(l.offset + l.n_blocks for l in self.leaves)

    @property
    def total_params(self) -> int:
        return sum(int(np.prod(l.shape)) if l.shape else 1 for l in self.leaves)

    def leaf_slices(self) -> list[tuple[int, int]]:
        """[(start, end)] global block-id ranges per leaf, in flatten order."""
        return [(l.offset, l.offset + l.n_blocks) for l in self.leaves]

    def blocks_for_k(self, fraction: float) -> int:
        """Number of blocks in a fraction-r checkpoint (ceil, >= 1)."""
        return max(1, math.ceil(fraction * self.total_blocks))


# Widest block, in elements: 1 MiB of f32, 512 KiB of bf16. Wide enough
# that no leaf of the reduced configs is cut; a published-width LM's
# stacked layers are cut to blocks the size of its embedding's.
MAX_BLOCK_ELEMS = 1 << 18


@functools.lru_cache(maxsize=None)
def leaf_view_shape(shape: tuple, block_rows: int) -> tuple[int, int]:
    """``(rows, row_width)`` 2-D view of a leaf of this shape — the unit
    every block op reshapes to (see the module docstring)."""
    if not shape:
        return 1, 1
    rows, width = int(shape[0]), int(np.prod(shape[1:]))
    if min(rows, block_rows) * width <= MAX_BLOCK_ELEMS:
        return rows, width
    limit = max(MAX_BLOCK_ELEMS // block_rows, 1)
    best = 1
    for d in range(1, math.isqrt(width) + 1):
        if width % d == 0:
            for c in (d, width // d):
                if best < c <= limit:
                    best = c
    if best < 128:       # no lane-wide cut: keep the natural rows
        return rows, width
    return rows * (width // best), best


def _leaf_name(path) -> str:
    return jax.tree_util.keystr(path)


def partition_pytree(params: PyTree, block_rows: int = 128,
                     colocate: tuple = ()) -> BlockPartition:
    """Build the static block partition for ``params``.

    Works on concrete arrays or ShapeDtypeStructs (no data access).

    ``colocate``: top-level keys whose subtrees share block ids with each
    other (matching by the remaining path). This models the parameter-
    server reality that optimizer state lives WITH its parameters — a
    failed partition loses a weight block *and its Adam moments together*,
    and partial recovery restores them together. Without colocation, a
    partial restore could mix a new weight with stale moments (which makes
    adaptive optimizers diverge — measured in EXPERIMENTS.md §Repro).
    E.g. state = {"net": ..., "mu": ..., "nu": ...} with
    colocate=("net", "mu", "nu"): mu's and nu's leaves reuse net's blocks.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = []
    offset = 0
    canonical_offsets: dict = {}
    for path, x in flat:
        shape = tuple(x.shape)
        rows, row_width = leaf_view_shape(shape, block_rows)
        n_blocks = max(1, math.ceil(rows / block_rows))
        name = _leaf_name(path)
        leaf_offset = offset
        if colocate and path and getattr(path[0], "key", None) in colocate:
            canon = jax.tree_util.keystr(tuple(path[1:]))
            if canon in canonical_offsets:
                leaf_offset, prev_blocks = canonical_offsets[canon]
                assert prev_blocks == n_blocks, (
                    f"colocated leaf {name} has {n_blocks} blocks, "
                    f"group has {prev_blocks}")
            else:
                canonical_offsets[canon] = (offset, n_blocks)
                offset += n_blocks
        else:
            offset += n_blocks
        leaves.append(LeafMeta(
            name=name, shape=shape, dtype=x.dtype, rows=rows,
            row_width=row_width, n_blocks=n_blocks, offset=leaf_offset))
    return BlockPartition(block_rows=block_rows, leaves=tuple(leaves),
                          treedef=treedef)


# ---------------------------------------------------------------------------
# Runtime (jittable) block ops
# ---------------------------------------------------------------------------

def leaf_frame_width(leaf: LeafMeta, block_rows: int) -> int:
    """Payload elements per block of this leaf — the width of its
    :func:`leaf_block_view` rows (single-block leaves are unpadded), and
    therefore the per-block payload of both the parity frames and the
    flat parameter arena (which zero-pad it to their own alignments)."""
    if leaf.n_blocks == 1:
        return max(leaf.rows, 1) * max(leaf.row_width, 1)
    return block_rows * leaf.row_width


def word_packable(dtype) -> bool:
    """True when ``dtype`` values are stored in arena/frame words as raw
    bit patterns: 1/2/4-byte ints and floats (f32, bf16, f16, the fp8
    family, int8/16/32, uint8/16/32). Everything else (f64, int64,
    complex, bool) falls back to the legacy f32-image convention — one
    word per element, value cast through float32."""
    dt = np.dtype(dtype)
    # ml_dtypes types (bfloat16, the fp8 family) register as numpy kind
    # 'V' (void) but are plain fixed-width bit patterns like any other
    # int/float, so admit them alongside the native f/i/u kinds. True
    # void/structured dtypes never appear as pytree leaves here.
    return dt.kind in "fiuV" and dt.itemsize in (1, 2, 4)


def dtype_word_ratio(dtype) -> int:
    """Elements per 32-bit word: 1 (f32/i32), 2 (bf16/f16/i16), 4
    (fp8/i8). Non-word-packable dtypes use the f32-image convention, so
    one element per word."""
    dt = np.dtype(dtype)
    return 4 // dt.itemsize if word_packable(dt) else 1


def leaf_word_width(leaf: LeafMeta, block_rows: int) -> int:
    """Payload 32-bit *words* per block of this leaf: its
    :func:`leaf_frame_width` elements bit-packed ``dtype_word_ratio``
    per word (sub-word tail padded with zero bits)."""
    r = dtype_word_ratio(leaf.dtype)
    return -(-leaf_frame_width(leaf, block_rows) // r)


def _field_bits(words: jnp.ndarray, r: int) -> list[jnp.ndarray]:
    """The ``r`` unsigned bit fields of each int32 word, low bits first.

    Shifts and masks on the words themselves: a bitcast to a trailing
    axis of 2 or 4 elements would give the TPU an array whose minor
    dimension is padded to 128 lanes (64× the bytes)."""
    bits = 32 // r
    udt = jnp.uint16 if r == 2 else jnp.uint8
    return [(jax.lax.shift_right_logical(words, k * bits)
             & ((1 << bits) - 1)).astype(udt) for k in range(r)]


def word_fields(words: jnp.ndarray, dtype) -> list[jnp.ndarray]:
    """The ``dtype_word_ratio`` elements packed in each int32 word, as
    that many arrays shaped like ``words`` (field ``k`` holds element
    ``k`` of every word), in ``dtype``."""
    dt = np.dtype(dtype)
    r = dtype_word_ratio(dt)
    if r == 1:
        return [words if dt == np.dtype(np.int32)
                else jax.lax.bitcast_convert_type(words, dt)]
    return [jax.lax.bitcast_convert_type(f, dt)
            for f in _field_bits(words, r)]


def words_to_elems(words: jnp.ndarray, dtype) -> jnp.ndarray:
    """``(..., W)`` int32 words → ``(..., W·r)`` elements of ``dtype`` in
    element order (the inverse of :func:`elems_to_words`). The fields
    interleave as raw bits, so NaN payloads survive."""
    dt = np.dtype(dtype)
    r = dtype_word_ratio(dt)
    if r == 1:
        return word_fields(words, dt)[0]
    fields = _field_bits(words, r)
    out = jnp.zeros(words.shape[:-1] + (words.shape[-1] * r,),
                    fields[0].dtype)
    for k, f in enumerate(fields):
        out = out.at[..., k::r].set(f)
    return jax.lax.bitcast_convert_type(out, dt)


def fields_to_words(fields: list[jnp.ndarray]) -> jnp.ndarray:
    """Inverse of :func:`word_fields`: ``r`` same-shaped arrays of one
    word-packable dtype → int32 words, field ``k`` in bits
    ``[k·32/r, (k+1)·32/r)``."""
    r = len(fields)
    if r == 1:
        f = fields[0]
        return f if f.dtype == jnp.int32 \
            else jax.lax.bitcast_convert_type(f, jnp.int32)
    bits = 32 // r
    udt = jnp.uint16 if r == 2 else jnp.uint8
    out = None
    for k, f in enumerate(fields):
        w = jax.lax.bitcast_convert_type(f, udt).astype(jnp.int32) \
            << (k * bits)
        out = w if out is None else out | w
    return out


def elems_to_words(elems: jnp.ndarray) -> jnp.ndarray:
    """``(..., W·r)`` word-packable elements → ``(..., W)`` int32 words,
    ``r = dtype_word_ratio`` consecutive elements per word, element 0 in
    the low-order bits (numpy's little-endian ``.view(int32)``)."""
    r = dtype_word_ratio(elems.dtype)
    return fields_to_words([elems[..., k::r] for k in range(r)])


def leaf_block_words(x: jnp.ndarray, block_rows: int) -> jnp.ndarray:
    """(n_blocks, payload_words) int32 raw bit pattern of a leaf's blocks.

    Word-packable dtypes pack ``dtype_word_ratio`` consecutive elements
    per word, element 0 in the low-order bytes — the same packing as a
    numpy ``.view(int32)`` on little-endian hosts (property-tested in
    ``tests/test_quant_arena.py``). Other dtypes store one f32 image per
    word, the historical frames convention.
    """
    r = dtype_word_ratio(x.dtype)
    if not word_packable(x.dtype):
        x = x.astype(jnp.float32)
    view = leaf_block_view(x, block_rows)
    if r > 1:
        tail = -view.shape[1] % r
        if tail:
            view = jnp.pad(view, ((0, 0), (0, tail)))
    return elems_to_words(view)


def decode_block_words(words: jnp.ndarray, leaf: LeafMeta,
                       block_rows: int) -> jnp.ndarray:
    """Inverse of :func:`leaf_block_words`: ``(n_blocks, >= payload_words)``
    int32 words back to the leaf-shaped array — bit-exact for
    word-packable dtypes, a value cast through f32 otherwise."""
    dt = np.dtype(leaf.dtype)
    elems = leaf_frame_width(leaf, block_rows)
    r = dtype_word_ratio(dt)
    pw = -(-elems // r)
    words = words[:, :pw]
    if not word_packable(dt):
        vals = jax.lax.bitcast_convert_type(words, jnp.float32)
    else:
        vals = words_to_elems(words, dt)
    vals = vals[:, :elems]
    rows = max(leaf.rows, 1)
    vals = vals.reshape(-1, max(leaf.row_width, 1))[:rows]
    if leaf.shape and leaf.rows != leaf.shape[0]:
        # a cut leaf: keep the two reshapes apart (see leaf_block_view)
        vals = jax.lax.optimization_barrier(vals)
    return vals.reshape(leaf.shape).astype(leaf.dtype)


def leaf_block_view(x: jnp.ndarray, block_rows: int) -> jnp.ndarray:
    """Reshape a leaf to (n_blocks, elems_per_block), zero-padded.

    Single-block leaves (rows <= block_rows) are returned unpadded as
    (1, rows·row_width) — padding a 2-row layer-stacked leaf out to 128
    rows would be a 64× memory/compute blowup for zero benefit. Consumers
    reduce within blocks, so per-leaf block widths may differ.
    """
    rows, row_width = leaf_view_shape(tuple(x.shape), block_rows)
    flat = x.reshape(rows, row_width)
    if x.ndim and rows != x.shape[0]:
        # A cut leaf: leaf -> rows -> blocks are two relayouts on a TPU.
        # Fused into one, the v5e compiler spends over a minute on it for
        # a (4, 1536, 8960) bf16 leaf (80.8 s, against 2.8 s apart, on a
        # CPU host); the barrier keeps them apart and leaves every
        # program's device memory as it was.
        flat = jax.lax.optimization_barrier(flat)
    n_blocks = max(1, math.ceil(rows / block_rows))
    if n_blocks == 1:
        return flat.reshape(1, rows * row_width)
    pad = n_blocks * block_rows - rows
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    return flat.reshape(n_blocks, block_rows * row_width)


def split_global_mask(mask: jnp.ndarray, partition: BlockPartition) -> list[jnp.ndarray]:
    """Split a (total_blocks,) vector into per-leaf (n_blocks,) segments."""
    return [mask[l.offset:l.offset + l.n_blocks] for l in partition.leaves]


def expand_block_mask(block_mask: jnp.ndarray, leaf: LeafMeta,
                      block_rows: int) -> jnp.ndarray:
    """(n_blocks,) bool -> bool array broadcastable to the leaf shape.

    Expands over rows then broadcasts across trailing dims. A leaf viewed
    with narrower rows than its leading axis (:func:`leaf_view_shape`)
    gets a full leaf-shaped mask.
    """
    row_mask = jnp.repeat(block_mask, block_rows)[:leaf.rows]
    if len(leaf.shape) == 0:
        return row_mask[0]
    if leaf.rows != leaf.shape[0]:
        return jnp.broadcast_to(row_mask[:, None],
                                (leaf.rows, leaf.row_width)).reshape(
                                    leaf.shape)
    return row_mask.reshape((leaf.rows,) + (1,) * (len(leaf.shape) - 1))


def select_blocks(dst: PyTree, src: PyTree, global_mask: jnp.ndarray,
                  partition: BlockPartition) -> PyTree:
    """Per-block select: where mask is True take ``src``'s block, else ``dst``.

    This is the primitive behind both partial recovery (dst=live params,
    src=checkpoint, mask=lost blocks) and partial checkpoint save
    (dst=checkpoint values, src=live params, mask=selected blocks).
    """
    dst_flat = jax.tree_util.tree_leaves(dst)
    src_flat = jax.tree_util.tree_leaves(src)
    masks = split_global_mask(global_mask, partition)
    out = []
    for d, s, m, leaf in zip(dst_flat, src_flat, masks, partition.leaves):
        em = expand_block_mask(m, leaf, partition.block_rows)
        out.append(jnp.where(em, s, d))
    return jax.tree_util.tree_unflatten(partition.treedef, out)


def block_scores(a: PyTree, b: PyTree, partition: BlockPartition,
                 norm_fn: Callable[[jnp.ndarray, jnp.ndarray, LeafMeta], jnp.ndarray],
                 ) -> jnp.ndarray:
    """Per-block distance scores between two pytrees -> (total_blocks,) f32.

    ``norm_fn(a_view, b_view, leaf)`` maps two (n_blocks, block_elems) views
    to per-block scores; see :mod:`repro.core.norms`. Colocated leaves
    (shared offsets) accumulate into the same slots.
    """
    a_flat = jax.tree_util.tree_leaves(a)
    b_flat = jax.tree_util.tree_leaves(b)
    out = jnp.zeros((partition.total_blocks,), jnp.float32)
    for xa, xb, leaf in zip(a_flat, b_flat, partition.leaves):
        va = leaf_block_view(xa.astype(jnp.float32), partition.block_rows)
        vb = leaf_block_view(xb.astype(jnp.float32), partition.block_rows)
        s = norm_fn(va, vb, leaf).astype(jnp.float32)
        out = jax.lax.dynamic_update_slice(
            out, jax.lax.dynamic_slice(out, (leaf.offset,),
                                       (leaf.n_blocks,)) + s,
            (leaf.offset,))
    return out


# The two norms below are jitted so that the f32 difference of a leaf is
# reduced as it is formed: run op by op, each leaf would hold two f32
# copies and their difference at once (2.7 GB for a 151,936-row vocab's
# embedding at d_model 1536), on the recovery path where the device is
# fullest.
@functools.partial(jax.jit, static_argnums=(3,))
def masked_sq_norm(a: PyTree, b: PyTree, global_mask: jnp.ndarray,
                   partition: BlockPartition) -> jnp.ndarray:
    """||(a − b) restricted to masked blocks||² — the δ' of Theorem 4.1."""
    def sq(va, vb, leaf):
        return jnp.sum((va - vb) ** 2, axis=-1)
    per_block = block_scores(a, b, partition, sq)
    return jnp.sum(jnp.where(global_mask, per_block, 0.0))


@jax.jit
def tree_sq_norm(a: PyTree, b: PyTree) -> jnp.ndarray:
    """||a − b||² over the whole tree — the δ of full recovery."""
    diffs = jax.tree_util.tree_map(
        lambda x, y: jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2), a, b)
    return jax.tree_util.tree_reduce(jnp.add, diffs, jnp.float32(0.0))
