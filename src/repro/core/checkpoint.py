"""Running checkpoint + selection strategies (paper §4.2, §4.3).

The *running checkpoint* lives in device memory (the paper's "in-memory
cache" on each PS node) and is mirrored to persistent storage asynchronously
by :mod:`repro.checkpoint_io`. It is initialized to ``x^{(0)}`` and updated
in place by partial checkpoints, so at any time it holds a mix of parameters
saved at different iterations — exactly the paper's construction.

``save_step`` is a pure jittable function: given the live params and the
current checkpoint it returns the new checkpoint plus the selected block
mask — the ``jnp.where`` fold rewrites every leaf, so it moves O(model)
bytes per save. It remains the reference semantics (and the
``FTController(inplace_save=False)`` path). The controller has two
faster, bit-equivalent save paths above it (both measured in
``bench_maintain``):

- **tree scatter** (no fabric): ``select_save_mask`` picks the mask, then
  :func:`repro.kernels.fused_maintain.ops.tree_scatter_save` scatters
  only the selected blocks into the donated checkpoint buffers —
  O(k·block_bytes), one dispatch per touched leaf;
- **arena scatter** (arena-capable fabric, the default): the checkpoint
  values live as a flat parameter arena (:mod:`repro.core.arena`) and the
  save is ONE donated tile scatter — O(k·seg_bytes) and a single dispatch
  for the whole model, which also wins on wall-clock where per-leaf
  dispatch overhead used to dominate. With **arena-resident training
  state** (the default trainer path) the scatter sources straight from
  the live arena itself — the training state IS this step's values, so
  there is no pack and no replica freshness gating; tree-stepping callers
  source from the maintenance sweep's replica arena instead (same
  values when fresh, else a one-off pack).

Selection strategies:

- PRIORITY     — top-k blocks by distance-since-last-save (paper §4.2).
- ROUND_ROBIN  — k blocks at a rotating cursor (paper §5.4 baseline).
- RANDOM       — k blocks uniformly at random (paper §5.4 baseline).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.blocks import BlockPartition, block_scores, select_blocks
from repro.core.norms import NormFn
from repro.core.policy import CheckpointPolicy, SelectionStrategy

PyTree = Any


@partial(jax.tree_util.register_dataclass,
         data_fields=["values", "saved_iter", "rr_cursor"],
         meta_fields=[])
@dataclasses.dataclass
class RunningCheckpoint:
    values: PyTree              # same structure/shapes as params
    saved_iter: jnp.ndarray     # (total_blocks,) int32 — iter each block was saved
    rr_cursor: jnp.ndarray      # () int32 — round-robin cursor


def init_running_checkpoint(params: PyTree, partition: BlockPartition) -> RunningCheckpoint:
    """Paper §4.2: the running checkpoint starts as x^{(0)}."""
    return RunningCheckpoint(
        values=jax.tree_util.tree_map(jnp.array, params),
        saved_iter=jnp.zeros((partition.total_blocks,), jnp.int32),
        rr_cursor=jnp.zeros((), jnp.int32),
    )


def _mask_from_indices(idx: jnp.ndarray, total: int) -> jnp.ndarray:
    return jnp.zeros((total,), bool).at[idx].set(True)


def select_save_mask(ckpt: RunningCheckpoint, params: PyTree, *,
                     policy: CheckpointPolicy, partition: BlockPartition,
                     norm_fn: NormFn, rng: Optional[jax.Array] = None,
                     scores: Optional[jnp.ndarray] = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Choose which blocks to save. Returns (mask, new_rr_cursor).

    ``scores`` may be precomputed (e.g. by the fabric's arena sweep);
    otherwise they are computed with ``norm_fn`` for the PRIORITY strategy.
    """
    total = partition.total_blocks
    k = partition.blocks_for_k(policy.fraction)
    if policy.strategy == SelectionStrategy.PRIORITY:
        if scores is None:
            scores = block_scores(params, ckpt.values, partition, norm_fn)
        _, idx = jax.lax.top_k(scores, k)
        return _mask_from_indices(idx, total), ckpt.rr_cursor
    if policy.strategy == SelectionStrategy.ROUND_ROBIN:
        idx = (ckpt.rr_cursor + jnp.arange(k)) % total
        return _mask_from_indices(idx, total), (ckpt.rr_cursor + k) % total
    if policy.strategy == SelectionStrategy.RANDOM:
        if rng is None:
            raise ValueError("RANDOM strategy requires an rng key")
        idx = jax.random.choice(rng, total, (k,), replace=False)
        return _mask_from_indices(idx, total), ckpt.rr_cursor
    raise ValueError(f"unknown strategy {policy.strategy}")


def save_step(ckpt: RunningCheckpoint, params: PyTree, step: jnp.ndarray, *,
              policy: CheckpointPolicy, partition: BlockPartition,
              norm_fn: NormFn, rng: Optional[jax.Array] = None,
              scores: Optional[jnp.ndarray] = None,
              ) -> tuple[RunningCheckpoint, jnp.ndarray]:
    """One partial-checkpoint update. Pure & jittable (policy/partition static).

    Returns (new_checkpoint, saved_block_mask).
    """
    mask, cursor = select_save_mask(ckpt, params, policy=policy,
                                    partition=partition, norm_fn=norm_fn,
                                    rng=rng, scores=scores)
    new_values = select_blocks(ckpt.values, params, mask, partition)
    new_saved = jnp.where(mask, jnp.int32(step), ckpt.saved_iter)
    return RunningCheckpoint(new_values, new_saved, cursor), mask


def full_save(ckpt: RunningCheckpoint, params: PyTree,
              step: jnp.ndarray) -> RunningCheckpoint:
    """Traditional full checkpoint: overwrite everything (r = 1 fast path)."""
    return RunningCheckpoint(
        values=jax.tree_util.tree_map(jnp.array, params),
        saved_iter=jnp.full_like(ckpt.saved_iter, jnp.int32(step)),
        rr_cursor=ckpt.rr_cursor,
    )
