"""Shared train-step builders (used by TrainLoop and launch/dryrun).

``make_train_step`` is the classic PyTree step. ``make_arena_train_step``
is its arena-native twin: the live parameters enter and leave the step as
the flat arena (:mod:`repro.core.arena`) — decoded to the leaf-shaped
tree view at the top of the program for the forward pass, loss/grad taken
w.r.t. that tree (NOT through the decode — see the function docstring for
why), the gradient packed back to arena form in the same program, and the
optimizer run as the flat elementwise apply
(:func:`repro.optim.optimizers.arena_apply`). Jitted with donation, the
arena buffer is reused across steps and never round-trips through a
host-visible pack; the per-step fault-tolerance sweep then reads
``state.arena`` directly.

Both steps implement microbatched gradient accumulation
(``cfg.microbatch > 1``): the global batch is split into MB microbatches
processed by a ``lax.scan`` with an fp32-accumulated gradient buffer.
This is the standard memory lever for the largest dense architectures —
per-step transient activation memory scales 1/MB while keeping the same
global batch semantics.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.api import ModelOps
from repro.optim.optimizers import Optimizer, arena_apply
from repro.sharding.partition import DistContext
from repro.training.train_state import ArenaTrainState, TrainState

PyTree = Any


def make_train_step(ops: ModelOps, cfg: ModelConfig, ctx: DistContext,
                    optimizer: Optimizer):
    loss_and_grad = jax.value_and_grad(ops.train_loss)

    def train_step(state: TrainState, batch: PyTree):
        mb = max(cfg.microbatch, 1)
        if mb == 1:
            loss, grads = loss_and_grad(state.params, batch, cfg, ctx)
        else:
            def split(x):
                return x.reshape((mb, x.shape[0] // mb) + tuple(x.shape[1:]))

            mbatch = jax.tree_util.tree_map(split, batch)
            acc_dtype = jnp.dtype(cfg.opt_moment_dtype)
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dtype), state.params)

            def body(carry, bx):
                loss_sum, gacc = carry
                l, g = loss_and_grad(state.params, bx, cfg, ctx)
                gacc = jax.tree_util.tree_map(
                    lambda a, x: (a.astype(jnp.float32)
                                  + x.astype(jnp.float32)).astype(a.dtype),
                    gacc, g)
                return (loss_sum + l, gacc), None

            (loss, grads), _ = jax.lax.scan(
                body, (jnp.float32(0.0), g0), mbatch)
            loss = loss / mb
            grads = jax.tree_util.tree_map(lambda g: g / mb, grads)
        params, opt_state = optimizer.update(grads, state.opt_state,
                                             state.params)
        return TrainState(params, opt_state, state.step + 1), loss

    return train_step


def make_arena_train_step(ops: ModelOps, cfg: ModelConfig, ctx: DistContext,
                          optimizer: Optimizer, layout):
    """Arena-native train step: ``(ArenaTrainState, batch) -> (state', loss)``.

    The arena is decoded to the leaf-shaped tree view once at the top of
    the program (the model's forward pass needs shapes), the loss/grad is
    the same tree computation as :func:`make_train_step`, and the
    gradient is packed back to arena form in the same program before the
    flat elementwise optimizer apply — the whole step is one jitted
    function of ``(arena, moments) -> (arena', moments')``, meant to be
    jitted with ``donate_argnums=(0,)`` so those buffers are reused in
    place and never round-trip through a host-visible pack.

    (The grad is deliberately taken w.r.t. the *tree*, not the arena:
    differentiating through the decode would transpose each leaf's slice
    into its own full-arena scatter — ~n_leaves arena-sized buffers —
    where the explicit ``pack_arena`` of the grads is one model-sized
    pass.)

    Bit-equivalent to the PyTree step on an all-f32 model: the decode is
    a bitcast view of the stored words, ``pack_values`` of the grads is
    the f32 image of the same values the tree optimizer reads, and the
    flat apply is the same elementwise math. On mixed-precision models
    the grads/moments live in the f32 *value* domain
    (``layout.total_values`` ≥ ``total_words``) and :func:`arena_apply`
    does the decode → update → re-encode round trip one coalesced
    same-dtype run at a time; stored params round through exactly the
    tree path's ``.astype(p.dtype)``, while master moments stay f32
    (allclose to the tree path, documented in DESIGN.md).

    On a mesh (``ctx.mesh is not None``) the step is SPMD: the arena and
    adam moments carry the flat :func:`~repro.sharding.partition
    .arena_sharding` (each device owns a contiguous tile-aligned span),
    decoded leaves are constrained to the model's FSDP+TP partition
    specs, and the packed grads land on the flat sharding. The
    elementwise apply partitions exactly along the flat shards. On a
    forced-CPU mesh the sharded step is bit-equal to the PyTree step on
    the same mesh (asserted in ``tests/test_sharded_arena.py``). On a
    TPU v5e 2x2 mesh it is not: at qwen2-1.5b widths (4 layers, f32) the
    step-1 losses agree and the losses then part by up to 2.0e-5
    relative within 8 steps; at ``jax_default_matmul_precision=highest``
    the gap falls to 7.7e-8 (one f32 ulp at one of the 8 steps), so the
    default one-pass bf16 matmul precision accounts for most of it.
    Across topologies reduction order differs at ULP level as with any
    SPMD change. Only the packed result carries
    the constraint: constraining each packed part as well changes how
    XLA reduces the gradients, and the two steps then part at ULP level
    within a few steps on the CPU too.
    """
    from repro.core.arena import pack_values, unpack_arena
    from repro.sharding.partition import (arena_sharding,
                                          param_partition_specs)
    from jax.sharding import NamedSharding

    loss_and_grad = jax.value_and_grad(ops.train_loss)
    if ctx.mesh is not None:
        flat_sh = arena_sharding(ctx.mesh)

        def constrain_tree(p):
            p_shape = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), p)
            specs = param_partition_specs(p_shape, ctx)
            return jax.tree_util.tree_map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(ctx.mesh, s)), p, specs)

        def pack_grads(g):
            return pack_values(g, layout, out_sharding=flat_sh)

        def constrain_arena(a):
            # Value buffers only share the flat arena sharding when the
            # two domains coincide (all-f32 layout; mixed-dtype + mesh is
            # gated off upstream in the fabric).
            if a.size != layout.total_words:
                return a
            return jax.lax.with_sharding_constraint(a, flat_sh)
    else:
        def constrain_tree(p):
            return p

        def pack_grads(g):
            return pack_values(g, layout)

        def constrain_arena(a):
            return a

    def train_step(state: ArenaTrainState, batch: PyTree):
        params = constrain_tree(unpack_arena(state.arena, layout))
        mb = max(cfg.microbatch, 1)
        if mb == 1:
            loss, g = loss_and_grad(params, batch, cfg, ctx)
            grads = pack_grads(g)
        else:
            def split(x):
                return x.reshape((mb, x.shape[0] // mb) + tuple(x.shape[1:]))

            mbatch = jax.tree_util.tree_map(split, batch)
            acc_dtype = jnp.dtype(cfg.opt_moment_dtype)
            g0 = constrain_arena(jnp.zeros((layout.total_values,),
                                           acc_dtype))

            def body(carry, bx):
                loss_sum, gacc = carry
                l, g = loss_and_grad(params, bx, cfg, ctx)
                gacc = (gacc.astype(jnp.float32)
                        + pack_grads(g)).astype(acc_dtype)
                return (loss_sum + l, gacc), None

            (loss, gacc), _ = jax.lax.scan(
                body, (jnp.float32(0.0), g0), mbatch)
            loss = loss / mb
            grads = gacc / mb     # acc_dtype division, like the tree path
        new_arena, opt_state = arena_apply(optimizer, grads,
                                           state.opt_state, state.arena,
                                           layout)
        return ArenaTrainState(constrain_arena(new_arena), opt_state,
                               state.step + 1, state.layout), loss

    return train_step
