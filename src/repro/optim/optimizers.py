"""Minimal functional optimizers (optax-free: the container is offline and
the framework owns its substrate per the brief).

Each optimizer is ``init(params) -> state`` + ``update(grads, state, params)
-> (new_params, new_state)``. Optimizer state tensors mirror the parameter
pytree so SCAR block partitioning / sharding specs apply unchanged. Adam
moments are fp32 regardless of param dtype (TPU practice).

**Arena-native apply**: every optimizer here is elementwise, so the same
``update`` applies unchanged to the flat parameter arena
(:mod:`repro.core.arena`) — the arena is a one-leaf pytree and the moment
buffers become flat mirrors of it in the f32 *value* domain
(``(total_values,)``, master moments stay f32 whatever the stored
precision). :func:`arena_apply` wraps that call with the one step the
flat form can't express on its own: the dtype round trip. The word
arena stores raw leaf-dtype bit patterns, so the step is decode → f32
update → re-encode, one slice/bitcast per *coalesced same-dtype run*
(``layout.value_runs()``), never per segment. For an all-f32 layout the
decode/encode are the identity and the whole thing collapses to a bare
``optimizer.update`` on the arena — bit-identical to the historical f32
value-arena apply and to the per-leaf tree apply. Mixed-precision
layouts match the tree path's ``.astype(p.dtype)`` rounding exactly on
stored params; moments differ from the tree path only where the tree
path would also have quantized them (we keep them f32 — strictly less
perturbation, covered by the paper's Thm 3.2 self-correction class).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

PyTree = Any


class OptState(NamedTuple):
    step: jnp.ndarray
    mu: PyTree        # first moment (or momentum buffer); None-like zeros for sgd
    nu: PyTree        # second moment; zeros for sgd/momentum


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[PyTree], OptState]
    update: Callable[[PyTree, OptState, PyTree], tuple[PyTree, OptState]]
    name: str = "opt"


# ``zeros_like`` (not ``zeros(shape)``) keeps a mesh-sharded param's
# sharding: plain ``zeros`` would put every moment on the default device
def _zeros_like_f32(params):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x, dtype=jnp.float32), params)


def sgd(lr: float) -> Optimizer:
    def init(params):
        return OptState(jnp.zeros((), jnp.int32), (), ())

    def update(grads, state, params):
        new = jax.tree_util.tree_map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return new, OptState(state.step + 1, (), ())
    return Optimizer(init, update, "sgd")


def momentum(lr: float, beta: float = 0.9) -> Optimizer:
    def init(params):
        return OptState(jnp.zeros((), jnp.int32), _zeros_like_f32(params), ())

    def update(grads, state, params):
        mu = jax.tree_util.tree_map(
            lambda m, g: beta * m + g.astype(jnp.float32), state.mu, grads)
        new = jax.tree_util.tree_map(
            lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype),
            params, mu)
        return new, OptState(state.step + 1, mu, ())
    return Optimizer(init, update, "momentum")


def adam(lr: float, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8, moment_dtype=jnp.float32) -> Optimizer:
    return _adam_like(lr, b1, b2, eps, wd=0.0, name="adam",
                      moment_dtype=moment_dtype)


def adamw(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          wd: float = 0.01, moment_dtype=jnp.float32) -> Optimizer:
    # moment_dtype=jnp.bfloat16 halves optimizer-state HBM -- the
    # production lever for the largest (400B-class) architectures.
    return _adam_like(lr, b1, b2, eps, wd=wd, name="adamw",
                      moment_dtype=moment_dtype)


def _adam_like(lr, b1, b2, eps, wd, name, moment_dtype=jnp.float32) -> Optimizer:
    def _zeros_like_m(params):
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros_like(x, dtype=moment_dtype), params)

    def init(params):
        return OptState(jnp.zeros((), jnp.int32),
                        _zeros_like_m(params), _zeros_like_m(params))

    def update(grads, state, params):
        t = state.step + 1
        tf = t.astype(jnp.float32)
        mu = jax.tree_util.tree_map(
            lambda m, g: (b1 * m.astype(jnp.float32)
                          + (1 - b1) * g.astype(jnp.float32)
                          ).astype(moment_dtype), state.mu, grads)
        nu = jax.tree_util.tree_map(
            lambda v, g: (b2 * v.astype(jnp.float32)
                          + (1 - b2) * jnp.square(g.astype(jnp.float32))
                          ).astype(moment_dtype), state.nu, grads)
        bc1 = 1 - b1 ** tf
        bc2 = 1 - b2 ** tf

        def upd(p, m, v):
            m, v = m.astype(jnp.float32), v.astype(jnp.float32)
            step = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            out = p.astype(jnp.float32) - step
            if wd:
                out = out - lr * wd * p.astype(jnp.float32)
            return out.astype(p.dtype)

        new = jax.tree_util.tree_map(upd, params, mu, nu)
        return new, OptState(t, mu, nu)
    return Optimizer(init, update, name)


# ---------------------------------------------------------------------------
# Arena-native apply (flat parameter arena as the live representation)
# ---------------------------------------------------------------------------

def arena_apply(optimizer: Optimizer, grads: jnp.ndarray, state: OptState,
                arena: jnp.ndarray, layout) -> tuple[jnp.ndarray, OptState]:
    """One optimizer step over the flat word arena.

    ``arena`` is the ``(total_words,)`` word buffer laid out by ``layout``
    (:class:`repro.core.arena.ArenaLayout`); ``grads`` and ``state``'s
    moment buffers live in the f32 value domain (``(total_values,)``,
    ``optimizer.init`` on a value-shaped zeros buffer). The step decodes
    the arena to values — one slice + bitcast per coalesced same-dtype
    run, not per segment — runs the optimizer's own elementwise math
    (bit-identical to the per-leaf tree apply), and re-encodes through
    each run's stored dtype (the same ``.astype(p.dtype)`` rounding the
    tree path applies). For all-f32 layouts values *are* words, both
    casts vanish, and the update runs directly on the arena. Pad words
    stay zero either way: zero grads give zero moments and a zero step,
    weight decay of 0 is 0 (invariant I4), and sub-word element pads
    decode to 0.0 and re-encode to zero bits, so no masking pass is
    needed.
    """
    from repro.core.arena import decode_values, encode_values

    if layout.uniform_f32:
        return optimizer.update(grads, state, arena)
    values = decode_values(arena, layout)
    new_values, new_state = optimizer.update(grads, state, values)
    return encode_values(new_values, layout), new_state
