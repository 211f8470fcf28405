"""Plain training reference shared by the configurations' references.

Everything here is straightforward ``jax.numpy`` in float32 at
``Precision.HIGHEST``; it imports nothing of the program under test. A
configuration's reference module (``configs/<reference>.py``) supplies the
parameter specs and the loss; this module supplies the seeded weights,
the AdamW steps and the per-leaf readings that decide ``correct``.

Precision: parameters are stored between steps in the dtypes the
configuration states. ``Numerics(operand)`` says to which dtype every
matmul operand is rounded before an f32 multiply-accumulate: the
reference rounds nothing; the control (``Numerics.control``) rounds to
one step below the configuration's dtype (bfloat16 -> float8_e4m3fn,
float32 -> bfloat16), the matmul precision a later change would be
tempted by. The rounding is forward only: gradients pass through it
unchanged, as a low-precision matmul's backward does in f32, so small
cotangents are not flushed to zero by the lower dtype's range.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def round_through(x, dtype: str):
    """``x`` rounded to ``dtype`` and back to f32; the gradient passes
    through unchanged (straight-through)."""
    return x.astype(dtype).astype(jnp.float32)


def _round_fwd(x, dtype):
    return round_through(x, dtype), None


def _round_bwd(dtype, _, g):
    return (g,)


round_through.defvjp(_round_fwd, _round_bwd)


@dataclasses.dataclass(frozen=True)
class Numerics:
    operand: str | None = None   # matmul operand dtype; None: f32 as is

    @classmethod
    def control(cls, dtype: str) -> "Numerics":
        """One precision step below a configuration stored in ``dtype``."""
        return cls(LOWER[dtype])

    def q(self, x):
        """Round a matmul operand (f32 in, f32 out)."""
        if self.operand is None:
            return x
        return round_through(x, self.operand)

    def dot(self, eq: str, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    wd: float = 0.01


# ---------------------------------------------------------------------------
# leaves
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Spec:
    shape: tuple
    dtype: str            # stored dtype the configuration states
    init: str             # "normal" (1/sqrt(fan_in)), "ones", "zeros"
    fan_in: int = 1


def leaf_name(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def named_leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {leaf_name(p): x for p, x in flat}


def init_fn(specs):
    """``key -> weights`` for a spec tree: leaf ``i`` in sorted-name order
    draws from ``fold_in(key, i)``. Pure, so it can sit inside a jit."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda s: isinstance(s, Spec))
    names = [leaf_name(p) for p, _ in flat]
    order = {n: i for i, n in enumerate(sorted(names))}

    def make(key):
        out = []
        for (_, s), name in zip(flat, names):
            if s.init == "ones":
                x = jnp.ones(s.shape, jnp.float32)
            elif s.init == "zeros":
                x = jnp.zeros(s.shape, jnp.float32)
            else:
                k = jax.random.fold_in(key, order[name])
                x = jax.random.normal(k, s.shape, jnp.float32) \
                    / math.sqrt(max(s.fan_in, 1))
            out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def init_from_specs(specs, seed: int):
    """Seeded weights for a spec tree, made on the device in one jitted
    call."""
    return jax.jit(init_fn(specs))(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# loss pieces
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def chunked_xent(h, head, labels, num: Numerics, chunk: int):
    """Mean next-token cross-entropy of ``h @ head.T`` over all tokens,
    ``chunk`` positions at a time so no (B, S, V) logits are held."""
    B, S, D = h.shape
    n = S // chunk
    hc = jnp.moveaxis(h.reshape(B, n, chunk, D), 1, 0)
    yc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)

    @jax.checkpoint
    def one(hx, yx):
        logits = num.dot("bcd,vd->bcv", hx, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yx[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - ll)

    def body(acc, xs):
        return acc + one(*xs), None

    total, _ = jax.lax.scan(body, jnp.float32(0), (hc, yc))
    return total / (B * S)


# ---------------------------------------------------------------------------
# three AdamW steps and their readings
# ---------------------------------------------------------------------------

def leaf_norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in named_leaves(tree).items()}


def train_readings(loss_fn: Callable, params0, batches, num: Numerics,
                   opt: AdamW, steps: int = 3) -> dict:
    """Run ``steps`` AdamW steps from ``params0`` on ``batches`` and read
    what the program is compared on: each step's loss, the per-leaf norm
    of the first gradient, and the per-leaf norm of the parameters'
    change after the last step. Parameters are stored in their own
    dtypes between steps; moments are f32."""
    start = params0
    params = jax.tree_util.tree_map(jnp.copy, start)   # donated each step

    def f32(t):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, t, batch):
        loss, g = jax.value_and_grad(lambda p: loss_fn(p, batch, num))(
            f32(params))
        mu = jax.tree_util.tree_map(
            lambda m, x: opt.b1 * m + (1 - opt.b1) * x, mu, g)
        nu = jax.tree_util.tree_map(
            lambda v, x: opt.b2 * v + (1 - opt.b2) * x * x, nu, g)
        bc1 = 1 - opt.b1 ** t
        bc2 = 1 - opt.b2 ** t

        def upd(p, m, v):
            p32 = p.astype(jnp.float32)
            new = p32 - opt.lr * (m / bc1) / (jnp.sqrt(v / bc2) + opt.eps)
            new = new - opt.lr * opt.wd * p32
            return new.astype(p.dtype)

        return (jax.tree_util.tree_map(upd, params, mu, nu), mu, nu, loss,
                leaf_norms(g))

    mu = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    nu = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    losses, grad = [], None
    for i in range(steps):
        params, mu, nu, loss, gn = step(params, mu, nu,
                                        jnp.float32(i + 1), batches[i])
        losses.append(float(loss))
        if grad is None:
            grad = {k: float(v) for k, v in gn.items()}
    del mu, nu
    change = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))(
            params, start)
    return {"losses": losses, "grad": grad,
            "change": {k: float(v) for k, v in change.items()}}


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Largest gap between the program's and the reference's per-leaf
    norms, each over the larger of that leaf's reference norm and the
    median leaf's. ``keep`` names the leaves compared (default all)."""
    names = sorted(ref if keep is None else keep)
    med = float(np.median([ref[k] for k in ref]))
    worst, at = 0.0, (names[0] if names else "")
    for k in names:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not gap <= worst:          # a NaN reading wins and stays
            worst, at = gap, k
            if gap != gap:
                break
    return worst, at


def moving_leaves(ref_grad: dict, floor: float = 1e-3) -> list:
    """Leaves whose reference gradient is at least ``floor`` of the median
    leaf's. The others (a key bias under softmax) move under Adam by
    round-off alone, so their change is not compared."""
    med = float(np.median(list(ref_grad.values())))
    return sorted(k for k, v in ref_grad.items() if v >= floor * med)


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers ``correct`` is decided on."""
    loss_gap = float(np.max([abs(a - b) / abs(b) for a, b in
                             zip(prog["losses"], ref["losses"])]))
    grad_gap, grad_at = worst_leaf_gap(prog["grad"], ref["grad"])
    keep = moving_leaves(ref["grad"])
    change_gap, change_at = worst_leaf_gap(prog["change"], ref["change"],
                                           keep)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "grad_worst_leaf": grad_at, "change_gap": change_gap,
            "change_worst_leaf": change_at,
            "change_leaves_skipped": sorted(set(ref["grad"]) - set(keep))}

