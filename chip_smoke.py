"""Smoke run of the fault-tolerant training path on a TPU chip.

    python chip_smoke.py                 # one chip: kernel + train phases
    python chip_smoke.py --four-chips    # 2x2 mesh: sharded arena vs PyTree

One process, no children. Refuses to run (nonzero exit, no result line)
when JAX finds no TPU. Every check is an assert or a raise; each phase
prints one JSON line, and the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Step times printed here are smoke readings, not benchmark numbers.

Phases (one chip):

- kernel: the compiled Pallas kernels (``interpret=False``) on an f32
  arena of the train model's shapes, laid out without tail packing so
  the arena sweep is eligible, each against its jnp path — the arena
  sweep (replica and parity bit-equal, scores allclose), the arena tile
  scatter, the XOR parity fold and the RS(k, 2) GF(256) encode
  (bit-equal).
- train: qwen2-1.5b at its published widths (depth cut) in bf16 through
  ``TrainLoop`` on the arena-resident path with the tiered fabric and a
  sharded checkpoint store; one host loss injected and recovered without
  the running checkpoint or the disk; the step-1 loss checked against a
  PyTree-state loop on the same config and seed.

``--four-chips``: a ``(2, 2)`` ``("data", "model")`` mesh, the same widths
and depth in f32 (a meshed fabric needs an all-f32 model), the arena loop
against the PyTree loop on that mesh, one host loss, and the rotated
replica's interconnect bytes. Nothing else runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
# depth cut of qwen2-1.5b (published: 28): with the tiers resident, four
# layers is what one v5e's 16 GiB holds at batch 4 x 1024
LAYERS = 4
STEPS = 8
BATCH, SEQ = 4, 1024
FAIL_STEP = 5
# tokens per logits chunk of the vocab-sharded loss: the default 4096
# would hold a (4, 1024, 151936) f32 logits block and its gradient
LOSS_CHUNK = 256
# bf16 loss: two bf16 ulps at 1.0 — the arena and PyTree steps run the
# same forward on bit-identical weights, so any gap is reduction order
BF16_LOSS_RTOL = 2.0 ** -7
# same-mesh f32 loss: the arena and PyTree steps are bit-equal on the
# forced-CPU mesh. On a v5e 2x2 their step-1 losses agree, then part by
# up to 2.0e-5 relative within 8 steps, most of it from the TPU's
# default (one bf16 pass) matmul precision: at "highest" the gap is
# 7.7e-8, one f32 ulp at one step. 5x the default-precision margin
F32_LOSS_RTOL = 1e-4


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (cache reads included) and
    counts persistent-cache hits, via JAX's monitoring hooks."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def reading(self) -> dict:
        return {"compile_seconds": round(self.seconds, 3),
                "cache_hits": self.cache_hits}


def device_memory() -> dict:
    """The chip's high-water mark, what is held now, and its limit."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in
            ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")}


def model_config(dtype: str):
    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen2-1.5b"), n_layers=LAYERS,
                               dtype=dtype, loss_chunk=LOSS_CHUNK)


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def kernel_phase(cfg) -> dict:
    """Compiled kernels vs their jnp paths on an f32 arena of ``cfg``'s
    shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.arena import build_arena_layout, pack_arena
    from repro.core.blocks import partition_pytree
    from repro.fabric.domains import FailureDomainMap
    from repro.fabric.parity import ParityCodec
    from repro.fabric.placement import ClusterView
    from repro.kernels.fused_maintain.ops import (ArenaMaintainProgram,
                                                  arena_scatter_save)
    from repro.kernels.gf256_mac.ops import rs_encode
    from repro.kernels.gf256_mac.tables import rs_coefficients
    from repro.kernels.parity_xor.ops import parity_xor
    from repro.models import get_model
    from repro.sharding.partition import block_device_homes

    cfg = dataclasses.replace(cfg, dtype="float32")
    ops = get_model(cfg)
    params = jax.jit(ops.init_params, static_argnums=(1,))(
        jax.random.PRNGKey(SEED), cfg)
    part = partition_pytree(params, 128)
    layout = build_arena_layout(part, tail_pack=False)
    view = ClusterView(FailureDomainMap(8, 2, 2), block_device_homes(part, 8))
    codec = ParityCodec(part, view, group_size=4, use_pallas=False)
    live = jax.jit(lambda p: pack_arena(p, layout))(params)
    del params
    noise = jax.random.normal(jax.random.PRNGKey(SEED + 1), live.shape,
                              jnp.float32)
    ckpt = live + jnp.float32(1e-2) * noise
    del noise
    out = {"arena_words": int(layout.total_words),
           "arena_tiles": int(layout.n_tiles)}

    # arena sweep: replica copy + XOR parity + PRIORITY scores
    def sweep(use_pallas):
        prog = ArenaMaintainProgram(part, layout, codec.layout,
                                    codec.group_of, codec.n_groups,
                                    use_pallas=use_pallas,
                                    interpret=False)
        rep, scores, par = prog(live, ckpt)
        assert bool(jnp.array_equal(rep, live)), "replica != live arena"
        del rep
        return prog.sweep, scores, par

    kind_p, sc_p, par_p = sweep(True)
    assert kind_p == "pallas", kind_p
    kind_j, sc_j, par_j = sweep(False)
    assert kind_j == "jnp", kind_j
    assert bool(jnp.array_equal(par_p, par_j)), "sweep parity differs"
    np.testing.assert_allclose(np.asarray(sc_p), np.asarray(sc_j),
                               rtol=1e-4, atol=1e-6)
    out["sweep_parity_bit_equal"] = True
    out["sweep_scores_max_rel"] = float(np.max(
        np.abs(np.asarray(sc_p) - np.asarray(sc_j))
        / np.maximum(np.abs(np.asarray(sc_j)), 1e-30)))
    del sc_p, sc_j, par_p, par_j

    # tile scatter: a 1/8 partial save
    k = max(1, part.total_blocks // 8)
    idx = np.random.default_rng(SEED).choice(part.total_blocks, k,
                                             replace=False)
    saved = []
    for use_pallas in (True, False):
        dst, moved = arena_scatter_save(jnp.array(ckpt), live, layout, idx,
                                        use_pallas=use_pallas,
                                        interpret=False)
        saved.append(dst)
    assert bool(jnp.array_equal(saved[0], saved[1])), "scatter differs"
    out["scatter_bit_equal"] = True
    out["scatter_bytes"] = int(moved)
    del saved, ckpt

    # XOR parity fold and RS(k, 2) encode over real-width frames
    g, width = codec.group_size, codec.layout.frame_elems
    n = min(64, live.size // (g * width))
    frames = jax.lax.bitcast_convert_type(
        live[:n * g * width], jnp.int32).reshape(n, g, width)
    keep = jnp.asarray(np.random.default_rng(SEED + 2).random((n, g)) < 0.75)
    base = jnp.zeros((n, width), jnp.int32)
    xor = [parity_xor(frames, base, keep, use_pallas=p, interpret=False)
           for p in (True, False)]
    assert bool(jnp.array_equal(xor[0], xor[1])), "parity_xor differs"
    coeff = np.broadcast_to(rs_coefficients(g, 2)[:, None, :],
                            (2, n, g)).astype(np.int32)
    rs = [rs_encode(frames, jnp.asarray(coeff), use_pallas=p,
                    interpret=False) for p in (True, False)]
    assert bool(jnp.array_equal(rs[0], rs[1])), "gf256 RS(k,2) differs"
    out.update(parity_xor_bit_equal=True, rs_k2_bit_equal=True,
               frames=[n, g, width])
    return out


# ---------------------------------------------------------------------------
# train phase (one chip)
# ---------------------------------------------------------------------------

def _loop(cfg, ctx, loop_cfg, store=None):
    from repro.optim.optimizers import adamw
    from repro.training import TrainLoop
    return TrainLoop(cfg, ctx, optimizer=adamw(3e-4), loop_cfg=loop_cfg,
                     store=store)


def _recovery(loop) -> dict:
    fails = [f for m in loop.metrics for f in m.get("failures", [])]
    assert len(fails) == 1, fails
    counts = fails[0]["tier_counts"]
    assert counts["RUNNING_CKPT"] == 0 and counts["DISK"] == 0, counts
    assert counts["PEER_REPLICA"] + counts["PARITY"] > 0, counts
    return {k: v for k, v in counts.items() if v}


def train_phase(cfg) -> dict:
    import numpy as np
    from repro.checkpoint_io import ShardedCheckpointStore
    from repro.core.policy import CheckpointPolicy
    from repro.data.pipeline import ShardedLMDataset
    from repro.fabric import FabricConfig
    from repro.sharding import single_device_ctx
    from repro.training import ArenaTrainState, TrainLoopConfig

    ctx = single_device_ctx()
    out = {"layers": cfg.n_layers, "d_model": cfg.d_model,
           "d_ff": cfg.d_ff, "vocab": cfg.vocab, "dtype": cfg.dtype,
           "batch": BATCH, "seq": SEQ}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_store_") as tmp:
        store = ShardedCheckpointStore(tmp)
        loop = _loop(cfg, ctx, TrainLoopConfig(
            policy=CheckpointPolicy.scar(fraction=0.125, interval=4),
            fabric=FabricConfig(), arena_state=True,
            fail_schedule=[(FAIL_STEP, "host", 1)], seed=SEED),
            store=store)
        state = loop.init_state()
        assert isinstance(state, ArenaTrainState), type(state)
        out["params"] = int(loop.controller.partition.total_params)
        ds = ShardedLMDataset(cfg, batch=BATCH, seq=SEQ, ctx=ctx)
        state = loop.run(state, iter(ds), STEPS)
        losses = [m["loss"] for m in loop.metrics]
        assert len(losses) == STEPS and np.isfinite(losses).all(), losses
        fab = loop.controller.fabric
        assert fab.stats["live_packs"] == 0, fab.stats["live_packs"]
        store.flush()                  # raises if a background write failed
        out["store_bytes"] = store.disk_nbytes()["shard"]
        assert out["store_bytes"] > 0, out["store_bytes"]
        out.update(
            losses=losses,
            tiers=_recovery(loop),
            sweep=fab.stats["arena_sweep"],
            sweep_reason=fab.stats["arena_sweep_reason"],
            saves=loop.controller.stats["saves"],
            step_seconds=[round(m["seconds"], 4) for m in loop.metrics],
            overhead_seconds=[round(m.get("overhead_seconds", 0.0), 4)
                              for m in loop.metrics],
            arena_loop_memory=device_memory())
        del loop, state, ds, fab
        gc.collect()

    # the PyTree-state reference: same config and seed, one step
    ref = _loop(cfg, ctx, TrainLoopConfig(arena_state=False, seed=SEED))
    ref_state = ref.init_state()
    ref.run(ref_state, iter(ShardedLMDataset(cfg, batch=BATCH, seq=SEQ,
                                             ctx=ctx)), 1)
    ref_loss = ref.metrics[0]["loss"]
    np.testing.assert_allclose(out["losses"][0], ref_loss,
                               rtol=BF16_LOSS_RTOL)
    out["pytree_step1_loss"] = ref_loss
    out["step1_rel_diff"] = abs(out["losses"][0] - ref_loss) / abs(ref_loss)
    out["step1_rtol"] = BF16_LOSS_RTOL
    del ref, ref_state
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def _spread(arr) -> int:
    return len({s.device for s in arr.addressable_shards})


def four_chip_phase(cfg) -> dict:
    import jax
    import numpy as np
    from repro.core.policy import CheckpointPolicy
    from repro.data.pipeline import ShardedLMDataset
    from repro.fabric import FabricConfig
    from repro.launch.mesh import make_mesh_compat
    from repro.sharding.partition import make_dist_ctx
    from repro.training import ArenaTrainState, TrainLoopConfig

    n = len(jax.devices())
    assert n == 4, f"--four-chips needs 4 devices, JAX found {n}"
    mesh = make_mesh_compat((2, 2), ("data", "model"))
    ctx = make_dist_ctx(mesh)
    # assumption: each chip is its own host failure domain, two per rack
    fabric = FabricConfig(n_devices=4, devices_per_host=1, hosts_per_rack=2)
    log("four_chips_assumption", devices_per_host=1, hosts_per_rack=2,
        note="every chip is treated as its own host failure domain")

    def run(arena_state: bool):
        loop = _loop(cfg, ctx, TrainLoopConfig(
            policy=CheckpointPolicy.scar(fraction=0.125, interval=4),
            fabric=fabric, arena_state=arena_state,
            fail_schedule=[(FAIL_STEP, "host", 1)], seed=SEED))
        state = loop.init_state()
        # every array of the initial state spans the mesh: none was left
        # on the default device (sharded or replicated both give 4)
        spread = min(_spread(x) for x in jax.tree_util.tree_leaves(state)
                     if x.ndim)
        assert spread == 4, f"initial state on {spread} device(s)"
        ds = ShardedLMDataset(cfg, batch=BATCH, seq=SEQ, ctx=ctx)
        state = loop.run(state, iter(ds), STEPS)
        return loop, state

    la, sa = run(True)
    assert isinstance(sa, ArenaTrainState), type(sa)
    fab = la.controller.fabric
    out = {"mesh": dict(mesh.shape), "layers": cfg.n_layers,
           "dtype": cfg.dtype, "batch": BATCH, "seq": SEQ,
           "arena_shards": int(sa.layout.shards),
           "arena_devices": _spread(sa.arena),
           "replica_devices": _spread(fab.replicas.arena),
           "ckpt_arena_devices": _spread(la.controller._ckpt_arena),
           "arena_sharding": str(sa.arena.sharding)}
    assert out["arena_devices"] == 4 and out["replica_devices"] == 4, out
    rot = [d.id for d in fab._replica_sharding.mesh.devices.reshape(-1)]
    assert rot != sorted(rot), rot
    assert fab.stats["ici_bytes_moved"] > 0, fab.stats
    assert fab.stats["live_packs"] == 0, fab.stats["live_packs"]
    arena_losses = [m["loss"] for m in la.metrics]
    out.update(arena_losses=arena_losses, tiers=_recovery(la),
               ici_bytes_moved=int(fab.stats["ici_bytes_moved"]),
               dcn_bytes_moved=int(fab.stats["dcn_bytes_moved"]),
               replica_order=rot,
               sweep=fab.stats["arena_sweep"],
               sweep_reason=fab.stats["arena_sweep_reason"],
               step_seconds=[round(m["seconds"], 4) for m in la.metrics])
    del la, sa, fab
    gc.collect()

    lt, st = run(False)
    tree_losses = [m["loss"] for m in lt.metrics]
    _recovery(lt)
    assert np.isfinite(arena_losses).all() and np.isfinite(tree_losses).all()
    np.testing.assert_allclose(arena_losses, tree_losses,
                               rtol=F32_LOSS_RTOL)
    out.update(pytree_losses=tree_losses, loss_rtol=F32_LOSS_RTOL,
               loss_max_rel_diff=float(np.max(
                   np.abs(np.subtract(arena_losses, tree_losses))
                   / np.abs(tree_losses))),
               losses_bit_equal=arena_losses == tree_losses)
    return out


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh sharded-arena path")
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke.py: cannot import the repro package from "
                 f"{os.path.join(ROOT, 'src')} ({e}); run it from a "
                 "checkout of the repository")
    cache_dir = enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py: no TPU — JAX's first device is "
                 f"{dev.platform!r} ({dev.device_kind}); this smoke runs "
                 "only on a chip")
    clock = CompileClock()
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), jax=jax.__version__,
        compile_cache=cache_dir)

    t0 = time.perf_counter()
    if args.four_chips:
        res = four_chip_phase(model_config("float32"))
        log("four_chips", **res, seconds=round(time.perf_counter() - t0, 2),
            **clock.reading())
    else:
        res = kernel_phase(model_config("bfloat16"))
        log("kernels", **res, seconds=round(time.perf_counter() - t0, 2),
            **device_memory(), **clock.reading())
        gc.collect()
        t1 = time.perf_counter()
        res = train_phase(model_config("bfloat16"))
        log("train", **res, seconds=round(time.perf_counter() - t1, 2),
            **device_memory(), **clock.reading())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
