"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
SCAR fault tolerance, injecting partial failures along the way.

This is the deliverable-(b) end-to-end example: a real (small) transformer,
the sharded data pipeline, AdamW, the fault-tolerance controller with a
persistent on-disk store, and failure injection sampled from a geometric
distribution exactly as in the paper's §5.3.

The trainer runs **arena-resident** by default: the live training state is
the flat parameter arena (donated through the jitted step), the per-step
maintenance sweep reads it pack-free, and the partial save scatters
straight from it. ``--pytree`` forces the classic PyTree path for
comparison; both print the per-step maintenance overhead they observe.

Run:  PYTHONPATH=src python examples/train_lm_with_failures.py \
          [--steps 300] [--fail-prob 0.02] [--arch qwen2-1.5b] [--pytree]
(CPU: ~100M params; pass --tiny for a quick smoke run.)
"""
import argparse
import dataclasses
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.checkpoint_io import ShardedCheckpointStore
from repro.configs import get_config
from repro.core.policy import CheckpointPolicy
from repro.data.pipeline import ShardedLMDataset
from repro.fabric import FabricConfig
from repro.optim.optimizers import adamw
from repro.sharding import single_device_ctx
from repro.training import TrainLoop, TrainLoopConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--fail-prob", type=float, default=0.02)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--pytree", action="store_true",
                    help="force the classic PyTree training state")
    args = ap.parse_args()

    base = get_config(args.arch, reduced=True)
    if args.tiny:
        cfg, batch, seq = base, 2, 64
        args.steps = min(args.steps, 20)
    else:
        # ~100M params: scale the reduced config up
        cfg = dataclasses.replace(
            base, n_layers=8, d_model=768, n_heads=12, n_kv_heads=4,
            d_ff=2048, vocab=32000, d_head=64)
        batch, seq = 8, 256

    ctx = single_device_ctx()
    policy = CheckpointPolicy.scar(fraction=0.125, interval=8)
    store = ShardedCheckpointStore(tempfile.mkdtemp(prefix="scar_ckpt_"))
    loop = TrainLoop(cfg, ctx, optimizer=adamw(3e-4),
                     loop_cfg=TrainLoopConfig(policy=policy,
                                              fail_prob=args.fail_prob,
                                              fail_fraction=0.5,
                                              fabric=FabricConfig(),
                                              arena_state=not args.pytree),
                     store=store)
    state = loop.init_state()
    n = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    print(f"== training {args.arch}-derived LM: {n/1e6:.1f}M params, "
          f"{args.steps} steps, SCAR(r=1/8, partial recovery), "
          f"p_fail={args.fail_prob}/step, "
          f"state={'arena-resident' if loop.arena_layout is not None else 'pytree'}")

    ds = ShardedLMDataset(cfg, batch=batch, seq=seq, ctx=ctx)

    def on_step(i, loss):
        if i % 20 == 0 or i == 1:
            print(f"   step {i:4d}  loss {loss:.4f}")

    state = loop.run(state, iter(ds), args.steps, on_step=on_step)

    failures = [m for m in loop.metrics if "failure" in m]
    ckpts = sum(1 for m in loop.metrics if m.get("checkpointed"))
    print(f"== done. {ckpts} partial checkpoints, {len(failures)} failures")
    for m in failures:
        f = m["failure"]
        print(f"   failure @step {m['step']}: lost {f['lost_blocks']:.0f} "
              f"blocks, ||δ'||²={f['partial_sq']:.4f} "
              f"(full recovery would be {f['full_sq']:.4f})")
    losses = [m["loss"] for m in loop.metrics]
    print(f"   loss {losses[0]:.3f} -> {np.mean(losses[-10:]):.3f} "
          f"(finite: {np.isfinite(losses).all()})")
    stats = loop.controller.stats
    print(f"   controller: {stats['saves']} saves, "
          f"{stats['bytes_mirrored']/1e6:.1f}MB mirrored, "
          f"{stats['save_seconds']:.2f}s total dump time")
    over = loop.overhead_summary()
    print(f"   per-step maintenance overhead: "
          f"{over['overhead_seconds_mean']*1e3:.1f} ms "
          f"({over.get('maintain_bytes_per_step', 0)/1e6:.1f} MB/step "
          f"accounted) next to {over['step_seconds_mean']*1e3:.1f} ms/step "
          f"compute; arena-resident={over['arena_state']}, "
          f"{over.get('arena_resident_maintains', 0)} pack-free sweeps")


if __name__ == "__main__":
    enable_compile_cache()
    main()
