"""Forced-8-device CPU driver behind the sharded-arena bench rows.

``bench_maintain`` runs in the normal single-device process (the committed
byte baselines depend on that), so the SPMD measurements live here: the
parent spawns this module as a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and parses the JSON
this prints on stdout. Standalone use works too::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks._sharded_probe --quick

Two measurements:

  sharded      — arena-resident vs PyTree-pack TrainLoop on the SAME
                 (4, 2) mesh: accounted maintenance bytes/step for both,
                 loss bit-equality (identical shardings → identical
                 reduction orders; see DESIGN.md for why this only holds
                 same-mesh), pack-free-ness, and the ICI/DCN split of the
                 anti-affine replica transfer.
  elastic_soak — host loss at step 4 shrinks the mesh to the survivors
                 (8 → 4 shards under batch divisibility), the heal at
                 step 9 re-grows to the full mesh; training must stay
                 finite and arena-resident throughout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import time

import jax
import numpy as np


def _bench(quick: bool) -> dict:
    from repro.configs import get_config
    from repro.core.policy import CheckpointPolicy
    from repro.data.pipeline import ShardedLMDataset
    from repro.fabric import FabricConfig
    from repro.launch.mesh import make_mesh_compat
    from repro.sharding.partition import make_dist_ctx
    from repro.training import ArenaTrainState, TrainLoop, TrainLoopConfig

    cfg = get_config("qwen2-1.5b", reduced=True)
    mesh = make_mesh_compat((4, 2), ("data", "model"))
    warm = 2
    steps = 5 if quick else 10

    out = {}
    for name, arena_state in (("arena", True), ("pytree", False)):
        ctx = make_dist_ctx(mesh)
        loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
            policy=CheckpointPolicy.scar(fraction=0.25, interval=2),
            fabric=FabricConfig(), arena_state=arena_state))
        state = loop.init_state()
        if arena_state:
            assert isinstance(state, ArenaTrainState)
        ds = ShardedLMDataset(cfg, batch=8, seq=32, ctx=ctx)
        it = iter(ds)
        state = loop.run(state, it, warm)          # compile everything
        ctl = loop.controller
        fab = ctl.fabric
        b0 = fab.stats["maintain_bytes_moved"] + ctl.stats["save_bytes_moved"]
        m0 = fab.stats["arena_maintains"]
        i0, d0 = fab.stats["ici_bytes_moved"], fab.stats["dcn_bytes_moved"]
        t0 = time.perf_counter()
        state = loop.run(state, it, steps)
        total_us = (time.perf_counter() - t0) / steps * 1e6
        ms = loop.metrics[warm:]
        overhead_us = float(np.median(
            [m["overhead_seconds"] for m in ms])) * 1e6
        n_maint = max(fab.stats["arena_maintains"] - m0, 1)
        out[name] = {
            "bytes_per_step":
                (fab.stats["maintain_bytes_moved"]
                 + ctl.stats["save_bytes_moved"] - b0) / steps,
            "overhead_us": overhead_us,
            "total_us": total_us,
            "losses": [m["loss"] for m in loop.metrics],
            "live_packs": fab.stats["live_packs"],
            "resident_maintains": fab.stats["arena_resident_maintains"],
            "ici_per_maintain":
                (fab.stats["ici_bytes_moved"] - i0) / n_maint,
            "dcn_per_maintain":
                (fab.stats["dcn_bytes_moved"] - d0) / n_maint,
            "shards": fab.arena_layout.shards,
        }
    return {
        "shards": out["arena"]["shards"],
        "arena": out["arena"], "pytree": out["pytree"],
        "loss_bit_equal":
            out["arena"]["losses"] == out["pytree"]["losses"],
        "bytes_le_pack": bool(out["arena"]["bytes_per_step"]
                              <= out["pytree"]["bytes_per_step"]),
    }


def _elastic_soak(quick: bool) -> dict:
    from repro.configs import get_config
    from repro.core.policy import CheckpointPolicy
    from repro.data.pipeline import ShardedLMDataset
    from repro.fabric import FabricConfig
    from repro.launch.mesh import make_mesh_compat
    from repro.sharding.partition import make_dist_ctx
    from repro.training import ArenaTrainState, TrainLoop, TrainLoopConfig

    cfg = get_config("qwen2-1.5b", reduced=True)
    mesh = make_mesh_compat((4, 2), ("data", "model"))
    ctx = make_dist_ctx(mesh)
    steps = 12 if quick else 20
    loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
        policy=CheckpointPolicy.scar(fraction=0.25, interval=2),
        fabric=FabricConfig(elastic=True),
        fail_schedule=[(4, "host", 1)], heal_after=5))
    state = loop.init_state()
    assert isinstance(state, ArenaTrainState)
    ds = ShardedLMDataset(cfg, batch=8, seq=32, ctx=ctx)
    t0 = time.perf_counter()
    state = loop.run(state, iter(ds), steps)
    us_per_step = (time.perf_counter() - t0) / steps * 1e6
    fab = loop.controller.fabric
    resizes = [m["mesh_resize"]["shards"] for m in loop.metrics
               if "mesh_resize" in m]
    finite = all(np.isfinite(m["loss"]) for m in loop.metrics)
    params_finite = all(np.isfinite(np.asarray(l)).all()
                        for l in jax.tree_util.tree_leaves(state.params))
    return {
        "us_per_step": us_per_step,
        "steps": steps,
        "mesh_resizes": fab.stats["mesh_resizes"],
        "resize_shards": resizes,
        "min_shards": min(resizes) if resizes else fab.arena_layout.shards,
        "final_shards": fab.arena_layout.shards,
        "live_packs": fab.stats["live_packs"],
        "losses_finite": bool(finite),
        "cycle_ok": bool(finite and params_finite
                         and resizes == [4, 8]
                         and fab.stats["live_packs"] == 0
                         and fab.arena_layout.shards == 8),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    assert jax.device_count() == 8, (
        f"need 8 forced host devices, got {jax.device_count()} — set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    result = {"sharded": _bench(args.quick),
              "elastic": _elastic_soak(args.quick)}
    json.dump(result, sys.stdout)
    print()


if __name__ == "__main__":
    main()
