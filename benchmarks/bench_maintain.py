"""Beyond-paper: single-pass arena maintenance vs the seed path.

The paper's §4.3 constant-budget property (a fraction-r partial checkpoint
writes the same bytes per C iterations as a full checkpoint) only holds if
the *maintenance* hot path is itself O(r)-ish: the seed implementation made
three-plus independent full passes per maintained step (replica tree copy,
pack-frames + member gather + XOR parity encode with two materialized
full-model staging buffers, and a third full read for PRIORITY scoring),
and the partial save rewrote every leaf through a full-size ``jnp.where``.

Measured here, on the reduced qwen2 config (quick mode shrinks repeats,
not the model):

  maint_sweep_*      — analytic HBM bytes + measured wall-clock per
                       maintenance step, the arena sweep (resident and
                       packing a tree) vs the seed three-pass path (all
                       including PRIORITY scoring).
  maint_sweep_quant  — word-level quantized arena: the reduced model's
                       redundancy bytes per sweep (replica + parity +
                       staging) and analytic bytes/step with every leaf
                       cast to bf16, vs the f32 baseline of the same
                       shapes. REQUIRED: the bf16 run moves ≤ 0.55× the
                       f32 bytes (``quant_bytes_le_half_f32``) and the
                       all-f32 e2e run stays loss-bit-equal to the
                       PyTree path (``f32_loss_bit_equal`` — the word
                       arena is a bitwise no-op at f32).
  maint_arena_padding — tail packing: pad-word overhead of the default
                       (tail-packed) layout vs ``tail_pack=False``; the
                       ``padding_ratio`` gauge is RECORDED for the perf
                       trajectory.
  maint_partial_save — bytes moved into the running checkpoint by the
                       donation-based in-place save at r=0.125 vs the full
                       rewrite (the §4.3 property, now true in memory).
  maint_store_packed — packed append-mode shard mirror: bytes appended per
                       partial save, live index bytes, compaction reclaim.
  maint_arena_kernel — interpret-mode bit-exactness of the arena sweep
                       kernel vs the tree-path oracles.
  e2e_step_maintain  — full trainer pipeline (train step + maintain +
                       partial save) on the reduced LM, PyTree-pack path
                       vs arena-resident training state: accounted
                       bytes/step of the fault-tolerance machinery (the
                       resident path drops the per-step pack — exactly
                       the live tree's bytes fewer), maintenance
                       wall-clock, and bit-equality of the two paths'
                       training losses.
  maint_overlap_*    — sync vs async (double-buffered, deferred-fence)
                       every-step maintenance on the reduced LM:
                       clean-step overhead p50 per mode, bit-equality
                       of losses + running checkpoint, fraction of the
                       async sweep hidden under the next step's compute
                       (``overlap_efficiency``), and async-sweep /
                       train-step span overlap counts from the tracer.
  maint_sweep_sharded / tier_soak_elastic_mesh
                     — SPMD rows, measured in a forced-8-device CPU
                       subprocess (this process stays single-device so
                       the committed byte baselines hold): the sharded
                       arena loop's maintenance bytes/step vs the
                       PyTree-pack loop on the SAME (4, 2) mesh with
                       loss bit-equality, the ICI/DCN split of the
                       anti-affine replica transfer, and the host-loss →
                       mesh-shrink → heal → re-grow soak.
  maint_telemetry    — trace-driven soak with a live telemetry Recorder:
                       events.jsonl + Chrome trace + run report (written
                       under ``--telemetry-out`` when given), clean-step
                       overhead p50/p95 from the recorded histogram, and
                       a bit-exactness check of the perturbation ledger's
                       Thm-3.2/4.1 bounds against ``core/iteration_cost``.
                       The gated e2e rows above run with the default
                       NullRecorder — their bytes/step are untouched.
  tier_soak_multi_erasure
                     — RS(k, 2) vs XOR under a correlated two-host
                       same-step loss plus an injected in-arena bit
                       flip with an every-step integrity scrub: the RS
                       run must recover bit-exactly through the parity
                       tier (no checkpoint fallback, ‖δ′‖² = 0) and
                       detect/localize/correct the flip; the XOR
                       control's fallbacks and paid perturbation ride
                       along. Ledger artifact lands under
                       ``<telemetry-out>/multi_erasure``.

Bytes are the roofline currency here: on this CPU host the in-place save's
per-leaf eager dispatch overhead exceeds the memcpy it saves at the
reduced model size (the rewrite is one fused XLA program), so its
wall-clock row is honest-but-unflattering; the byte ratios are what
transfer to a bandwidth-bound accelerator.

Standalone: ``python -m benchmarks.bench_maintain [--quick]
[--out BENCH_maintain.json]`` (the CI smoke job's entry point).
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, timed
from repro.checkpoint_io import ShardedCheckpointStore
from repro.configs import get_config
from repro.core.blocks import block_scores, partition_pytree
from repro.core.controller import FTController
from repro.core.norms import get_norm
from repro.core.policy import CheckpointPolicy
from repro.fabric import CheckpointFabric, FabricConfig
from repro.models import get_model


def _reduced_params():
    cfg = get_config("qwen2-1.5b", reduced=True)
    ops = get_model(cfg)
    return ops.init_params(jax.random.PRNGKey(0), cfg)


def _tree_nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _drift(tree, scale=1e-2):
    return jax.tree_util.tree_map(lambda x: x + jnp.asarray(scale, x.dtype),
                                  tree)


def _kernel_check_rows(quick: bool) -> list[str]:
    from repro.core.arena import build_arena_layout, pack_arena
    from repro.fabric.domains import FailureDomainMap
    from repro.fabric.placement import ClusterView
    from repro.fabric.parity import ParityCodec
    from repro.kernels.fused_maintain.ops import ArenaMaintainProgram
    from repro.sharding.partition import block_device_homes

    rng = np.random.default_rng(5)
    rows_n = 40 if quick else 200
    params = {"w": jnp.asarray(rng.normal(size=(rows_n, 24)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    ck = jax.tree_util.tree_map(
        lambda x: x + jnp.asarray(rng.normal(size=x.shape), x.dtype), params)
    part = partition_pytree(params, 16)
    view = ClusterView(FailureDomainMap(8, 2, 2),
                       block_device_homes(part, 8))
    codec = ParityCodec(part, view, group_size=3, use_pallas=False)
    codec.encode(0, params)
    want_sc = np.asarray(block_scores(params, ck, part, get_norm("l2")))
    # interpret-mode arena sweep vs the tree-path oracles: the whole
    # model in ONE Pallas dispatch
    layout = build_arena_layout(part)
    prog = ArenaMaintainProgram(part, layout, codec.layout, codec.group_of,
                                codec.n_groups, use_pallas=True,
                                interpret=True)
    z = pack_arena(ck, layout)
    (arep, asc, apar), aus = timed(
        lambda: jax.block_until_ready(prog(params, z)), repeats=2)
    arep_ok = bool((np.asarray(arep)
                    == np.asarray(pack_arena(params, layout))).all())
    apar_ok = bool((np.asarray(apar) == np.asarray(codec.parity)).all())
    asc_ok = bool(np.allclose(np.asarray(asc), want_sc,
                              rtol=1e-5, atol=1e-5))
    return [csv_row(
        "maint_arena_kernel", aus,
        f"replica_bit_exact={arep_ok};parity_bit_exact={apar_ok};"
        f"scores_match={asc_ok};tiles={layout.n_tiles};dispatches=1")]


def _sweep_rows(params, quick: bool) -> list[str]:
    """Arena-resident vs arena-pack maintenance sweep vs the seed passes:
    analytic bytes + wall clock. ``arena_resident`` feeds the sweep the
    live flat arena itself (the trainer default — pack-free, pure
    2-read/1-write); ``arena`` packs a live tree first (one pack + ONE
    kernel dispatch); ``seed`` calls the seed's three passes directly
    (``replicas.refresh``, ``parity.encode``, ``block_scores``)."""
    from repro.core.arena import pack_arena
    part = partition_pytree(params, 128)
    ck_values = _drift(params)
    reps = 2 if quick else 4
    out = {}
    rows = []
    for name in ("arena_resident", "arena", "seed"):
        fab = CheckpointFabric(part, FabricConfig())
        pack = jax.jit(lambda t: pack_arena(t, fab.arena_layout))
        ck_arg = pack(ck_values)
        # arena-resident live state: the sweep's input IS the flat arena
        # — no pack inside the maintain at all
        live_arg = pack(params) if name == "arena_resident" else params

        def maintain(i):
            if name != "seed":
                fab.maintain(i, live_arg, ckpt_values=ck_arg, force=True)
                return
            fab.replicas.refresh(i, params)
            fab.parity.encode(i, params)
            # the seed scores separately: the third full pass the arena
            # sweep folds in
            jax.block_until_ready(
                block_scores(params, ck_values, part, get_norm("l2")))

        maintain(0)                                         # compile
        t0 = time.perf_counter()
        for i in range(1, reps + 1):
            maintain(i)
        jax.block_until_ready(fab.parity.parity)
        wall_us = (time.perf_counter() - t0) / reps * 1e6
        t = fab._traffic_model()
        bytes_step = t[name]
        staging = t["staging_seed" if name == "seed" else "staging_arena"]
        out[name] = {"bytes": bytes_step, "us": wall_us}
        rows.append(csv_row(
            f"maint_sweep_{name}", wall_us,
            f"bytes_per_step={bytes_step};staging_bytes={staging};"
            f"model_bytes={t['model']};arena_maintains="
            f"{fab.stats['arena_maintains']}"))
    # headline: the default (arena) path vs the seed path — the committed
    # floor the CI regression guard holds every run
    ratio = out["seed"]["bytes"] / max(out["arena"]["bytes"], 1)
    wall_ratio = out["seed"]["us"] / max(out["arena"]["us"], 1e-9)
    rows.append(csv_row(
        "maint_headline", 0.0,
        f"bytes_ratio_seed_over_arena={ratio:.2f};"
        f"meets_2x={bool(ratio >= 2.0)};"
        f"wall_ratio_seed_over_arena={wall_ratio:.2f};"
        f"resident_bytes_vs_pack="
        f"{out['arena_resident']['bytes'] / max(out['arena']['bytes'], 1):.3f}"))
    return rows


def _padding_rows(params, quick: bool) -> list[str]:
    """Tail packing: alignment overhead of the default layout vs the
    fully tile-aligned (``tail_pack=False``) layout on the reduced
    model. ``padding_ratio`` = pad words / live payload words."""
    from repro.core.arena import build_arena_layout

    part = partition_pytree(params, 128)
    packed = build_arena_layout(part)
    aligned = build_arena_layout(part, tail_pack=False)
    n_tail = (sum(1 for ab in packed.blocks
                  if ab.offset >= packed.tail_start)
              if packed.has_tail else 0)
    saved = (aligned.total_words - packed.total_words) * 4
    return [csv_row(
        "maint_arena_padding", 0.0,
        f"padding_ratio={packed.padding_ratio:.4f};"
        f"padding_ratio_unpacked={aligned.padding_ratio:.4f};"
        f"tail_blocks={n_tail};bytes_saved={saved};"
        f"arena_bytes={packed.nbytes};"
        f"tail_packed_not_larger="
        f"{bool(packed.total_words <= aligned.total_words)}")]


def _quant_rows(params, quick: bool, f32_loss_bit_equal: bool) -> list[str]:
    """Word-level quantized arena: redundancy bytes of the reduced model
    with every leaf cast to bf16 vs the f32 baseline of the same shapes.
    The arena stores raw words (2 bf16 elements per 32-bit word), so the
    replica, parity and sweep traffic all halve; the 0.55 gate leaves
    slack for tile-alignment padding on narrow leaves.

    ``f32_loss_bit_equal`` re-surfaces the e2e headline's
    ``loss_bit_equal`` under the quant gate: for an all-f32 model the
    word arena is bitwise the historical layout, so the arena-resident
    training run must stay bit-identical to the PyTree path."""
    p16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    out = {}
    for name, tree in (("f32", params), ("bf16", p16)):
        part = partition_pytree(tree, 128)
        fab = CheckpointFabric(part, FabricConfig())
        fab.maintain(1, tree, force=True)
        t = fab._traffic_model()
        out[name] = {"bytes": int(t["arena"]),
                     "red": int(sum(fab.redundancy_nbytes().values())),
                     "padding": float(t.get("padding_ratio", 0.0))}
    ratio_bytes = out["bf16"]["bytes"] / max(out["f32"]["bytes"], 1)
    ratio_red = out["bf16"]["red"] / max(out["f32"]["red"], 1)
    ok = bool(ratio_bytes <= 0.55 and ratio_red <= 0.55)
    return [csv_row(
        "maint_sweep_quant", 0.0,
        f"bytes_per_step_bf16={out['bf16']['bytes']};"
        f"bytes_per_step_f32={out['f32']['bytes']};"
        f"redundancy_bytes_bf16={out['bf16']['red']};"
        f"redundancy_bytes_f32={out['f32']['red']};"
        f"bytes_ratio_bf16_over_f32={ratio_bytes:.3f};"
        f"redundancy_ratio_bf16_over_f32={ratio_red:.3f};"
        f"quant_bytes_le_half_f32={ok};"
        f"f32_loss_bit_equal={bool(f32_loss_bit_equal)};"
        f"padding_ratio={out['bf16']['padding']:.4f}")]


def _partial_save_rows(params, quick: bool) -> list[str]:
    """In-place partial save: O(k·block_bytes) AND faster than the
    full-leaf rewrite.

    The ``inplace`` variant is the production shape: an arena fabric
    maintains every step (that cost is the sweep's, measured above) and
    the save is ONE donated tile scatter from the sweep's replica arena
    into the checkpoint arena — wall-clock now beats the single-program
    ``jnp.where`` rewrite that used to win on dispatch count. A
    ``inplace_tree`` row keeps the old per-leaf scatter honest. The
    budget headline uses ROUND_ROBIN over one full rotation, so the
    average bytes per save is ≈ ``r``·(full bytes) (arena tile padding
    adds the small ``frac_of_full − r`` gap); a PRIORITY row rides along
    for context — drift-weighted selection legitimately concentrates on
    the biggest (most-drifted) blocks."""
    from repro.core.policy import RecoveryMode, SelectionStrategy

    model_bytes = _tree_nbytes(params)
    frac = 0.125
    part = partition_pytree(params, 128)
    k = part.blocks_for_k(frac)
    cycle = -(-part.total_blocks // k)          # saves per full rotation
    rr_pol = CheckpointPolicy(fraction=frac, full_interval=8,
                              strategy=SelectionStrategy.ROUND_ROBIN,
                              recovery=RecoveryMode.PARTIAL)
    rows = []
    moved_per_save = {}
    wall_per_save = {}
    variants = (("inplace", dict(inplace_save=True,
                                 fabric=FabricConfig())),
                ("inplace_tree", dict(inplace_save=True)),
                ("rewrite", dict(inplace_save=False)))
    # warm one full ROUND_ROBIN *selection period*, not one rotation:
    # when total_blocks % k != 0 the selection window shifts each
    # rotation, so distinct (selection size → jit bucket) keys keep
    # appearing for total/gcd(total, k) saves — timing before that pays
    # a recompile mid-measurement
    period = part.total_blocks // math.gcd(part.total_blocks, k)
    warm = -(-period // cycle) * cycle
    for name, kw in variants:
        ctl = FTController(params, rr_pol, **kw)
        has_fabric = ctl.fabric is not None
        live = params
        for i in range(warm):                   # compile every
            live = _drift(live)                 # (leaf, bucket) pair
            if has_fabric:
                ctl.maintain(1 + i, live)
            ctl.checkpoint_now(1 + i, live)
        ctl.stats.update(saves=0, save_seconds=0.0, save_bytes_moved=0)
        for i in range(cycle):
            live = _drift(live)
            if has_fabric:
                # production loop order: the sweep refreshes the tiers
                # (and the replica arena the save scatters from); block on
                # it so save_seconds times the save, not the sweep's async
                # tail (the sweep is measured by the maint_sweep_* rows)
                ctl.maintain(1 + warm + i, live)
                jax.block_until_ready(ctl.fabric.replicas.arena)
            ctl.checkpoint_now(1 + warm + i, live)
        if kw.get("inplace_save"):
            moved = ctl.stats["save_bytes_moved"] / max(ctl.stats["saves"], 1)
        else:
            moved = float(model_bytes)   # jnp.where rewrites every leaf
        moved_per_save[name] = moved
        t_save = ctl.stats["save_seconds"] / max(ctl.stats["saves"], 1)
        wall_per_save[name] = t_save * 1e6
        rows.append(csv_row(
            f"maint_partial_save_{name}", t_save * 1e6,
            f"bytes_moved_per_save={moved:.0f};"
            f"frac_of_full={moved / model_bytes:.4f};"
            f"saves_per_rotation={cycle};"
            f"arena={bool(has_fabric)}"))
    frac_of_full = moved_per_save["inplace"] / model_bytes
    rows.append(csv_row(
        "maint_partial_save_headline", 0.0,
        f"r={frac};frac_of_full={frac_of_full:.4f};"
        f"near_r={bool(frac_of_full <= 1.5 * frac)};"
        f"rewrite_over_inplace="
        f"{moved_per_save['rewrite'] / max(moved_per_save['inplace'], 1):.1f};"
        f"inplace_beats_rewrite_wallclock="
        f"{bool(wall_per_save['inplace'] < wall_per_save['rewrite'])};"
        f"wall_rewrite_over_inplace="
        f"{wall_per_save['rewrite'] / max(wall_per_save['inplace'], 1e-9):.2f}"))
    # drift-weighted PRIORITY context row
    ctl = FTController(params, CheckpointPolicy.scar(fraction=frac,
                                                     interval=8))
    live = _drift(params)
    ctl.checkpoint_now(1, live)
    rows.append(csv_row(
        "maint_partial_save_priority", 0.0,
        f"bytes_moved={ctl.stats['save_bytes_moved']};"
        f"frac_of_full="
        f"{ctl.stats['save_bytes_moved'] / model_bytes:.4f};"
        f"blocks_frac={frac}"))
    return rows


def _store_rows(params, quick: bool) -> list[str]:
    """Packed append-mode shard mirror: append volume, live bytes,
    compaction reclaim."""
    part = partition_pytree(params, 128)
    store_dir = tempfile.mkdtemp(prefix="bench_maintain_store_")
    try:
        store = ShardedCheckpointStore(store_dir)
        store.init(params, part)
        k = part.blocks_for_k(0.125)
        rng = np.random.default_rng(0)
        saves = 3 if quick else 6
        appended = 0
        for i in range(saves):
            mask = np.zeros((part.total_blocks,), bool)
            mask[rng.choice(part.total_blocks, k, replace=False)] = True
            appended += store.write_blocks(mask, params, step=i + 1,
                                           background=False)
        before = store.disk_nbytes()
        reclaimed = store.compact()
        after = store.disk_nbytes()
        rows = [csv_row(
            "maint_store_packed", 0.0,
            f"appended_bytes={appended};log_bytes={before['shard']};"
            f"live_bytes={before['live']};reclaimed={reclaimed};"
            f"compacted_log={after['shard']};"
            f"compaction_exact={bool(after['shard'] == after['live'])}")]
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    rows.extend(_arena_store_rows(params, quick))
    return rows


def _arena_store_rows(params, quick: bool) -> list[str]:
    """Domain-keyed arena-segment mirror: a fraction-r save appends ONE
    contiguous buffer per touched host shard, and a re-keying compact()
    migrates segments to their blocks' *current* homes."""
    import os

    from repro.core.arena import ARENA_TILE
    from repro.core.policy import RecoveryMode, SelectionStrategy

    part = partition_pytree(params, 128)
    store_dir = tempfile.mkdtemp(prefix="bench_maintain_arena_store_")
    try:
        store = ShardedCheckpointStore(store_dir)
        pol = CheckpointPolicy(fraction=0.125, full_interval=8,
                               strategy=SelectionStrategy.ROUND_ROBIN,
                               recovery=RecoveryMode.PARTIAL)
        ctl = FTController(params, pol, store=store,
                           fabric=FabricConfig(elastic=True))
        assert ctl._arena_layout is not None
        live = params
        saves = 2 if quick else 4
        t0 = time.time()
        for i in range(1, saves + 1):
            live = _drift(live)
            ctl.maintain(i, live)
            ctl.checkpoint_now(i, live)
        store.flush()
        mirror_us = (time.time() - t0) / saves * 1e6
        hosts = sum(1 for n in os.listdir(store_dir)
                    if n.startswith("host_"))
        # degrade placement (host loss + elastic re-home), then re-key the
        # mirror during the generational rewrite
        lost, failed = ctl.fabric.domain_failure("host", 0)
        live, _ = ctl.on_failure(live, lost, failed_devices=failed,
                                 step=saves)
        before = store.disk_nbytes()
        reclaimed = store.compact(rekey_homes=ctl.fabric.view.homes,
                                  domains=ctl.fabric.domains)
        vals = store.read_all()
        ck = ctl.ckpt.values
        ok = all(bool((np.asarray(a) == np.asarray(b)).all())
                 for a, b in zip(jax.tree_util.tree_leaves(vals),
                                 jax.tree_util.tree_leaves(ck)))
        after = store.disk_nbytes()
        return [csv_row(
            "maint_store_arena", mirror_us,
            f"host_shards={hosts};appended_per_save="
            f"{ctl.stats['bytes_mirrored'] // max(ctl.stats['saves'], 1)};"
            f"tile_words={ARENA_TILE};log_before={before['shard']};"
            f"reclaimed={reclaimed};rekeyed_read_exact={ok};"
            f"compaction_exact={bool(after['shard'] == after['live'])}")]
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)


def _e2e_rows(quick: bool) -> list[str]:
    """Full step+maintain pipeline: PyTree-pack vs arena-resident state.

    Bytes/step is the fault-tolerance machinery's accounted traffic
    (fabric ``maintain_bytes_moved`` + controller ``save_bytes_moved``
    per step) — the resident path must move strictly fewer bytes (the
    pack is gone). Wall-clock: the maintenance overhead (maintain +
    save, ``overhead_seconds``) robustly wins on the resident path; the
    *total* step+maintain wall-clock also rides along but on this CPU
    the arena step itself pays tile-padding overhead in the optimizer's
    elementwise passes, so the total is recorded, never gated (bytes
    are the roofline currency — see the module docstring)."""
    from repro.data.pipeline import ShardedLMDataset
    from repro.sharding import single_device_ctx
    from repro.training import ArenaTrainState, TrainLoop, TrainLoopConfig

    cfg = get_config("qwen2-1.5b", reduced=True)
    warm = 2 if quick else 3
    steps = 5 if quick else 12
    out = {}
    rows = []
    for name, arena_state in (("arena", True), ("pytree", False)):
        ctx = single_device_ctx()
        pol = CheckpointPolicy.scar(fraction=0.125, interval=4)
        loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
            policy=pol, fabric=FabricConfig(), arena_state=arena_state))
        state = loop.init_state()
        assert isinstance(state, ArenaTrainState) == arena_state
        ds = ShardedLMDataset(cfg, batch=2, seq=64, ctx=ctx)
        it = iter(ds)
        state = loop.run(state, it, warm)          # compile everything
        ctl = loop.controller
        b0 = (ctl.fabric.stats["maintain_bytes_moved"]
              + ctl.stats["save_bytes_moved"])
        t0 = time.perf_counter()
        state = loop.run(state, it, steps)
        total_us = (time.perf_counter() - t0) / steps * 1e6
        bytes_step = (ctl.fabric.stats["maintain_bytes_moved"]
                      + ctl.stats["save_bytes_moved"] - b0) / steps
        ms = loop.metrics[warm:]
        # medians: single OS-scheduler spikes otherwise dominate the
        # handful of quick-mode steps and flip the recorded wall flags
        overhead_us = float(np.median(
            [m["overhead_seconds"] for m in ms])) * 1e6
        step_us = float(np.median([m["seconds"] for m in ms])) * 1e6
        out[name] = {"bytes": bytes_step, "total_us": total_us,
                     "overhead_us": overhead_us,
                     "losses": [m["loss"] for m in loop.metrics],
                     "resident":
                         ctl.fabric.stats["arena_resident_maintains"]}
        rows.append(csv_row(
            f"e2e_step_maintain_{name}", total_us,
            f"bytes_per_step={bytes_step:.0f};"
            f"overhead_us_per_step={overhead_us:.0f};"
            f"step_us={step_us:.0f};steps={steps};"
            f"resident_maintains={out[name]['resident']}"))
    ratio = out["pytree"]["bytes"] / max(out["arena"]["bytes"], 1)
    over_ratio = (out["pytree"]["overhead_us"]
                  / max(out["arena"]["overhead_us"], 1e-9))
    rows.append(csv_row(
        "e2e_step_maintain_headline", 0.0,
        f"bytes_ratio_pack_over_resident={ratio:.3f};"
        f"arena_fewer_bytes="
        f"{bool(out['arena']['bytes'] < out['pytree']['bytes'])};"
        f"loss_bit_equal="
        f"{bool(out['arena']['losses'] == out['pytree']['losses'])};"
        f"overhead_wall_ratio_pack_over_resident={over_ratio:.2f};"
        f"resident_overhead_faster={bool(over_ratio > 1.0)};"
        f"total_wall_ratio_pack_over_resident="
        f"{out['pytree']['total_us'] / max(out['arena']['total_us'], 1e-9):.2f}"))
    return rows


def _overlap_rows(quick: bool) -> list[str]:
    """Sync vs async every-step maintenance on the reduced LM.

    Both runs maintain every step; partial saves land every 4 steps
    (fraction=0.25 of full_interval=16 — NOT the scar every-step-save
    schedule, whose PRIORITY selection consumes the sweep's scores and
    so forces a settle on every step, leaving no overlap window).  The
    async run snapshots the live arena into the inactive replica slot
    behind an ``optimization_barrier`` copy and defers the fence to the
    next consume point, so the sweep runs under step N+1's compute.
    Gated: losses + running checkpoint bit-identical across modes, and
    async clean-step overhead p50 <= 0.5x the sync overhead p50.
    ``overlap_efficiency`` (hidden/total async sweep seconds) is
    RECORDED for the perf trajectory."""
    from repro.core.policy import RecoveryMode, SelectionStrategy
    from repro.data.pipeline import ShardedLMDataset
    from repro.sharding import single_device_ctx
    from repro.telemetry import Recorder
    from repro.training import TrainLoop, TrainLoopConfig

    cfg = get_config("qwen2-1.5b", reduced=True)
    warm = 2 if quick else 3
    steps = 8 if quick else 16
    out = {}
    rows = []
    for name, async_m in (("sync", False), ("async", True)):
        ctx = single_device_ctx()
        pol = CheckpointPolicy(fraction=0.25, full_interval=16,
                               strategy=SelectionStrategy.PRIORITY,
                               recovery=RecoveryMode.PARTIAL)
        rec = Recorder()
        loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
            policy=pol, fabric=FabricConfig(async_maintain=async_m),
            arena_state=True, recorder=rec))
        state = loop.init_state()
        ds = ShardedLMDataset(cfg, batch=2, seq=64, ctx=ctx)
        it = iter(ds)
        state = loop.run(state, it, warm)          # compile everything
        ctl = loop.controller
        b0 = ctl.fabric.stats["maintain_bytes_moved"]
        state = loop.run(state, it, steps)
        ms = loop.metrics[warm:]
        overhead_us = float(np.median(
            [m["overhead_seconds"] for m in ms])) * 1e6
        step_us = float(np.median([m["seconds"] for m in ms])) * 1e6
        trains = rec.tracer.intervals("scar/step/train")
        overlapping = sum(
            any(m0 < t1 and t0 < m1 for (t0, t1) in trains)
            for (m0, m1) in rec.tracer.intervals("scar/async_sweep"))
        eff = loop.overhead_summary()["overlap_efficiency"]
        out[name] = {
            "overhead_us": overhead_us,
            "losses": [m["loss"] for m in loop.metrics],
            "ckpt": np.asarray(ctl._ckpt_arena),
            "maint_bytes":
                (ctl.fabric.stats["maintain_bytes_moved"] - b0) / steps,
            "eff": eff,
        }
        rows.append(csv_row(
            f"maint_overlap_{name}", overhead_us,
            f"step_us={step_us:.0f};steps={steps};"
            f"maint_bytes_per_step={out[name]['maint_bytes']:.0f};"
            f"overlap_efficiency={eff:.3f};"
            f"maintain_spans_overlapping_train={overlapping};"
            f"fence_count={ctl.fabric.stats['fence_count']};"
            f"async_maintains={ctl.fabric.stats['async_maintains']};"
            f"published_epoch={ctl.fabric.published_epoch};"
            f"epoch_staleness="
            f"{ctl.fabric.replicas.staleness(int(state.step))}"))
    bit = (out["sync"]["losses"] == out["async"]["losses"]
           and out["sync"]["ckpt"].shape == out["async"]["ckpt"].shape
           and bool((out["sync"]["ckpt"] == out["async"]["ckpt"]).all()))
    ratio = (out["async"]["overhead_us"]
             / max(out["sync"]["overhead_us"], 1e-9))
    rows.append(csv_row(
        "maint_overlap_headline", 0.0,
        f"async_over_sync_overhead_ratio={ratio:.3f};"
        f"async_overhead_lt_sync={bool(ratio <= 0.5)};"
        f"overlap_bit_equal={bit};"
        f"overlap_efficiency={out['async']['eff']:.3f};"
        f"maint_bytes_ratio_async_over_sync="
        f"{out['async']['maint_bytes'] / max(out['sync']['maint_bytes'], 1):.3f}"))
    return rows


def _telemetry_rows(quick: bool, out_dir: str = "") -> list[str]:
    """Soak the reduced LM under an MTBF failure trace with a live
    Recorder attached: streams ``events.jsonl``, exports the Perfetto
    trace + run report (kept under ``out_dir`` when given), and asserts
    the perturbation ledger's bounds are bit-identical to the theory
    module's. Runs separately from the gated e2e rows, which keep the
    default NullRecorder and therefore the committed byte baselines."""
    import os

    from repro.core.iteration_cost import (iteration_cost_bound,
                                           single_perturbation_bound)
    from repro.data.pipeline import ShardedLMDataset
    from repro.sharding import single_device_ctx
    from repro.telemetry import Recorder, format_report, run_report
    from repro.training import TrainLoop, TrainLoopConfig

    cfg = get_config("qwen2-1.5b", reduced=True)
    steps = 12 if quick else 30
    tmp = None
    if not out_dir:
        tmp = tempfile.mkdtemp(prefix="bench_maintain_telemetry_")
        out_dir = tmp
    try:
        rec = Recorder(out_dir=out_dir)
        ctx = single_device_ctx()
        loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
            policy=CheckpointPolicy.scar(fraction=0.125, interval=4),
            fabric=FabricConfig(elastic=True),
            mtbf={"device": steps / 2.0}, heal_after=3,
            recorder=rec, seed=0))
        state = loop.init_state()
        ds = ShardedLMDataset(cfg, batch=2, seq=64, ctx=ctx)
        loop.run(state, iter(ds), steps)
        # price the faults with reference rates, then hold the ledger to
        # its contract: every bound bit-identical to core/iteration_cost
        c, x0_err = 0.9, 10.0
        rec.ledger.set_rates(c, x0_err)
        exact = all(
            e.bound == single_perturbation_bound(e.delta_norm, c,
                                                 T=e.step, x0_err=x0_err)
            for e in rec.ledger.entries)
        if rec.ledger.entries:
            exact = exact and (
                rec.ledger.cumulative_bound(steps)
                == float(iteration_cost_bound(
                    rec.ledger.delta_series(steps), c, x0_err)))
        over = loop.overhead_summary()
        report = run_report(rec, horizon=steps)
        with open(os.path.join(out_dir, "report.txt"), "w") as f:
            f.write(format_report(report) + "\n")
        rec.close()   # trace.json + metrics.json land next to the JSONL
        return [csv_row(
            "maint_telemetry", 0.0,
            f"ledger_bound_exact={bool(exact)};"
            f"events={len(rec.events)};"
            f"recoveries={report['recovery']['n_recoveries']};"
            f"overhead_p50_us={over['overhead_seconds_p50'] * 1e6:.0f};"
            f"overhead_p95_us={over['overhead_seconds_p95'] * 1e6:.0f};"
            f"clean_steps={over['overhead_clean_steps']};"
            f"artifacts={'temp' if tmp is not None else out_dir}")]
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def _multi_erasure_rows(quick: bool, out_dir: str = "") -> list[str]:
    """RS(k, 2) multi-erasure + silent-error soak on the reduced LM.

    One run with the RS tier: a simultaneous two-host loss (both events
    in the same trace step, recovered through the controller's combined
    multi-domain path) plus an injected in-arena bit flip under an
    every-step integrity scrub. One XOR control run with the identical
    loss schedule. REQUIRED flags (deterministic):

      rs_recovery_bit_equal   — the double loss recovered bit-exactly
                                through replicas + RS parity: zero
                                applied perturbation, no RUNNING_CKPT or
                                DISK blocks, no tier fallback.
      silent_error_detected   — the scrub caught the injected flip,
                                localized it to its block, corrected it
                                in place, and its ledger entry prices
                                the detection at ‖δ′‖² = 0.

    The XOR control's fallback count and paid perturbation ride along
    recorded — the staleness cost the RS tier deletes. The RS run's
    telemetry (events.jsonl + ledger.json with the priced entries) lands
    under ``<out_dir>/multi_erasure`` when ``--telemetry-out`` is given."""
    import dataclasses
    import os

    from repro.data.pipeline import ShardedLMDataset
    from repro.sharding import single_device_ctx
    from repro.telemetry import Recorder
    from repro.training import TrainLoop, TrainLoopConfig

    cfg = get_config("qwen2-1.5b", reduced=True)
    steps = 8 if quick else 14
    tmp = None
    if out_dir:
        out_dir = os.path.join(out_dir, "multi_erasure")
        os.makedirs(out_dir, exist_ok=True)
    else:
        tmp = tempfile.mkdtemp(prefix="bench_maintain_rs_")
        out_dir = tmp
    try:
        out = {}
        for name, rs in (("rs", 2), ("xor", 0)):
            rec = Recorder(out_dir=out_dir if name == "rs" else None)
            ctx = single_device_ctx()
            loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
                policy=CheckpointPolicy.scar(fraction=0.125, interval=4),
                fabric=FabricConfig(rs_parity=rs, elastic=True),
                # same-step host events = one correlated double loss
                # spanning both racks (kills primaries AND the
                # anti-affine replicas of some blocks). Hosts 1 + 3, not
                # 0: byte-balanced placement packs the many small leaves
                # onto host 0, and its pigeonhole surplus (more blocks
                # than the other hosts combined) forces same-host parity
                # groups no code survives losing — a real fallback the
                # XOR row prices, not the bit-equal path gated here.
                fail_schedule=[(4, "host", 1), (4, "host", 3)],
                flip_schedule=[6] if rs else None,
                scrub_interval=1 if rs else 0,
                recorder=rec, seed=0))
            state = loop.init_state()
            ds = ShardedLMDataset(cfg, batch=2, seq=64, ctx=ctx)
            loop.run(state, iter(ds), steps)
            fails = [f for m in loop.metrics
                     for f in m.get("failures", [])]
            assert len(fails) == 1 and len(fails[0]["events"]) == 2
            scrubs = [m["scrub"] for m in loop.metrics if "scrub" in m]
            out[name] = {
                "counts": fails[0]["tier_counts"],
                "lost": fails[0]["lost_blocks"],
                "applied_sq": fails[0]["applied_sq"],
                "fallbacks": len(fails[0].get("tier_fallbacks", [])),
                "detected": sum(s["detected"] for s in scrubs),
                "corrected": sum(s["corrected"] for s in scrubs),
                "ledger": rec.ledger,
                "rec": rec,
            }
        rs_, xor_ = out["rs"], out["xor"]
        bit_equal = bool(
            rs_["lost"] > 0 and rs_["applied_sq"] == 0.0
            and rs_["counts"]["RUNNING_CKPT"] == 0
            and rs_["counts"]["DISK"] == 0 and rs_["fallbacks"] == 0)
        silent_entries = [
            e for e in rs_["ledger"].entries
            if (e.tier_counts or {}).get("SILENT_ERROR")]
        detected = bool(
            rs_["detected"] == 1 and rs_["corrected"] == 1
            and len(silent_entries) == 1
            and silent_entries[0].applied_sq == 0.0)
        with open(os.path.join(out_dir, "ledger.json"), "w") as f:
            json.dump({"summary": rs_["ledger"].summary(),
                       "entries": [dataclasses.asdict(e)
                                   for e in rs_["ledger"].entries]},
                      f, indent=2, default=float)
        rs_["rec"].close()
        xor_["rec"].close()
        return [csv_row(
            "tier_soak_multi_erasure", 0.0,
            f"rs_recovery_bit_equal={bit_equal};"
            f"silent_error_detected={detected};"
            f"rs_lost_blocks={rs_['lost']};"
            f"rs_parity_blocks={rs_['counts']['PARITY']};"
            f"xor_fallbacks={xor_['fallbacks']};"
            f"xor_ckpt_blocks="
            f"{xor_['counts']['RUNNING_CKPT'] + xor_['counts']['DISK']};"
            f"xor_applied_sq={xor_['applied_sq']:.3e};"
            f"artifacts={'temp' if tmp is not None else out_dir}")]
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def _sharded_rows(quick: bool) -> list[str]:
    """SPMD rows: the sharded arena sweep and the elastic-mesh soak.

    These need more than one XLA device, which this process deliberately
    does not have (the committed single-device byte baselines would
    shift), so the measurement runs in a subprocess with
    ``--xla_force_host_platform_device_count=8`` — see
    ``benchmarks/_sharded_probe.py`` for what each number means. The
    headline flags (``sharded_loss_bit_equal``, ``sharded_bytes_le_pack``,
    ``elastic_cycle_ok``) are deterministic and REQUIRED by
    ``check_maintain_regression``; the wall-clock rides along recorded.

    The child is pinned to the CPU: its forced 8-device topology is a CPU
    construct, and on a chip machine this process already holds the chip
    the child would otherwise try to open. Its rows say ``platform=cpu``."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks._sharded_probe"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded probe failed (rc={proc.returncode}):\n{proc.stderr}")
    res = json.loads(proc.stdout.splitlines()[-1])
    sh, el = res["sharded"], res["elastic"]
    a = sh["arena"]
    rows = [csv_row(
        "maint_sweep_sharded", a["overhead_us"],
        f"platform=cpu;bytes_per_step={a['bytes_per_step']:.0f};"
        f"pack_bytes_per_step={sh['pytree']['bytes_per_step']:.0f};"
        f"shards={sh['shards']};"
        f"sharded_loss_bit_equal={bool(sh['loss_bit_equal'])};"
        f"sharded_bytes_le_pack={bool(sh['bytes_le_pack'])};"
        f"live_packs={a['live_packs']};"
        f"resident_maintains={a['resident_maintains']};"
        f"ici_bytes_per_maintain={a['ici_per_maintain']:.0f};"
        f"dcn_bytes_per_maintain={a['dcn_per_maintain']:.0f}")]
    rows.append(csv_row(
        "tier_soak_elastic_mesh", el["us_per_step"],
        f"platform=cpu;steps={el['steps']};mesh_resizes={el['mesh_resizes']};"
        f"min_shards={el['min_shards']};final_shards={el['final_shards']};"
        f"live_packs={el['live_packs']};"
        f"losses_finite={bool(el['losses_finite'])};"
        f"elastic_cycle_ok={bool(el['cycle_ok'])}"))
    return rows


def run(trials: int = 4, quick: bool = False,
        telemetry_out: str = "") -> list[str]:
    rows = _kernel_check_rows(quick)
    params = _reduced_params()
    sweep_rows = _sweep_rows(params, quick)
    rows.extend(sweep_rows)
    rows.extend(_padding_rows(params, quick))
    rows.extend(_partial_save_rows(params, quick))
    rows.extend(_store_rows(params, quick))
    e2e_rows = _e2e_rows(quick)
    rows.extend(e2e_rows)
    f32_bit = any(r.startswith("e2e_step_maintain_headline")
                  and "loss_bit_equal=True" in r for r in e2e_rows)
    rows.extend(_quant_rows(params, quick, f32_bit))
    rows.extend(_overlap_rows(quick))
    rows.extend(_sharded_rows(quick))
    rows.extend(_telemetry_rows(quick, telemetry_out))
    rows.extend(_multi_erasure_rows(quick, telemetry_out))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="",
                    help="also write rows as JSON (CI perf trajectory)")
    ap.add_argument("--telemetry-out", default="",
                    help="keep the soak's telemetry artifacts "
                         "(events.jsonl, trace.json, metrics.json, "
                         "report.txt) in this directory")
    args = ap.parse_args()
    rows = run(quick=args.quick, telemetry_out=args.telemetry_out)
    print("name,us_per_call,derived")
    for row in rows:
        print(row, flush=True)
    if args.out:
        parsed = []
        for row in rows:
            name, us, derived = row.split(",", 2)
            parsed.append({"name": name, "us_per_call": float(us),
                           "derived": derived})
        with open(args.out, "w") as f:
            json.dump({"bench": "maintain", "quick": args.quick,
                       "rows": parsed}, f, indent=2)


if __name__ == "__main__":
    main()
