"""SPMD LM trainer with SCAR fault tolerance as a first-class feature.

``TrainLoop`` owns:

- the jitted ``train_step`` (value_and_grad + optimizer update), with
  params/opt-state sharded per :mod:`repro.sharding.partition` when a mesh
  is present;
- an :class:`repro.core.controller.FTController` over the *parameter*
  PyTree (optimizer moments are recoverable state too — SCAR checkpoints
  params; Adam moments after a partial restore are simply kept, which is
  itself a perturbation the theory covers; see DESIGN.md);
- **arena-resident training state** (the default when the controller's
  fabric is arena-capable and no mesh is configured): the live params are
  the flat parameter arena (:class:`~repro.training.train_state.ArenaTrainState`),
  donated through the jitted step, and the per-step controller calls
  (``maintain``/``maybe_checkpoint``) consume ``state.arena`` directly —
  the maintenance sweep runs pack-free (pure 2-read/1-write) and the
  partial save sources straight from the training state. The PyTree path
  stays available via ``TrainLoopConfig(arena_state=False)`` for
  non-arena-compatible models;
- optional fault injection (iteration sampled from a geometric
  distribution, as in the paper's §5.3), either the paper's uniform
  block-loss model or correlated whole-domain loss
  (``fail_domain="host"``) routed through the checkpoint fabric's tier
  planner (:mod:`repro.fabric`);
- trace-driven soak mode (``mtbf=``): an MTBF-sampled multi-event failure
  schedule where failed domains stay dead in the fabric's cluster view
  (elastic fabrics re-home/re-seed across the survivors) and optionally
  heal ``heal_after`` steps later — long-horizon degraded-mode training
  with per-event tier/perturbation accounting in ``metrics`` and
  ``controller.stats["events"]``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.controller import FTController
from repro.core.policy import CheckpointPolicy
from repro.models import get_model
from repro.optim.optimizers import Optimizer, adamw
from repro.sharding.partition import DistContext, named_shardings
from repro.telemetry.recorder import NULL_RECORDER, Histogram
from repro.telemetry.spans import SpanTracer, merge_rollup
from repro.training.train_state import ArenaTrainState, TrainState

PyTree = Any


@dataclasses.dataclass
class TrainLoopConfig:
    policy: Optional[CheckpointPolicy] = None
    fail_prob: float = 0.0          # per-iteration geometric failure prob
    fail_fraction: float = 0.5      # fraction of blocks lost per failure
    fail_domain: str = "uniform"    # "uniform" | "device" | "host" | "rack"
    fabric: Optional[Any] = None    # FabricConfig → tiered recovery fabric
    # arena-resident training state: the live params ARE the flat arena
    # (needs an arena-capable fabric; works single-device and on SPMD
    # meshes — the arena then carries the flat per-device sharding and
    # the sweep runs shard-local). When requested but the fabric cannot
    # engage it (non-arena dtypes, custom scorers, partial tiers) the
    # loop warns and records a ``fabric/arena_gated`` event before
    # falling back to the PyTree path — set False to silence that and
    # force the tree path deliberately.
    arena_state: bool = True
    # elastic SPMD mesh: with a meshed elastic fabric, a domain loss
    # shrinks the mesh to the survivors (arena relayouted, step re-jitted,
    # training continues) and a heal re-grows it. None = auto (on exactly
    # when the arena path engaged on a mesh and the fabric is elastic).
    elastic_mesh: Optional[bool] = None
    # record per-step maintenance overhead (``overhead_seconds`` in
    # metrics): blocks on the sweep's device outputs each step so the
    # number is the maintenance work, not its dispatch. Disable on
    # accelerators when the sweep should overlap the next step's
    # dispatch instead of being measured.
    measure_overhead: bool = True
    # trace-driven soak mode: per-domain-kind MTBF means (in steps) sampled
    # into a multi-event failure schedule each run(); failed domains stay
    # dead in the cluster view, and optionally heal ``heal_after`` steps
    # later (re-admitting their devices to the placement engine)
    mtbf: Optional[dict] = None     # e.g. {"host": 200.0, "device": 80.0}
    # deterministic event schedule: (step, kind, index) triples (or
    # FailureEvent objects) applied exactly, alongside any mtbf-sampled
    # trace — reproducible soaks and elastic-mesh tests
    fail_schedule: Optional[list] = None
    heal_after: Optional[int] = None
    # silent-error soak: in-arena bit flips injected at these steps — an
    # int step (random block/word/bit) or a (step, block) pair targeting
    # one block. The flip corrupts the replica snapshot invisibly; an RS
    # fabric's scrub detects/corrects it, while an XOR fabric carries the
    # corruption into its next replica-tier recovery where the measured
    # ‖δ′‖² prices the undetected window honestly.
    flip_schedule: Optional[list] = None
    # integrity-scrub cadence in steps (0 = never). Runs the fabric's
    # syndrome pass after maintenance; detections land in metrics and the
    # perturbation ledger at ‖δ′‖² ≈ 0 (corrected in place).
    scrub_interval: int = 0
    # telemetry sink (repro.telemetry.Recorder): events/spans/ledger for
    # the whole loop + its controller/fabric/store. Default NULL_RECORDER —
    # every emit point is a no-op and the hot path is unchanged.
    recorder: Optional[Any] = None
    log_every: int = 10
    seed: int = 0

    def __post_init__(self):
        if self.fail_domain != "uniform" and self.fabric is None:
            raise ValueError("correlated fail_domain injection needs a "
                             "fabric (set TrainLoopConfig.fabric)")
        if (self.mtbf is not None or self.fail_schedule) \
                and self.fabric is None:
            raise ValueError("trace-driven soak mode needs a fabric "
                             "(set TrainLoopConfig.fabric)")
        if (self.flip_schedule or self.scrub_interval) \
                and self.fabric is None:
            raise ValueError("bit-flip injection / integrity scrubs need "
                             "a fabric (set TrainLoopConfig.fabric)")


def _clean(rec: dict) -> bool:
    """A step whose maintenance overhead was measured and that no
    failure, heal or injected fault touched."""
    return ("overhead_seconds" in rec and "failures" not in rec
            and "heals" not in rec and "failure" not in rec)


class TrainLoop:
    def __init__(self, cfg: ModelConfig, ctx: DistContext,
                 optimizer: Optional[Optimizer] = None,
                 loop_cfg: Optional[TrainLoopConfig] = None,
                 store=None):
        self.cfg = cfg
        self.ctx = ctx
        self.ops = get_model(cfg)
        self.optimizer = optimizer or adamw(3e-4)
        self.loop_cfg = loop_cfg or TrainLoopConfig()
        self._store = store
        self._rng = np.random.default_rng(self.loop_cfg.seed)
        self.controller: Optional[FTController] = None
        self.metrics: list[dict] = []
        self._redundancy_flags: list[bool] = []
        self.arena_layout = None          # set when the arena path engages
        # elastic-mesh bookkeeping: the base (full) mesh, the mesh the
        # step currently runs on, which fabric logical device sits at
        # each current mesh position, and whether a resize has happened
        # (batches are re-placed onto the current mesh only after one —
        # the never-resized path is byte-for-byte the old loop)
        self._base_mesh = ctx.mesh
        self._cur_mesh = ctx.mesh
        self._mesh_logical = (np.arange(ctx.mesh.devices.size, dtype=np.int32)
                              if ctx.mesh is not None else None)
        self._mesh_resized = False
        self.recorder = (self.loop_cfg.recorder
                         if self.loop_cfg.recorder is not None
                         else NULL_RECORDER)
        # clean-step maintenance-overhead distribution: feeds the
        # p50/p95/max in overhead_summary(). A real recorder shares its
        # named histogram; otherwise a private one (same type, no sink)
        self._overhead_hist = (
            self.recorder.histogram("train/overhead_seconds")
            if self.recorder.enabled else Histogram())
        # host spans are on with or without a recorder: each step's record
        # takes this tracer's rollup (a recorder's tracer also keeps the
        # span records for trace.json)
        self.tracer = (self.recorder.tracer if self.recorder.enabled
                       else SpanTracer(keep=False))

        from repro.training.step import make_train_step
        self._train_step = jax.jit(
            make_train_step(self.ops, cfg, ctx, self.optimizer),
            donate_argnums=(0,))
        self._arena_step = None           # built lazily by init_state

    # -- initialization ------------------------------------------------------

    def init_state(self, rng: Optional[jax.Array] = None):
        rng = rng if rng is not None else jax.random.PRNGKey(self.loop_cfg.seed)
        if self.ctx.mesh is not None:
            p_shape = jax.eval_shape(
                lambda r: self.ops.init_params(r, self.cfg), rng)
            shardings = named_shardings(p_shape, self.ctx)
            params = jax.jit(self.ops.init_params, static_argnums=(1,),
                             out_shardings=shardings)(rng, self.cfg)
        else:
            params = self.ops.init_params(rng, self.cfg)
        if self.loop_cfg.policy is not None:
            self.controller = FTController(params, self.loop_cfg.policy,
                                           store=self._store,
                                           fabric=self.loop_cfg.fabric,
                                           recorder=self.loop_cfg.recorder,
                                           mesh=self.ctx.mesh)
        if (self.loop_cfg.arena_state and self.controller is not None
                and self.controller.arena_ready):
            # arena-resident training state: pack once here, never again —
            # every subsequent step donates the arena through the jitted
            # update and the controller reads it in place. On a mesh the
            # pack lands the flat per-device sharding and the moments are
            # placed to match, so the whole state is SPMD from step one.
            self.arena_layout = self.controller.arena_layout
            if self._arena_step is None:
                from repro.training.step import make_arena_train_step
                self._arena_step = jax.jit(
                    make_arena_train_step(self.ops, self.cfg, self.ctx,
                                          self.optimizer,
                                          self.arena_layout),
                    donate_argnums=(0,))
            arena = self.controller.pack_live(params)
            state = ArenaTrainState.create(arena, self.optimizer,
                                           self.arena_layout)
            if self.ctx.mesh is not None:
                from repro.sharding.partition import shard_arena_state
                state = shard_arena_state(state, self.ctx.mesh)
            return state
        if self.loop_cfg.arena_state and self.controller is not None \
                and self.loop_cfg.fabric is not None:
            # arena-resident state was requested (the default) with a
            # fabric, but the fabric could not build an arena layout.
            # Since the word-level arena, quantized dtypes (bf16/f16/fp8/
            # int8…) are arena-native; only truly word-unpackable leaves
            # (f64, int64, complex, bool), custom scorers, partial tiers,
            # or mixed-dtype models on an SPMD mesh gate here. Never fall
            # back silently: the tree path packs every maintained step, a
            # real perf cliff on SPMD meshes.
            import warnings
            msg = ("arena_state=True but the fabric is not arena-capable "
                   "(word-unpackable dtype such as f64/int64/bool, custom "
                   "scorer, partial tiers, or mixed dtypes on a mesh); "
                   "falling back to PyTree training state (per-step packs). "
                   "Set TrainLoopConfig(arena_state=False) to silence.")
            warnings.warn(msg, stacklevel=2)
            if self.recorder.enabled:
                self.recorder.event("fabric/arena_gated", reason=msg)
        return TrainState.create(params, self.optimizer)

    # -- live-state plumbing (both representations) --------------------------

    @staticmethod
    def _live(state):
        """The live parameter value in its canonical form: the flat arena
        for ArenaTrainState, the tree for TrainState. Controller entry
        points accept either."""
        return state.arena if isinstance(state, ArenaTrainState) \
            else state.params

    @staticmethod
    def _with_live(state, new_live):
        if isinstance(state, ArenaTrainState):
            return ArenaTrainState(new_live, state.opt_state, state.step,
                                   state.layout)
        return TrainState(new_live, state.opt_state, state.step)

    # -- elastic SPMD mesh ---------------------------------------------------

    def _elastic_enabled(self, state) -> bool:
        """Whether this run() may shrink/re-grow the mesh on domain
        events: arena-resident state on a mesh with an elastic meshed
        fabric. ``elastic_mesh=True`` with missing prerequisites is a
        config error, not a silent no-op."""
        want = self.loop_cfg.elastic_mesh
        if want is False:
            return False
        fab = self.controller.fabric if self.controller is not None else None
        ok = (isinstance(state, ArenaTrainState)
              and self._base_mesh is not None
              and fab is not None and fab.cfg.elastic
              and getattr(fab, "mesh", None) is not None)
        if want and not ok:
            raise ValueError(
                "elastic_mesh=True needs arena-resident state on a mesh "
                "with an elastic meshed fabric (FabricConfig(elastic=True) "
                "and a DistContext mesh whose size matches n_devices)")
        return ok

    def _place_batch(self, batch):
        """Re-place a batch onto the current (possibly shrunk) mesh:
        batch dim over the data axis. Only runs after a resize — the
        dataset's own placement targets the base mesh, and arrays
        committed there cannot mix with survivor-mesh state in one jit."""
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self._cur_mesh
        sh = NamedSharding(mesh, PartitionSpec("data"))
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(np.asarray(x), sh), batch)

    def _maybe_resize(self, state, step: int, rec: dict):
        """Shrink or re-grow the mesh to the fabric's alive-device set.

        The survivor count is the largest k ≤ alive that divides the
        global batch (the data axis must tile it); survivors keep their
        fabric logical ids, so failure domains stay meaningful on the
        shrunk topology. The arena and the 1-D adam moments relayout
        bit-exactly (the data region is shard-count-invariant; only the
        zero pad tail is resized), the step re-jits against the survivor
        mesh, and the fabric re-homes/re-seeds/re-stripes before an
        immediate forced maintain so every tier is fresh on the new
        placement."""
        fab = self.controller.fabric
        alive = fab.view.alive_devices()
        k = int(alive.size)
        bdim = self._last_batch_dim or k
        while k > 1 and bdim % k != 0:
            k -= 1
        survivors = alive[:k]
        if np.array_equal(survivors, self._mesh_logical):
            return state
        from repro.launch.mesh import mesh_devices, survivor_mesh
        base_devs = mesh_devices(self._base_mesh)
        if k == len(base_devs):
            new_mesh = self._base_mesh    # full re-grow: original shape
        else:
            new_mesh = survivor_mesh([base_devs[int(i)] for i in survivors])
        old_layout = self.arena_layout
        new_layout = fab.resize_mesh(new_mesh, survivors, step=step)
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core.arena import relayout_arena
        from repro.sharding.partition import arena_sharding
        ash = arena_sharding(new_mesh)
        rep_sh = NamedSharding(new_mesh, PartitionSpec())

        def move(x):
            if getattr(x, "ndim", None) == 1 \
                    and x.size == old_layout.total_words:
                return relayout_arena(x, old_layout, new_layout,
                                      out_sharding=ash)
            if getattr(x, "ndim", None) == 1 \
                    and x.size == old_layout.total_values:
                # value-domain moment mirrors of a quantized layout
                # (total_values > total_words); same shard-count-invariant
                # data region argument, value-granular
                from repro.core.arena import relayout_values
                return relayout_values(x, old_layout, new_layout,
                                       out_sharding=ash)
            # scalars (adam step count) re-commit replicated on the new
            # mesh — a leaf left on the old device set cannot enter the
            # re-jitted step
            return jax.device_put(x, rep_sh)

        state = ArenaTrainState(move(state.arena),
                                jax.tree_util.tree_map(move, state.opt_state),
                                move(state.step), new_layout)
        from repro.training.step import make_arena_train_step
        ctx = dataclasses.replace(self.ctx, mesh=new_mesh)
        self._arena_step = jax.jit(
            make_arena_train_step(self.ops, self.cfg, ctx, self.optimizer,
                                  new_layout),
            donate_argnums=(0,))
        self.arena_layout = new_layout
        self.controller.rebind_arena()
        # tiers were invalidated by the re-home/re-stripe: refresh them
        # from the relayouted live arena on the new placement
        fab.maintain(step, state.arena, force=True)
        self._cur_mesh = new_mesh
        self._mesh_logical = survivors
        self._mesh_resized = True
        rec["mesh_resize"] = {"shards": int(new_layout.shards),
                              "alive_devices": int(alive.size)}
        return state

    # -- run loop -------------------------------------------------------------

    def run(self, state, batches, n_steps: int,
            on_step: Optional[Callable[[int, float], None]] = None):
        """Train ``n_steps`` steps, appending one record per step to
        :attr:`metrics`: ``step``, ``loss``, ``seconds`` (the train step,
        fenced by reading the loss), ``overhead_seconds`` (maintain +
        save, and the sync-mode fence on the sweep), event fields, and the
        step's span rollup (:func:`repro.telemetry.spans.new_rollup`):
        ``spans``, ``compiles``, ``bytes``, ``store_lag_s``. Background
        writes that land in the closing flush go on the last record."""
        it = iter(batches)
        events_at = self._sample_trace(n_steps)
        heal_at: dict[int, list] = {}
        flips_at: dict[int, list] = {}
        for fl in (self.loop_cfg.flip_schedule or []):
            s, blk = (int(fl[0]), int(fl[1])) \
                if isinstance(fl, (tuple, list)) else (int(fl), None)
            flips_at.setdefault(max(1, min(s, n_steps)), []).append(blk)
        elastic = self._elastic_enabled(state)
        self._last_batch_dim = None
        step0 = int(state.step)
        self.tracer.take()          # what ran before this run is not a step's
        for i in range(1, n_steps + 1):
            with self.tracer.span("scar/step", step=step0 + i):
                state, rec = self._step(i, state, it, elastic, events_at,
                                        heal_at, flips_at)
            rec.update(self.tracer.take())
            self.metrics.append(rec)
            if on_step is not None:
                on_step(i, rec["loss"])
        # epoch boundary: settle any in-flight async sweep (the deferred
        # fence's last consume point) and drain the background store
        # writer so run() returns with redundancy published and durable —
        # in async mode this is where store flushes live now, not on the
        # per-step hot path
        if self.controller is not None:
            if self.controller.fabric is not None:
                self.controller.fabric.block_until_maintained()
            if self.controller.store is not None \
                    and hasattr(self.controller.store, "flush"):
                self.controller.store.flush()
        if n_steps > 0:
            # writes that landed in the closing flush: the last step's
            merge_rollup(self.metrics[-1], self.tracer.take())
        return state

    def _step(self, i: int, state, it, elastic: bool, events_at: dict,
              heal_at: dict, flips_at: dict):
        """One iteration of :meth:`run` (inside its ``scar/step`` span):
        the train step, then maintenance, the save, and this step's
        domain events, heals, bit flips and scrubs."""
        # re-read each iteration: an elastic resize swaps the jitted
        # step under our feet mid-run
        step_fn = (self._arena_step if isinstance(state, ArenaTrainState)
                   else self._train_step)
        batch = next(it)
        if elastic:
            self._last_batch_dim = int(
                jax.tree_util.tree_leaves(batch)[0].shape[0])
            if self._mesh_resized:
                batch = self._place_batch(batch)
        t0 = time.perf_counter()
        with self.tracer.span("scar/step/train"):
            state, loss = step_fn(state, batch)
            loss = float(loss)   # fences on the loss output
        dt = time.perf_counter() - t0
        rec = {"step": int(state.step), "loss": loss, "seconds": dt}
        if self.controller is None:
            return state, rec
        # maintain first: the arena maintenance sweep scores the blocks
        # against the running checkpoint in the same read, and a
        # same-step partial save below reuses those scores
        tm0 = time.perf_counter()
        live = self._live(state)
        self.controller.maintain(int(state.step), live)
        if self.controller.maybe_checkpoint(int(state.step), live):
            rec["checkpointed"] = True
        fab = self.controller.fabric
        async_mode = (fab is not None
                      and getattr(fab.cfg, "async_maintain", False))
        # per-step fault-tolerance overhead (maintain + save), excluding
        # the rare failure/heal events below — the examples report this
        # next to the step time. Sync mode blocks on the sweep's device
        # outputs first: checkpoint_now only blocks on save steps, and
        # under async dispatch a maintain-only step would otherwise book
        # dispatch time here and push the sweep's compute into the NEXT
        # step's "seconds". Async-maintain mode must NOT block — hiding
        # the sweep under the next step is the whole point; its overhead
        # is the dispatch cost, and the sweep's un-hidden remainder books
        # into the fabric's fence histogram at the deferred fence instead.
        if self.loop_cfg.measure_overhead:
            if fab is not None and not async_mode:
                with self.tracer.span("scar/step/fence"):
                    fab.block_until_maintained()
            rec["overhead_seconds"] = time.perf_counter() - tm0
        evs = events_at.pop(i, [])
        if len(evs) > 1:
            # simultaneous multi-domain loss: every event resolves
            # against the pre-failure view and the union recovers in ONE
            # tier-planned pass (the RS tier's multi-erasure case —
            # applying them sequentially would let the first recovery's
            # re-encode hide the correlation)
            live, info = self.controller.on_domain_events(
                live, [(e.kind, e.index) for e in evs],
                step=int(state.step))
            state = self._with_live(state, live)
            rec.setdefault("failures", []).append(info)
            if self.loop_cfg.heal_after is not None:
                applied = {(a["kind"], a["index"])
                           for a in info.get("events", [])}
                for ev in evs:
                    if (ev.kind, ev.index) in applied:
                        heal_at.setdefault(i + self.loop_cfg.heal_after,
                                           []).append(ev)
        elif evs:
            ev = evs[0]
            live, info = self.controller.on_domain_event(
                live, ev.kind, ev.index, step=int(state.step))
            state = self._with_live(state, live)
            rec.setdefault("failures", []).append(info)
            if (self.loop_cfg.heal_after is not None
                    and not info.get("skipped")):
                heal_at.setdefault(i + self.loop_cfg.heal_after,
                                   []).append(ev)
        for ev in heal_at.pop(i, []):
            heal = self.controller.heal_domain(ev.kind, ev.index, live,
                                               step=int(state.step))
            rec.setdefault("heals", []).append(heal)
        if elastic and ("failures" in rec or "heals" in rec):
            # domain events changed the survivor set: shrink the mesh to
            # the alive devices (or re-grow after a heal), relayout the
            # arena state, and re-jit the step — training continues on
            # the new topology next step
            state = self._maybe_resize(state, int(state.step), rec)
        for blk in flips_at.pop(i, []):
            # soft-error injection: corrupt the replica snapshot
            # invisibly — only the scrub (or the honestly-priced
            # perturbation of a later replica recovery) sees it
            if fab is not None and fab.replicas is not None \
                    and fab.replicas.arena is not None:
                where = fab.inject_arena_bit_flip(block=blk, rng=self._rng)
                rec.setdefault("bit_flips", []).append(where)
        if (self.loop_cfg.scrub_interval
                and i % self.loop_cfg.scrub_interval == 0):
            sc = self.controller.scrub(step=int(state.step))
            if sc["checked"]:
                rec["scrub"] = {"detected": sc["detected"],
                                "corrected": sc["corrected"]}
        if (self.loop_cfg.fail_prob > 0
                and self._rng.random() < self.loop_cfg.fail_prob):
            new_live, info = self._inject(state)
            state = self._with_live(state, new_live)
            rec["failure"] = info
        # clean-step overhead sample: failure/heal steps are excluded so
        # the distribution answers "what does fault tolerance cost when
        # nothing is on fire"
        if _clean(rec):
            self._overhead_hist.observe(rec["overhead_seconds"])
        if fab is not None:
            # per-step placement health — availability_summary() folds
            # these into the soak goodput report
            full = fab.redundancy_state()["full"]
            rec["redundancy_full"] = full
            self._redundancy_flags.append(full)
        return state, rec

    def availability_summary(self) -> dict:
        """Aggregate this loop's soak accounting (per-event tier counts +
        per-step redundancy flags) into the availability/goodput report —
        see :func:`repro.fabric.availability.summarize_availability`."""
        from repro.fabric.availability import summarize_availability
        events = (self.controller.stats["events"]
                  if self.controller is not None else [])
        out = summarize_availability(events, self._redundancy_flags)
        if self.recorder.enabled:
            led = self.recorder.ledger.summary()
            out["telemetry"] = {
                "events_total": len(self.recorder.events),
                "recoveries_priced": led["n_events"],
                "iterations_owed_total": led["iterations_owed_total"]}
        return out

    def overhead_summary(self) -> dict:
        """Per-step wall-clock split (train step vs fault-tolerance
        maintain+save) plus the fabric's accounted maintenance bytes —
        what the arena-resident refactor is buying per step. The
        ``overhead_seconds_*`` distribution covers **clean steps only**
        (failure/heal-event steps excluded at observe time) and comes
        from the telemetry histogram, so the p95 a dashboards reads and
        the one reported here are the same samples.

        ``phases`` attributes the clean-step overhead, read from each
        step's span rollup: ``sweep`` (``scar/maintain``), ``save``
        (``scar/save``), ``fence`` (blocking waits — the loop's sync-mode
        ``scar/step/fence`` merged with the fabric's deferred async-fence
        waits). ``overlap_efficiency`` is the fraction of
        async sweep wall-clock hidden under the trainer's compute
        (0.0 in sync mode — nothing is overlapped)."""
        steps = [m["seconds"] for m in self.metrics]
        over = self._overhead_hist.summary()
        out = {"steps": len(steps),
               "step_seconds_mean": float(np.mean(steps)) if steps else 0.0,
               "overhead_seconds_mean": over["mean"],
               "overhead_seconds_p50": over["p50"],
               "overhead_seconds_p95": over["p95"],
               "overhead_seconds_max": over["max"],
               "overhead_clean_steps": over["count"],
               "arena_state": self.arena_layout is not None}
        fab = (self.controller.fabric
               if self.controller is not None else None)
        sweep, save, fence = Histogram(), Histogram(), Histogram()
        for m in filter(_clean, self.metrics):
            sp = m["spans"]
            # a save's forced refresh is a sweep inside the save: book it
            # to the save, as the save's own wall clock always did
            sweep.observe(sp.get("scar/maintain", 0.0)
                          - sp.get("scar/save/refresh", 0.0))
            save.observe(sp.get("scar/save", 0.0))
            if "scar/step/fence" in sp:
                fence.observe(sp["scar/step/fence"])
        if fab is not None:
            fence.samples += list(fab.fence_hist.samples)
        out["phases"] = {"sweep": sweep.summary(), "save": save.summary(),
                         "fence": fence.summary()}
        out["overlap_efficiency"] = (fab.overlap_efficiency()
                                     if fab is not None else 0.0)
        if self.controller is not None and self.controller.fabric is not None:
            fab = self.controller.fabric
            # one parity encode per maintained step (swept or not) under
            # the default same-interval tiers — the per-step denominator
            maintains = max(fab.stats["parity_encodes"], 1)
            out["maintain_bytes_per_step"] = (
                fab.stats["maintain_bytes_moved"] // maintains)
            out["arena_resident_maintains"] = \
                fab.stats["arena_resident_maintains"]
            out["async_maintains"] = fab.stats["async_maintains"]
        return out

    def _sample_trace(self, n_steps: int) -> dict[int, list]:
        """Soak schedule for one run(): loop-iteration → events. The
        mtbf-sampled trace plus any explicit ``fail_schedule`` entries.
        Empty without either (or without a controller to recover)."""
        if self.controller is None or self.controller.fabric is None:
            return {}
        trace = []
        if self.loop_cfg.mtbf is not None:
            trace += self.controller.fabric.domains.sample_failure_trace(
                self._rng, n_steps, self.loop_cfg.mtbf)
        if self.loop_cfg.fail_schedule:
            from repro.fabric.domains import FailureEvent
            trace += [ev if isinstance(ev, FailureEvent)
                      else FailureEvent(int(ev[0]), str(ev[1]), int(ev[2]))
                      for ev in self.loop_cfg.fail_schedule]
        events_at: dict[int, list] = {}
        for ev in sorted(trace, key=lambda e: e.step):
            events_at.setdefault(max(1, min(ev.step, n_steps)),
                                 []).append(ev)
        return events_at

    def _inject(self, state) -> tuple[Any, dict]:
        """One failure event per the configured model (uniform/correlated).
        Returns the recovered live value in the state's own form."""
        live = self._live(state)
        if self.loop_cfg.fail_domain == "uniform":
            lost = self.controller.sample_failure(self.loop_cfg.fail_fraction)
            return self.controller.on_failure(live, lost,
                                              step=int(state.step))
        lost, failed = self.controller.sample_domain_failure(
            self.loop_cfg.fail_domain)
        return self.controller.on_failure(live, lost,
                                          failed_devices=failed,
                                          step=int(state.step))

    def inject_failure(self, state, fraction: Optional[float] = None,
                       ) -> tuple[Any, dict]:
        """Explicit failure injection (for experiments/examples)."""
        assert self.controller is not None, "enable a CheckpointPolicy first"
        if fraction is not None:
            lost = self.controller.sample_failure(fraction)
            new_live, info = self.controller.on_failure(
                self._live(state), lost, step=int(state.step))
        else:
            new_live, info = self._inject(state)
        return self._with_live(state, new_live), info
