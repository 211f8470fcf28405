"""One run of one cell: build the job, prove its first steps, warm up,
measure a window of training, then decide ``correct``.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names a configuration (``configs/<name>.json``, whose ``reference`` names
``configs/<reference>.py``) and a traffic mix (``traffic/<name>.json``);
the cell's per-layer metrics name readers ``metrics/<name>.py``. A later
cell, configuration, traffic mix or metric is a new file and a new entry,
never an edit here.

The run, in order:

1. weights from the seed (the reference module's specs, one jitted
   call), handed to the program's ``TrainLoop`` on the arena-resident
   path with the traffic's SCAR policy, fabric and a sharded store (a
   traffic mix whose ``policy`` is null trains with no fault tolerance;
   a cell on several chips runs on the traffic's ``mesh``);
2. steps 1-3 through ``TrainLoop.run`` on the seeded token stream,
   reading each loss, the first gradient's per-leaf norms (from AdamW's
   first moment) and the per-leaf norms of the weights' change;
3. warm-up: one save period that compiles every shape the window uses
   (a save step and, with host loss, one loss of each host), then one
   more period, timed, that sets the window's length in whole periods;
4. the window: ``TrainLoop.run`` over whole save periods, ending when
   the last sweep is fenced and the store's background writes drained;
5. after it: peak device memory, then the exact checks of the fabric's
   state (replica, parity, the last save in memory and on disk, every
   recovery), then the program's state is freed and the plain reference
   retrains steps 1-3 for the comparison.

The parity tier is checked against the plain reference of its codec,
``codecs/<kind>.py`` (``xor``; ``rs`` for ``FabricConfig.rs_parity`` > 0).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_module(path: str):
    name = "bench_" + os.path.splitext(os.path.basename(path))[0] \
        .replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reference_module(conf: dict):
    return _load_module(os.path.join(HERE, "configs",
                                     conf["reference"] + ".py"))


def metric_reader(name: str):
    return _load_module(os.path.join(HERE, "metrics", name + ".py"))


def codec_reference(fab):
    """The plain reference of the fabric's parity codec."""
    kind = "rs" if fab.cfg.rs_parity > 0 else "xor"
    return _load_module(os.path.join(HERE, "codecs", kind + ".py"))


def param_specs(conf: dict):
    """The configuration's parameter specs, stored in its dtype."""
    ref = reference_module(conf)
    return ref.param_specs(ref.arch(conf), conf.get("torch_dtype",
                                                    "bfloat16"))


def use_compile_cache() -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when it is set, else ``.jax_cache/`` at the checkout's root."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))


@dataclasses.dataclass
class Cell:
    name: str
    conf: dict                  # configs/<name>.json
    traffic: dict               # traffic/<name>.json
    chips: int = 1
    per_layer: tuple = ()       # per-layer metric entries for this cell
    end_to_end: tuple = ()      # end-to-end metric entries for this cell


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    from tokens import load_traffic
    bm = load_benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in bm["configs"]}
    with open(os.path.join(ROOT, confs[w["config"]]["file"])) as f:
        conf = json.load(f)
    return Cell(name=name, conf=conf, traffic=load_traffic(w["traffic"]),
                chips=int(w["chips"]),
                per_layer=tuple(m for m in bm["per_layer"]
                                if _reports(m, name)),
                end_to_end=tuple(m for m in bm["end_to_end"]
                                 if _reports(m, name)))


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------

def program_config(conf: dict):
    from repro.configs import get_config
    over = dict(conf["program"])
    return dataclasses.replace(get_config(over.pop("registry")), **over)


@dataclasses.dataclass
class Job:
    cell: Cell
    seed: int
    ref: object                 # the configuration's reference module
    arch: object
    specs: object
    loop: object
    state: object
    stream: object
    store: object
    store_dir: str
    step: int = 0               # global steps trained


def _check_tree(params, cfg) -> None:
    """The benchmark's weights must be the tree the program's own
    initializer would make: same names, shapes and dtypes."""
    import jax
    from repro.models import get_model
    want = jax.eval_shape(lambda k: get_model(cfg).init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                jax.tree_util.tree_leaves(want),
                jax.tree_util.tree_leaves(got))):
        raise ValueError(f"configuration and program disagree on the "
                         f"weights: program {want}, benchmark {got}")


def dist_ctx(cell: Cell):
    """One chip, or the traffic's ``mesh`` (``shape``, ``axes``) over the
    cell's chips."""
    from repro.sharding import single_device_ctx
    if cell.chips == 1:
        return single_device_ctx()
    from repro.launch.mesh import make_mesh_compat
    from repro.sharding.partition import make_dist_ctx
    m = cell.traffic["mesh"]
    return make_dist_ctx(make_mesh_compat(tuple(m["shape"]),
                                          tuple(m["axes"])))


def build(cell: Cell, seed: int, store_root: str) -> Job:
    import jax
    import reflib
    from repro.checkpoint_io import ShardedCheckpointStore
    from repro.core.policy import CheckpointPolicy, SelectionStrategy
    from repro.fabric import FabricConfig
    from repro.optim.optimizers import adamw
    from repro.training import ArenaTrainState, TrainLoop, TrainLoopConfig
    from tokens import TokenStream

    ref = reference_module(cell.conf)
    arch = ref.arch(cell.conf)
    specs = param_specs(cell.conf)
    cfg = program_config(cell.conf)
    tr = cell.traffic
    pol = tr["policy"]
    policy = pol and CheckpointPolicy(
        fraction=pol["fraction"], full_interval=pol["full_interval"],
        strategy=SelectionStrategy(pol["strategy"]),
        async_persist=pol["async_persist"])
    fabric = (FabricConfig(**tr["fabric"]) if tr.get("fabric") is not None
              else None)
    if policy and fabric is None:
        raise ValueError("a policy without a fabric keeps a tree, which "
                         "the checks of the saved state do not read")
    loss = tr.get("host_loss") or {}
    loop_cfg = TrainLoopConfig(policy=policy, fabric=fabric,
                               arena_state=True, seed=seed,
                               heal_after=loss.get("heal_after"))
    store_dir = tempfile.mkdtemp(prefix="store_", dir=store_root)
    store = ShardedCheckpointStore(store_dir) if policy else None
    opt = tr["optimizer"]
    loop = TrainLoop(cfg, dist_ctx(cell),
                     optimizer=adamw(opt["lr"], opt["b1"], opt["b2"],
                                     opt["eps"], opt["wd"]),
                     loop_cfg=loop_cfg, store=store)
    make, key = jax.jit(reflib.init_fn(specs)), jax.random.PRNGKey(seed)
    _check_tree(jax.eval_shape(make, key), cfg)
    # the loop makes its state from these weights instead of its own
    # initializer: the benchmark's inputs come from the seed, not from
    # the program under test (on a mesh the loop jits this call with its
    # shardings, so the weights are made where they live)
    loop.ops = dataclasses.replace(
        loop.ops, init_params=lambda _rng, _cfg: make(key))
    state = loop.init_state()
    if fabric is not None and not isinstance(state, ArenaTrainState):
        raise RuntimeError("the arena-resident training path did not "
                           "engage for this configuration")
    stream = TokenStream(arch.vocab, tr["batch"], tr["seq"], seed)
    return Job(cell, seed, ref, arch, specs, loop, state, stream, store,
               store_dir)


def period(job: Job) -> int:
    ctl = job.loop.controller
    return ctl.policy.partial_interval if ctl is not None else 1


def run_steps(job: Job, n: int, probe=None, losses: bool = True) -> None:
    """``n`` more steps through ``TrainLoop.run``, with the traffic's host
    losses at their global steps (none where ``losses`` is False)."""
    from tokens import loss_steps
    sched = (loss_steps(job.cell.traffic, job.seed, job.step + 1, n)
             if losses else [])
    kind = (job.cell.traffic.get("host_loss") or {}).get("kind", "host")
    job.loop.loop_cfg.fail_schedule = [(s - job.step, kind, h)
                                       for s, h in sched] or None
    job.state = job.loop.run(job.state, _feed(job, probe), n)
    job.step += n


def _feed(job: Job, probe):
    import jax.numpy as jnp
    while True:
        if probe is None:
            b = job.stream.next_numpy()
            yield {k: jnp.asarray(v) for k, v in b.items()}
        else:
            with probe.span("input"):
                b = job.stream.next_numpy()
                b = {k: jnp.asarray(v) for k, v in b.items()}
            yield b


# ---------------------------------------------------------------------------
# readings of the program's first steps
# ---------------------------------------------------------------------------

def _as_tree(job: Job, words):
    """A tree view of a flat arena of the program's layout (the tree
    itself where the loop keeps a tree)."""
    from repro.core.arena import unpack_arena
    layout = job.loop.arena_layout
    return words if layout is None else unpack_arena(words, layout)


def grad_norms(job: Job) -> dict:
    """Per-leaf norms of the first gradient as AdamW received it, worked
    out from its first moment after one step (m_1 = (1 - b1) g). An
    arena's value-domain moment is viewed as a tree through the program's
    arena layout; leaves stored in bf16 round the gradient to bf16 on the
    way, which moves a norm by under 2^-9."""
    import jax
    import reflib
    from repro.core.arena import encode_values
    layout = job.loop.arena_layout
    b1 = job.cell.traffic["optimizer"]["b1"]
    f = jax.jit(lambda mu: reflib.leaf_norms(
        mu if layout is None else _as_tree(job, encode_values(mu, layout))))
    return {k: float(v) / (1 - b1)
            for k, v in f(job.state.opt_state.mu).items()}


def change_norms(job: Job) -> dict:
    """Per-leaf norms of (weights now - weights from the seed)."""
    import jax
    import jax.numpy as jnp
    import reflib
    make = reflib.init_fn(job.specs)

    def f(live, key):
        now, start = _as_tree(job, live), make(key)
        return reflib.leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            now, start))

    live = job.loop._live(job.state)
    out = jax.jit(f)(live, jax.random.PRNGKey(job.seed))
    return {k: float(v) for k, v in out.items()}


def first_steps(job: Job) -> dict:
    """Steps 1-3 through the window's own call and feed. Also returns the
    device's peak before and after the readings, to show whether they
    raised it."""
    run_steps(job, 1, losses=False)
    before = memory_peak()
    grad = grad_norms(job)
    run_steps(job, 2, losses=False)
    change = change_norms(job)
    return {"losses": [m["loss"] for m in job.loop.metrics[:3]],
            "grad": grad, "change": change,
            "peaks": {"after_step_1": before, "after_readings": memory_peak()}}


# ---------------------------------------------------------------------------
# exact checks of the fabric's state after the window
# ---------------------------------------------------------------------------

def _words(x):
    import numpy as np
    return np.asarray(x).view(np.int32).reshape(-1)


def fabric_checks(job: Job, probe) -> dict:
    """Word-for-word checks, each a count of words that differ (limit 0),
    of each tier the traffic's policy and fabric keep:

    - ``replica_words``: the replica tier against the live arena;
    - ``parity_words``: the parity tier against its codec's plain
      reference (``codecs/<kind>.py``) over the live arena;
    - ``save_words``: the blocks the window's last step saved, in the
      running-checkpoint arena, against the live arena;
    - ``save_blocks_off``: how far the number of blocks that step saved
      is from the policy's ``ceil(fraction x blocks)``;
    - ``store_words``: the store read back from disk against the
      running checkpoint, every block;
    - ``recovery_words``: every recovered arena against the arena before
      its loss.
    """
    import jax
    import numpy as np
    loop, ctl = job.loop, job.loop.controller
    out = {}
    if ctl is None:
        return out
    fab, layout = ctl.fabric, loop.arena_layout
    live = _words(loop._live(job.state))
    if fab is not None and fab.replicas is not None:
        out["replica_words"] = int(np.sum(_words(fab.replicas.arena)
                                          != live))
    if fab is not None and fab.parity is not None:
        want = codec_reference(fab).parity(live, layout, fab.parity)
        out["parity_words"] = int(np.sum(_words(fab.parity.parity)
                                         != want.reshape(-1)))
    # the window ends on a save step: its blocks hold this step's values
    saved = np.asarray(job.store.saved_iters()) == job.step
    k = math.ceil(ctl.policy.fraction * ctl.partition.total_blocks)
    ckpt_tree = ctl.ckpt.values
    ckpt_arena = _words(ctl.pack_live(ckpt_tree))
    bad = 0
    for ab in layout.blocks:
        if saved[ab.gid]:
            sl = slice(ab.offset, ab.offset + ab.payload)
            bad += int(np.sum(ckpt_arena[sl] != live[sl]))
    out["save_words"] = bad
    out["save_blocks_off"] = abs(int(saved.sum()) - k)
    disk = job.store.read_all()
    bad = 0
    for a, b in zip(jax.tree_util.tree_leaves(disk),
                    jax.tree_util.tree_leaves(ckpt_tree)):
        a = np.ascontiguousarray(np.asarray(a)).view(np.uint8)
        b = np.ascontiguousarray(np.asarray(b)).view(np.uint8)
        bad += int(np.sum(a != b))
    out["store_words"] = bad
    out["recovery_words"] = int(sum(int(x) for x in probe.recovery_checks))
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, work_dir: str, counter=None,
             fault=None) -> dict:
    """Run ``cell`` once and return the result line's object (without
    printing). ``fault`` (tests only) breaks the program under the run:
    a callable ``fault(job, where)`` called once the job is built
    ("built") and once the window has closed ("window_closed")."""
    import jax
    import numpy as np
    import reflib
    from probe import Probe
    from tokens import first_batches

    probe = Probe()
    marks = [("start", t_start), ("imported", time.perf_counter())]
    job = build(cell, seed, work_dir)
    marks.append(("built", time.perf_counter()))
    probe.attach(job.loop.controller, job.store)
    if fault is not None:
        fault(job, "built")
    prog = first_steps(job)
    marks.append(("first_steps", time.perf_counter()))
    per = period(job)
    # warm-up: to the end of the first full save period after step 3,
    # one loss of each host on the way where the traffic has losses
    hosts = int((cell.traffic.get("host_loss") or {}).get("hosts", 0))
    warm = per * max(1, math.ceil((3 + 2 * hosts) / per)) - job.step
    run_steps(job, warm, probe)
    t = time.perf_counter()
    marks.append(("warmed", t))
    run_steps(job, per, probe)
    t_period = time.perf_counter() - t
    marks.append(("timed_period", t + t_period))
    n = per * max(1, round(seconds / t_period))

    tokens = n * cell.traffic["batch"] * cell.traffic["seq"]
    ctl = job.loop.controller
    fab = ctl.fabric if ctl is not None else None
    stats = ctl.stats if ctl is not None else {"saves": 0,
                                                "save_seconds": 0.0}
    m0 = len(job.loop.metrics)
    saves0, save_s0 = stats["saves"], stats["save_seconds"]
    c0 = counter.snapshot() if counter is not None else None
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="trace_", dir=work_dir)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    with probe.span("window"):
        w0 = time.perf_counter()
        run_steps(job, n, probe)
        w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    c1 = counter.snapshot() if counter is not None else None
    window_s = w1 - w0
    peak = memory_peak()
    window = job.loop.metrics[m0:]
    recov = probe.durations("recovery", w0, w1)

    # what the per-layer readers (metrics/<name>.py) read
    ctx = {
        "probe": probe, "window_s": window_s, "w0": w0, "w1": w1,
        "tokens": tokens, "steps": window,
        "saves": stats["saves"] - saves0,
        "save_seconds": stats["save_seconds"] - save_s0,
        "compiles": (None if c0 is None
                     else c1["compiles"] - c0["compiles"]),
        "trace": None, "device": device_info(),
        "flops_per_token": job.ref.flops_per_token(job.arch,
                                                   cell.traffic["seq"]),
        "arena_words": (int(job.state.arena.size)
                        if job.loop.arena_layout is not None else None),
        "parity_words": (int(fab.parity.parity.size)
                         if fab is not None and fab.parity is not None
                         else None),
    }
    if trace:
        import xtrace
        from probe import SPANS
        ctx["trace"] = xtrace.reduce(xtrace.load(
            xtrace.find_xplane(trace_dir), SPANS))
        shutil.rmtree(trace_dir, ignore_errors=True)

    e2e = {"tokens_per_s": (tokens / window_s, "tokens/s"),
           "peak_hbm_gb": (peak / 1e9, "GB"),
           "setup_s": (setup_s, "s")}
    if recov:
        e2e["recovery_s"] = (sum(recov) / len(recov), "s")
    losses = [m["loss"] for m in window]
    failed = int(sum(not np.isfinite(x) for x in losses))

    if fault is not None:
        fault(job, "window_closed")
    exact = fabric_checks(job, probe)
    # what the run wrote to disk: the shard log holds every block write,
    # the parity mirror is rewritten in place on each save
    disk = (dict(job.store.disk_nbytes(), saves=int(stats["saves"]))
            if job.store is not None else None)
    # free the program's state before the reference runs
    del job.state, job.loop, ctl, fab
    job.stream = None
    gc.collect()
    shutil.rmtree(job.store_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    batches = first_batches(job.arch.vocab, cell.traffic["batch"],
                            cell.traffic["seq"], seed, 3)
    params0 = reflib.init_from_specs(job.specs, seed)
    opt = cell.traffic["optimizer"]
    ref = reflib.train_readings(
        job.ref.loss_fn(job.arch), params0, batches, reflib.Numerics(),
        reflib.AdamW(opt["lr"], opt["b1"], opt["b2"], opt["eps"],
                     opt["wd"]))
    del params0, batches
    gaps = reflib.compare(prog, ref)
    ref_s = time.perf_counter() - t_ref

    limits = cell.conf["limits"]
    checks = {k: {"value": gaps[k], "limit": limits[k]}
              for k in ("loss_gap", "grad_gap", "change_gap")}
    checks.update({k: {"value": v, "limit": 0} for k, v in exact.items()})
    correct = (failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()))

    device = dict(ctx["device"], memory_peak_bytes=peak)
    info_extra = {}
    result = {"correct": bool(correct), "attempted": n, "failed": failed}
    if trace:
        tr = ctx["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": tr["device_ops"][:10],
            "idle_gaps": tr["idle_gaps"][:10]}
        info_extra = {"idle_by_span": tr["idle_by_span"]}
    else:
        want = {m["name"] for m in cell.end_to_end} or set(e2e)
        metrics = {k: {"value": v, "unit": u} for k, (v, u) in e2e.items()
                   if k in want}
    result.update(metrics=metrics, device=device)
    result["info"] = {"window_iterations": n, "window_s": window_s,
                      "window_lowered": (counter.lowered_between(w0, w1)
                                         if counter is not None else None),
                      "period_s": t_period, "reference_s": ref_s,
                      "store": disk,
                      "peaks": prog["peaks"],
                      "setup_marks": {b[0]: b[1] - a[1] for a, b in
                                      zip(marks, marks[1:])},
                      "worst_leaves": {"grad": gaps["grad_worst_leaf"],
                                       "change": gaps["change_worst_leaf"]},
                      "change_leaves_skipped":
                          gaps["change_leaves_skipped"],
                      "program": prog["losses"], "reference": ref["losses"],
                      **info_extra}
    result["checks"] = checks
    return result


def check_lines(result: dict) -> list:
    return [f"{k} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'OVER'}"
            for k, c in result["checks"].items()]
