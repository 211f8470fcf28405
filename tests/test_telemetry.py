"""Unified telemetry layer: recorder/event bus, span tracer, ledger, report.

Covers the PR's acceptance points: the events.jsonl round trip, Chrome
trace-export validity (Perfetto-loadable complete events with contained
nesting), the perturbation ledger's bounds bit-matching
``core/iteration_cost``, the NullRecorder zero-overhead default, and the
classic runners' stats-snapshot guarantee.
"""
import json
import time

import numpy as np
import pytest

from repro.core.controller import FTController
from repro.core.iteration_cost import (iteration_cost_bound,
                                       single_perturbation_bound)
from repro.core.policy import CheckpointPolicy
from repro.fabric import CheckpointFabric, FabricConfig
from repro.models.classic import make_model
from repro.telemetry import (EVENT_SCHEMA, NULL_RECORDER, Histogram,
                             NullRecorder, PerturbationLedger, Recorder,
                             SpanTracer, format_report, read_events_jsonl,
                             run_report)
from repro.training import run_with_failure, run_with_trace


# ---------------------------------------------------------------------------
# recorder + event bus
# ---------------------------------------------------------------------------

def test_events_jsonl_round_trip(tmp_path):
    out = tmp_path / "telemetry"
    rec = Recorder(out_dir=str(out))
    rec.event("failure", step=3, lost_blocks=np.int64(4), failed_devices=2)
    rec.event("maintain", step=np.int32(3), mode="arena",
              bytes_moved=1024, replica=True, parity=True)
    rec.event("save", step=4, blocks=2, bytes_moved=np.float64(8.0),
              seconds=0.01, mode="arena")
    rec.close()
    back = read_events_jsonl(str(out / "events.jsonl"))
    assert back == rec.events
    # stamped fields + monotone sequence, and every value JSON-native
    assert [e["seq"] for e in back] == [0, 1, 2]
    assert all(isinstance(e["ts"], float) for e in back)
    assert back[0]["lost_blocks"] == 4 and back[1]["mode"] == "arena"
    json.dumps(back)   # fully serializable after the round trip


def test_event_kinds_documented():
    """Every kind the instrumented components emit is in EVENT_SCHEMA."""
    m = make_model("qp")
    rec = Recorder()
    run_with_failure(m, CheckpointPolicy(fraction=0.5, full_interval=4),
                     fail_iter=6, fail_fraction=0.5, max_iters=12,
                     fabric=FabricConfig(n_devices=8), recorder=rec)
    kinds = {e["kind"] for e in rec.events}
    assert kinds  # the run must actually emit
    assert kinds <= set(EVENT_SCHEMA)


def test_scope_registration_by_reference():
    rec = Recorder()
    stats = rec.scope("fabric", {"x": 0})
    stats["x"] = 7
    assert rec.metrics()["scopes"]["fabric"]["x"] == 7
    # collisions get a unique suffix instead of silently aliasing
    other = rec.scope("fabric", {"x": 1})
    assert other is not stats
    assert set(rec.scopes) == {"fabric", "fabric#2"}
    # metrics() is a snapshot, not a live view
    snap = rec.metrics()
    stats["x"] = 99
    assert snap["scopes"]["fabric"]["x"] == 7


def test_background_thread_events_are_serialized(tmp_path):
    """The store's mirror events fire from its worker thread — the bus
    must keep the JSONL lines whole and the seq unique under that."""
    import threading
    rec = Recorder(out_dir=str(tmp_path / "t"))

    def emit(k):
        for i in range(50):
            rec.event("mirror", step=i, bytes=k, segments=1,
                      background=True)

    threads = [threading.Thread(target=emit, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rec.close()
    back = read_events_jsonl(str(tmp_path / "t" / "events.jsonl"))
    assert len(back) == 200
    assert sorted(e["seq"] for e in back) == list(range(200))


# ---------------------------------------------------------------------------
# span tracer + Chrome trace export
# ---------------------------------------------------------------------------

def test_spans_nest_and_export_chrome_trace(tmp_path):
    tracer = SpanTracer()
    with tracer.span("outer", step=1):
        with tracer.span("inner"):
            time.sleep(0.002)
    doc = tracer.chrome_trace()
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert set(evs) == {"outer", "inner"}
    for e in evs.values():   # complete events, µs timestamps
        assert e["ph"] == "X"
        assert e["dur"] >= 0 and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # containment: the inner span lies strictly inside the outer one, so
    # Perfetto renders the nesting on one track
    outer, inner = evs["outer"], evs["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert evs["outer"]["args"] == {"step": 1}
    # the written file is valid JSON with the same events
    path = tracer.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        assert len(json.load(f)["traceEvents"]) == 2


# ---------------------------------------------------------------------------
# perturbation ledger: bounds bit-match core/iteration_cost
# ---------------------------------------------------------------------------

def test_ledger_bounds_bit_match_iteration_cost():
    led = PerturbationLedger(c=0.9, x0_err=10.0)
    led.record(step=5, lost_blocks=3, tier_counts={"RUNNING_CKPT": 3},
               applied_sq=0.25)
    led.record(step=12, lost_blocks=1, tier_counts={"PEER_REPLICA": 1},
               applied_sq=0.0)
    for e in led.entries:
        assert e.bound == single_perturbation_bound(
            e.delta_norm, 0.9, T=e.step, x0_err=10.0)
    assert led.cumulative_bound(20) == float(iteration_cost_bound(
        led.delta_series(20), 0.9, 10.0))
    # the dense series carries each event's ‖δ'‖ at its iteration
    dense = led.delta_series(20)
    assert len(dense) == 21
    assert dense[5] == pytest.approx(0.5) and dense[12] == 0.0
    owed = led.iterations_owed()
    assert owed == sorted(owed)   # cumulative series is monotone


def test_ledger_backfills_bounds_on_set_rates():
    led = PerturbationLedger()
    e = led.record(step=7, lost_blocks=2, tier_counts=None, applied_sq=4.0)
    assert e.bound is None and led.cumulative_bound() is None
    led.set_rates(0.8, 5.0)
    assert e.bound == single_perturbation_bound(2.0, 0.8, T=7, x0_err=5.0)
    assert led.summary()["iterations_owed_total"] == pytest.approx(e.bound)


def test_record_recovery_feeds_ledger_and_bus():
    rec = Recorder()
    rec.record_recovery(step=9, lost_blocks=4,
                        tier_counts={"PARITY": 4}, applied_sq=1.0)
    (entry,) = rec.ledger.entries
    assert entry.delta_norm == 1.0 and entry.source_tiers == {"PARITY": 4}
    (ev,) = rec.events
    assert ev["kind"] == "recovery" and ev["tier_counts"] == {"PARITY": 4}


# ---------------------------------------------------------------------------
# NullRecorder: the zero-overhead default
# ---------------------------------------------------------------------------

def test_null_recorder_is_allocation_free_singletons():
    assert NULL_RECORDER.enabled is False
    assert isinstance(NULL_RECORDER, NullRecorder)
    # shared singletons, no per-call allocation
    assert NULL_RECORDER.histogram("x") is NULL_RECORDER.counter("y")
    d = {"k": 1}
    assert NULL_RECORDER.scope("s", d) is d
    # spans stay on without a recorder: no tracer, so nothing is kept
    assert NULL_RECORDER.tracer is None
    with NULL_RECORDER.span("scar/noop") as sp:
        assert sp.tracer is None
    NULL_RECORDER.event("anything", x=1)
    NULL_RECORDER.record_recovery(step=1, lost_blocks=1,
                                  tier_counts=None, applied_sq=0.0)
    assert NULL_RECORDER.metrics() == {}


def test_components_default_to_null_recorder():
    m = make_model("qp")
    p = m.init(__import__("jax").random.PRNGKey(1))
    ctl = FTController(p, CheckpointPolicy(fraction=0.5, full_interval=4),
                       fabric=FabricConfig(n_devices=8))
    assert ctl.recorder is NULL_RECORDER
    assert ctl.fabric.recorder is NULL_RECORDER
    # stats stay plain dicts, registered nowhere
    assert isinstance(ctl.stats, dict) and isinstance(ctl.fabric.stats, dict)


def test_fabric_attach_recorder_rebinds_stats():
    m = make_model("qp")
    p = m.init(__import__("jax").random.PRNGKey(1))
    from repro.core.blocks import partition_pytree
    part = partition_pytree(p, 16)
    fab = CheckpointFabric(part, FabricConfig(n_devices=8))
    stats = fab.stats
    rec = Recorder()
    fab.attach_recorder(rec)
    assert fab.recorder is rec
    assert rec.scopes["fabric"] is stats     # same dict, now registered
    fab.attach_recorder(Recorder())          # second attach: no-op
    assert fab.recorder is rec
    fab2 = CheckpointFabric(part, FabricConfig(n_devices=8))
    fab2.attach_recorder(NULL_RECORDER)      # null attach: no-op
    assert fab2.recorder is NULL_RECORDER


# ---------------------------------------------------------------------------
# end-to-end: instrumented runs, snapshots, report
# ---------------------------------------------------------------------------

def test_run_with_failure_emits_and_prices(tmp_path):
    m = make_model("qp")
    rec = Recorder(out_dir=str(tmp_path / "t"))
    res = run_with_failure(m, CheckpointPolicy(fraction=0.5,
                                               full_interval=4),
                           fail_iter=8, fail_fraction=0.5, max_iters=16,
                           fabric=FabricConfig(n_devices=8), recorder=rec)
    kinds = {e["kind"] for e in rec.events}
    assert {"failure", "recovery", "maintain", "save"} <= kinds
    # the ledger entry mirrors the recovery diagnostics exactly
    (entry,) = rec.ledger.entries
    assert entry.applied_sq == pytest.approx(
        float(res["recovery"]["applied_sq"]))
    assert entry.lost_blocks == int(res["recovery"]["lost_blocks"])
    rec.ledger.set_rates(0.9, 10.0)
    assert entry.bound == single_perturbation_bound(
        entry.delta_norm, 0.9, T=8, x0_err=10.0)
    rec.close()
    # all three artifacts land
    for name in ("events.jsonl", "trace.json", "metrics.json"):
        assert (tmp_path / "t" / name).exists()
    report = run_report(rec, horizon=16)
    assert report["recovery"]["n_recoveries"] == 1
    assert report["ledger"]["cumulative_bound"] == float(
        iteration_cost_bound(rec.ledger.delta_series(16), 0.9, 10.0))
    assert "iterations owed" in format_report(report)


def test_classic_runner_results_are_snapshots():
    """Post-run mutation of the live controller/fabric stats must not
    corrupt the returned result dicts."""
    m = make_model("qp")
    rec = Recorder()
    res = run_with_failure(m, CheckpointPolicy(fraction=0.5,
                                               full_interval=4),
                           fail_iter=6, fail_fraction=0.5, max_iters=12,
                           fabric=FabricConfig(n_devices=8), recorder=rec)
    # the recorder scope IS the controller's live dict — mutate it
    live_ctl = rec.scopes["controller"]
    live_fab = rec.scopes["fabric"]
    assert res["controller_stats"]["saves"] == live_ctl["saves"]
    live_ctl["saves"] += 100
    live_fab["maintain_bytes_moved"] += 10 ** 9
    live_ctl["events"].append({"poison": True})
    assert res["controller_stats"]["saves"] == live_ctl["saves"] - 100
    assert res["fabric_stats"]["maintain_bytes_moved"] \
        == live_fab["maintain_bytes_moved"] - 10 ** 9
    assert all("poison" not in e for e in res["controller_stats"]["events"])


def test_run_with_trace_snapshots_events():
    m = make_model("qp")
    rec = Recorder()
    res = run_with_trace(m, CheckpointPolicy(fraction=0.5, full_interval=4),
                         fabric=FabricConfig(n_devices=8, elastic=True),
                         max_iters=20, mtbf={"device": 8.0}, recorder=rec)
    live = rec.scopes["controller"]
    n_before = len(res["controller_stats"]["events"])
    live["events"].append({"poison": True})
    assert len(res["controller_stats"]["events"]) == n_before
    assert "fabric_stats" in res


def test_report_on_null_recorder_is_well_formed():
    report = run_report(NULL_RECORDER)
    assert report["events"]["total"] == 0
    assert report["ledger"] is None
    assert "telemetry: 0 events" in format_report(report)


def test_histogram_summary_percentiles():
    h = Histogram()
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5 and s["max"] == 100.0
    assert s["p50"] == 3.0
    assert s["p95"] == pytest.approx(
        float(np.percentile([1, 2, 3, 4, 100], 95)))


# ---------------------------------------------------------------------------
# in-program spans: always on, never waiting, per-step rollup
# ---------------------------------------------------------------------------

def test_spans_never_wait_for_the_device(monkeypatch):
    """No span calls ``jax.block_until_ready``: not a bare tracer's, not
    the null recorder's, and not the fabric's ``scar/maintain`` with a
    recorder attached (which once fenced the sweep)."""
    import jax
    import jax.numpy as jnp

    def refuse(*_a, **_k):
        raise AssertionError("a span waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    x = jnp.ones((16,))
    rec = Recorder()
    with SpanTracer(keep=False).span("scar/test/outer") as sp:
        with NULL_RECORDER.span("scar/test/null"):
            with rec.span("scar/test/kept"):
                y = x * 2
        sp.add_bytes(y.nbytes)
    m = make_model("qp")
    p = m.init(jax.random.PRNGKey(1))
    ctl = FTController(p, CheckpointPolicy(fraction=0.5, full_interval=4),
                       fabric=FabricConfig(n_devices=8), recorder=rec)
    ctl.maintain(1, p)
    assert rec.tracer.durations("scar/maintain")
    assert rec.tracer.durations("scar/test/kept")


def test_new_shape_books_one_compile_to_its_span():
    """A program compiled inside nested spans books exactly one compile,
    to the innermost span; a second call of the same shape books none."""
    import jax
    import jax.numpy as jnp
    tracer = SpanTracer(keep=False)
    f = jax.jit(lambda v: v * 3 + 1)
    x = jnp.ones((7, 13))
    tracer.take()
    with tracer.span("scar/test/outer"):
        with tracer.span("scar/test/inner"):
            f(x)
    r = tracer.take()
    assert r["compiles"]["scar/test/inner"][0] == 1
    assert r["compiles"]["scar/test/inner"][1] > 0
    assert "scar/test/outer" not in r["compiles"]
    assert set(r["spans"]) == {"scar/test/outer", "scar/test/inner"}
    with tracer.span("scar/test/inner"):
        f(x)
    assert tracer.take()["compiles"] == {}


def _lm_run(tmp_path, recorder):
    """Reduced LM with fabric and a background store: 8 steps, a save
    every 4th (the last step's), one host lost at step 3 and healed at
    step 4."""
    from repro.checkpoint_io.store import ShardedCheckpointStore
    from repro.configs import get_config
    from repro.core.policy import RecoveryMode, SelectionStrategy
    from repro.data.pipeline import ShardedLMDataset
    from repro.sharding import single_device_ctx
    from repro.training import TrainLoop, TrainLoopConfig
    ctx = single_device_ctx()
    cfg = get_config("qwen2-1.5b", reduced=True)
    pol = CheckpointPolicy(fraction=0.25, full_interval=16,
                           strategy=SelectionStrategy.PRIORITY,
                           recovery=RecoveryMode.PARTIAL, async_persist=True)
    loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
        policy=pol, fabric=FabricConfig(), arena_state=True,
        fail_schedule=[(3, "host", 1)], heal_after=1, recorder=recorder),
        store=ShardedCheckpointStore(str(tmp_path)))
    state = loop.init_state()
    ds = ShardedLMDataset(cfg, batch=2, seq=32, ctx=ctx)
    loop.run(state, iter(ds), 8)
    return loop


@pytest.fixture(scope="module")
def lm_runs(tmp_path_factory):
    bare = _lm_run(tmp_path_factory.mktemp("bare"), None)
    rec = Recorder()
    kept = _lm_run(tmp_path_factory.mktemp("kept"), rec)
    return bare, kept, rec


def test_lm_loop_step_rollup(lm_runs):
    """Every step's record carries the span rollup; on save steps the
    save's host path is broken down, and no child outlasts its parent."""
    loop, _, rec = lm_runs
    saves = [m for m in loop.metrics if m.get("checkpointed")]
    assert [m["step"] for m in saves] == [4, 8]
    for m in loop.metrics:
        assert set(m) >= {"spans", "compiles", "bytes", "store_lag_s"}
        sp = m["spans"]
        for name, sec in sp.items():
            assert name.startswith("scar/") and sec >= 0
            # the background writer's spans run on its own thread, and
            # may have started in an earlier step
            if name not in ("scar/step", "scar/store/write",
                            "scar/store/parity_write"):
                assert sec <= sp["scar/step"]
        for k in ("scar/step/train", "scar/step/fence", "scar/maintain"):
            assert k in sp
    for m in saves:
        sp = m["spans"]
        kids = [k for k in sp if k.startswith("scar/save/")]
        assert set(kids) >= {"scar/save/select", "scar/save/scatter",
                             "scar/save/tiles_to_host",
                             "scar/save/store_wait",
                             "scar/save/store_enqueue",
                             "scar/save/parity_to_host"}
        assert sum(sp[k] for k in kids) <= sp["scar/save"]
        assert m["bytes"]["scar/save/tiles_to_host"] > 0
        assert m["bytes"]["scar/save/parity_to_host"] > 0
    # the parity mirror is the background writer's (async_persist): one
    # write per save, of the bytes each save copied to the host
    assert len(rec.tracer.durations("scar/store/parity_write")) \
        == len(saves)
    assert sum(m["bytes"].get("scar/store/parity_write", 0)
               for m in loop.metrics) == sum(
        m["bytes"]["scar/save/parity_to_host"] for m in saves) > 0
    fail = next(m for m in loop.metrics if "failures" in m)["spans"]
    assert sum(fail[k] for k in fail if k.startswith("scar/recovery/")) \
        <= fail["scar/recovery"]
    # one background write per save, each with its lag; the last save's
    # lands on the last step, by run()'s closing flush at the latest
    lags = [x for m in loop.metrics for x in m["store_lag_s"]]
    assert len(lags) == 2 and all(x > 0 for x in lags)
    assert loop.metrics[-1]["store_lag_s"]
    assert sum(m["bytes"].get("scar/store/write", 0)
               for m in loop.metrics) == sum(
        m["bytes"]["scar/save/tiles_to_host"] for m in saves) > 0
    # the overhead phases read the same rollup, over clean steps (the
    # save at step 4 shares its step with the heal)
    phases = loop.overhead_summary()["phases"]
    assert phases["save"]["max"] == loop.metrics[-1]["spans"]["scar/save"]
    assert phases["save"]["count"] == phases["fence"]["count"] == 6


def test_recorder_changes_only_what_is_kept(lm_runs):
    """Attaching a Recorder leaves the losses and the running checkpoint
    bit-identical, and the same spans and bytes in every step's rollup
    (but the background writer's, which lands where its timing puts it);
    only the Recorder's tracer keeps records (with parents and steps)."""
    bare, kept, rec = lm_runs
    assert [m["loss"] for m in bare.metrics] == \
        [m["loss"] for m in kept.metrics]
    assert (np.asarray(bare.controller._ckpt_arena)
            == np.asarray(kept.controller._ckpt_arena)).all()
    assert (np.asarray(bare.controller.ckpt.saved_iter)
            == np.asarray(kept.controller.ckpt.saved_iter)).all()
    def booked(m, d):
        # the background writer books into whichever step it lands in
        return {k: v for k, v in m[d].items()
                if k not in ("scar/store/write", "scar/store/parity_write")}

    for a, b in zip(bare.metrics, kept.metrics):
        assert set(booked(a, "spans")) == set(booked(b, "spans"))
        assert booked(a, "bytes") == booked(b, "bytes")
    assert bare.tracer.spans == []
    recs = {s.sid: s for s in rec.tracer.spans}
    save = next(s for s in recs.values() if s.name == "scar/save")
    assert save.step == 4
    child = next(s for s in recs.values()
                 if s.name == "scar/save/tiles_to_host" and s.step == 4)
    assert recs[child.parent] is save
    assert recs[save.parent].name == "scar/step"
