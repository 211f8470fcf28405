"""The trace reduction on small synthesised traces: busy union and idle
share, the sweep's programs found by jit name, idle gaps named by the
host span that covers them, and the breakdown's order."""
import benchcase  # noqa: F401  (puts bench/ on the path)
import pytest

import harness
import xtrace

MS = 1_000_000


def _trace():
    # one device, a 100 ms window; ops overlap at 10-30 ms, then a gap
    # under "save", a sweep, and a gap under "maintain" at the end
    ops = [("fusion.1", 0, 20 * MS), ("fusion.2", 10 * MS, 30 * MS),
           ("copy.3", 60 * MS, 70 * MS), ("fusion.1", 70 * MS, 80 * MS)]
    modules = [("jit_train_step(3)", 0, 30 * MS),
               ("jit__unscored_live(7)", 60 * MS, 70 * MS),
               ("jit__scored_live(8)", 70 * MS, 80 * MS)]
    spans = [("window", 0, 100 * MS), ("save", 30 * MS, 60 * MS),
             ("maintain", 60 * MS, 100 * MS), ("input", 60 * MS, 61 * MS)]
    return xtrace.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules},
                        spans)


def test_busy_union_and_idle():
    r = xtrace.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.05)       # 0-30 and 60-80 ms
    assert xtrace.gaps(_trace().ops["/device:TPU:0"], 0, 100 * MS) == [
        (30 * MS, 60 * MS), (80 * MS, 100 * MS)]


def test_busy_is_averaged_over_devices():
    t = _trace()
    t.ops["/device:TPU:1"] = [("fusion.1", 0, 100 * MS)]
    assert xtrace.reduce(t)["busy_s"] == pytest.approx((0.05 + 0.1) / 2)


def test_gaps_named_by_covering_span():
    r = xtrace.reduce(_trace())
    # 30-60 ms lies under "save"; 80-100 ms under "maintain"
    assert r["idle_by_span"] == pytest.approx({"save": 0.03,
                                               "maintain": 0.02})
    assert [n for n, _ in r["idle_gaps"]] == ["save", "maintain"]
    # a gap no span covers is the train step's (the loop's own code)
    t = _trace()
    t.spans = [s for s in t.spans if s[0] != "save"]
    assert xtrace.reduce(t)["idle_by_span"]["train_step"] == \
        pytest.approx(0.03)


def test_innermost_span_wins():
    spans = [("maintain", 0, 100), ("input", 40, 60)]
    assert xtrace.covering_span(spans, 50, "x") == "input"
    assert xtrace.covering_span(spans, 10, "x") == "maintain"
    assert xtrace.covering_span(spans, 150, "x") == "x"


def test_breakdown_orders_by_time():
    r = xtrace.reduce(_trace())
    names = [n for n, _ in r["device_ops"]]
    # programs first (largest first), then ops by their summed time
    assert names == ["jit_train_step", "jit__unscored_live",
                     "jit__scored_live", "fusion.1", "fusion.2", "copy.3"]
    assert [s for _, s in r["device_ops"][3:]] == pytest.approx(
        [0.03, 0.02, 0.01])
    assert len(xtrace.top({str(i): [float(i), 1] for i in range(30)})) == 10


def test_sweep_found_by_jit_name():
    r = xtrace.reduce(_trace())
    assert r["modules"]["jit__unscored_live"] == [pytest.approx(0.01), 1]
    reader = harness.metric_reader("sweep_roofline")
    ctx = {"trace": r, "device": {"kind": "TPU v5 lite", "count": 1},
           "arena_words": 1_000_000, "parity_words": 250_000}
    # (unscored 4 * 2.25M + scored 4 * 3.25M bytes) / 819 GB/s over 20 ms
    want = 100 * (4 * 2.25e6 + 4 * 3.25e6) / 819e9 / 0.02
    assert reader.read(ctx) == pytest.approx(want)
    r["modules"] = {}
    assert reader.read(ctx) is None


def test_trace_without_window_or_device_is_refused():
    t = _trace()
    t.spans = [s for s in t.spans if s[0] != "window"]
    with pytest.raises(ValueError):
        xtrace.reduce(t)
    t = _trace()
    t.ops = {}
    with pytest.raises(ValueError):
        xtrace.reduce(t)


def test_load_reads_host_spans(tmp_path):
    """A real (CPU) profile: the benchmark's spans come back by name."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones((8,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("maintain"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = xtrace.load(xtrace.find_xplane(str(tmp_path)),
                    ("window", "maintain"))
    names = sorted(n for n, _, _ in t.spans)
    assert names == ["maintain", "window"]
    (_, w0, w1), = [s for s in t.spans if s[0] == "window"]
    (_, m0, m1), = [s for s in t.spans if s[0] == "maintain"]
    assert w0 <= m0 <= m1 <= w1
