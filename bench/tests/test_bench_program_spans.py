"""The program's own spans as the benchmark reads them: on the profiler's
clock in a real (CPU) profile, named apart from the benchmark's probe,
and the per-layer readers of the per-step span rollup on hand-built
windows (``None`` on a program that keeps no rollup)."""
import os
import re

import benchcase  # noqa: F401  (puts bench/ and src/ on the path)
import pytest

import harness
import xtrace
from probe import SPANS

SRC = os.path.join(harness.ROOT, "src", "repro")
NAME = re.compile(r"""(?:span|record)\(\s*["']([^"']+)["']""")


def test_program_span_is_on_the_profilers_clock(tmp_path):
    """A program span, with and without a tracer, comes back among the
    host spans of a profile, inside the window that encloses it."""
    import jax
    import jax.numpy as jnp
    from repro.telemetry import NULL_RECORDER, SpanTracer
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones((8,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with SpanTracer(keep=False).span("scar/step"):
            with NULL_RECORDER.span("scar/save"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = xtrace.load(xtrace.find_xplane(str(tmp_path)),
                    ("window", "scar/step", "scar/save"))
    got = {n: (s, e) for n, s, e in t.spans}
    assert set(got) == {"window", "scar/step", "scar/save"}
    assert got["window"][0] <= got["scar/step"][0] <= got["scar/save"][0]
    assert got["scar/save"][1] <= got["scar/step"][1] <= got["window"][1]


def test_program_span_names_leave_the_probe_alone():
    """Every span the program opens is ``scar/``-prefixed, so none can be
    taken for one of the benchmark's own spans (``probe.SPANS``)."""
    names = set()
    for root, _, files in os.walk(SRC):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as f:
                    names |= set(NAME.findall(f.read()))
    assert {"scar/step", "scar/save", "scar/save/tiles_to_host",
            "scar/maintain", "scar/recovery", "scar/store/write"} <= names
    assert all(n.startswith("scar/") for n in names), sorted(names)
    assert not names & set(SPANS)


def _steps():
    return [
        {"step": 1, "spans": {"scar/step": 0.5, "scar/maintain": 0.1},
         "compiles": {"scar/maintain": [1, 0.3, 0]}, "bytes": {},
         "store_lag_s": []},
        {"step": 2, "checkpointed": True,
         "spans": {"scar/save": 2.0, "scar/save/tiles_to_host": 0.5,
                   "scar/save/parity_to_host": 0.2,
                   "scar/store/parity_write": 1.0},
         "compiles": {"scar/save/tiles_to_host": [2, 0.4, 0],
                      "scar/save": [1, 0.1, 1],
                      "scar/maintain": [1, 0.1, 0],
                      "scar/saved": [1, 9.0, 0]},
         "bytes": {}, "store_lag_s": [0.25]},
        {"step": 3, "checkpointed": True,
         "spans": {"scar/save": 1.0, "scar/save/tiles_to_host": 0.3,
                   "scar/save/parity_to_host": 0.1,
                   "scar/store/parity_write": 0.5},
         "compiles": {}, "bytes": {}, "store_lag_s": [0.5, 0.75]},
    ]


READINGS = {"tiles_to_host_s": 0.4, "parity_mirror_s": 0.9,
            "save_compile_s": 0.25, "maintain_compile_s": 0.4 / 3,
            "store_lag_s": 0.5}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_rollup_reader(name):
    reader = harness.metric_reader(name)
    assert reader.read({"steps": _steps()}) == pytest.approx(READINGS[name])
    # a program without the rollup (the parent of this reader): no field,
    # no reading
    bare = [{"step": 1, "loss": 2.0, "seconds": 0.3,
             "overhead_seconds": 0.1},
            {"step": 2, "loss": 2.0, "seconds": 0.3,
             "overhead_seconds": 3.0, "checkpointed": True}]
    assert reader.read({"steps": bare}) is None
