"""The harness on the CPU: every cell's window function at a reduced
size, without the CLI, and the CLI's refusals."""
import json
import os
import shutil
import subprocess
import sys
import time

import benchcase
import pytest

import harness
from probe import CompileCounter

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cell, tmp_path, seed=2**31 + 11, trace=False, fault=None):
    return harness.run_cell(cell, seed, 0.1, trace, time.perf_counter(),
                            work_dir=str(tmp_path),
                            counter=CompileCounter(), fault=fault)


@pytest.mark.parametrize("config,traffic", [
    ("qwen2-1.5b", "clean_4x1024"),
    ("qwen2-1.5b", "hostloss_4x1024"),
])
def test_window_runs_and_is_correct(config, traffic, tmp_path):
    cell = benchcase.small_cell(config, traffic)
    r = _run(cell, tmp_path)
    assert RESULT_KEYS <= set(r)
    assert r["correct"] is True, r["checks"]
    assert list(r)[-1] == "checks"
    want = {m["name"] for m in cell.end_to_end}
    assert set(r["metrics"]) == want
    assert "setup_s" in want and "tokens_per_s" in want
    assert ("recovery_s" in want) == (traffic == "hostloss_4x1024")
    for m in r["metrics"].values():
        assert m["value"] > 0 or m["unit"] == "GB"   # no HBM on the CPU
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    # the window is whole save periods and ends on a save step
    assert r["attempted"] % 4 == 0 and r["attempted"] >= 4
    assert r["checks"]["save_words"]["value"] == 0
    assert os.listdir(tmp_path) == []          # store and traces removed
    json.dumps(r)


def test_same_seed_same_first_steps(tmp_path):
    cell = benchcase.small_cell("qwen2-1.5b", "clean_4x1024")
    losses = {}
    for tag, seed in (("a", 5), ("b", 5), ("c", 6)):
        job = harness.build(cell, seed, str(tmp_path))
        losses[tag] = harness.first_steps(job)["losses"]
    assert losses["a"] == losses["b"]
    assert losses["a"] != losses["c"]


def test_per_layer_metrics_on_a_traced_window(tmp_path, monkeypatch):
    """``--trace 1`` reports every per-layer metric of the cell. The CPU
    has no device plane, so the trace's reduction is stood in for by a
    fixed one and the peaks by the v5e's; every reader runs on the real
    window's counters and spans."""
    import flops
    import xtrace
    v5e = flops.peaks("TPU v5 lite")
    monkeypatch.setattr(flops, "peaks", lambda kind, *a: v5e)
    monkeypatch.setattr(xtrace, "load", lambda path, names: None)
    monkeypatch.setattr(xtrace, "reduce", lambda tr: {
        "window_s": 1.0, "busy_s": 0.25, "devices": 1,
        "modules": {"jit__unscored_live": [0.02, 3],
                    "jit__scored_live": [0.01, 1]},
        "ops": {}, "idle_by_span": {"save": 0.5},
        "idle_gaps": [["save", 0.5], ["maintain", 0.25]],
        "device_ops": [["jit_train_step", 0.2]]})
    monkeypatch.setattr(xtrace, "find_xplane", lambda d: d)
    cell = benchcase.small_cell("qwen2-1.5b", "hostloss_4x1024")
    r = _run(cell, tmp_path, trace=True)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in cell.per_layer}
    assert r["metrics"]["device_idle_share"]["value"] == \
        pytest.approx(75.0)
    assert r["metrics"]["fast_tier_share"]["value"] == pytest.approx(100.0)
    assert 0 < r["metrics"]["ft_overhead_share"]["value"] < 100
    assert r["device"]["busy_s"] == 0.25 and r["device"]["window_s"] == 1.0
    assert r["breakdown"]["idle_gaps"][0] == ["save", 0.5]


def test_everything_loads_by_name():
    bm = harness.load_benchmark()
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"])
        ref = harness.reference_module(cell.conf)
        a = ref.arch(cell.conf)
        assert ref.flops_per_token(a, cell.traffic["seq"]) > 0
        assert cell.per_layer and cell.end_to_end
    for m in bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
        assert m["moves"] in {e["name"] for e in bm["end_to_end"]}
    for c in bm["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b.clean",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    p = _cli(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cli_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_window_without_fault_tolerance(tmp_path):
    """A traffic mix whose policy and fabric are null trains the plain
    loop: the step's readings are still compared, no tier is checked and
    the fault-tolerance metrics report nothing."""
    cell = benchcase.small_cell("qwen2-1.5b", "clean_4x1024")
    cell.traffic.update(policy=None, fabric=None)
    r = _run(cell, tmp_path)
    assert r["correct"] is True, r["checks"]
    assert set(r["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert r["attempted"] >= 1
    assert {"tokens_per_s", "setup_s"} <= set(r["metrics"])


MESH_RUN = """
import json, sys, tempfile, time
import benchcase, harness
from probe import CompileCounter
cell = benchcase.small_cell("qwen2-1.5b", "hostloss_4x1024")
cell.conf["torch_dtype"] = cell.conf["program"]["dtype"] = "float32"
cell.traffic.update(mesh={"shape": [2, 2], "axes": ["data", "model"]},
                    fabric={"n_devices": 4, "devices_per_host": 1,
                            "hosts_per_rack": 2})
cell.chips = 4
r = harness.run_cell(cell, 2**31 + 5, 0.1, False, time.perf_counter(),
                     work_dir=sys.argv[1], counter=CompileCounter())
print(json.dumps(r))
"""


def test_window_on_a_four_device_mesh(tmp_path):
    """A cell on four chips runs on its traffic's mesh: a float32 model
    sharded over a forced 2x2 CPU mesh, one host per device, with host
    losses, comes out correct with every tier checked."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [here, harness.HERE, os.path.join(harness.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", MESH_RUN, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True, r["checks"]
    assert r["device"]["count"] == 4
    assert "recovery_s" in r["metrics"]
    assert r["checks"]["parity_words"]["value"] == 0
