"""The ``mamba2-370m.clean`` cell on the CPU at a reduced size: the whole
run comes out correct, the control fails the limits, and the reference's
FLOPs agree with a hand count."""
import time

import benchcase
import pytest

import harness
import reflib
from probe import CompileCounter
from tokens import first_batches

CONFIG, TRAFFIC = "mamba2-370m-published", "clean_4x2048"


def _run(cell, tmp_path):
    return harness.run_cell(cell, 2**31 + 11, 0.1, False, time.perf_counter(),
                            work_dir=str(tmp_path),
                            counter=CompileCounter())


def test_cell_runs_and_is_correct(tmp_path):
    """Build (the weights must be the program's tree), steps 1-3, the
    window and every exact check of the fabric, with f32 leaves (A_log,
    dt_bias, D) beside bf16 ones in the arena."""
    cell = benchcase.small_cell(CONFIG, TRAFFIC)
    assert cell.name == "mamba2-370m.clean"
    r = _run(cell, tmp_path)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0
    words = {k: c["value"] for k, c in r["checks"].items()
             if k not in cell.conf["limits"]}
    assert set(words) >= {"replica_words", "parity_words", "save_words",
                          "save_blocks_off", "store_words"}
    assert all(v == 0 for v in words.values()), words
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_control_fails_the_limits(tmp_path, monkeypatch):
    """The reference with every matmul operand rounded to float8_e4m3fn,
    put in the program's place for the readings of steps 1-3, comes out
    not correct through the harness's whole run."""
    cell = benchcase.small_cell(CONFIG, TRAFFIC)
    first_steps = harness.first_steps
    opt = cell.traffic["optimizer"]
    adam = reflib.AdamW(opt["lr"], opt["b1"], opt["b2"], opt["eps"],
                        opt["wd"])
    control = reflib.Numerics.control(cell.conf["torch_dtype"])

    def steps(job):
        out = first_steps(job)        # the program trains on for the window
        tr = cell.traffic
        batches = first_batches(job.arch.vocab, tr["batch"], tr["seq"],
                                job.seed, 3)
        params = reflib.init_from_specs(job.specs, job.seed)
        got = reflib.train_readings(job.ref.loss_fn(job.arch), params,
                                    batches, control, adam)
        return dict(out, **{k: got[k] for k in ("losses", "grad", "change")})

    monkeypatch.setattr(harness, "first_steps", steps)
    r = _run(cell, tmp_path)
    assert r["correct"] is False
    assert any(r["checks"][k]["value"] > r["checks"][k]["limit"]
               for k in cell.conf["limits"]), r["checks"]


def test_flops_hand_count():
    """One reduced layer of the published block (d 64, d_inner 128, state
    16, 8 heads of 16, a width-4 conv over x, B and C = 160 channels,
    vocab 256, tied) at seq 64 with chunk 32."""
    cell = benchcase.small_cell(CONFIG, TRAFFIC)
    conf = dict(cell.conf, n_layer=1)
    ref = harness.reference_module(conf)
    a = ref.arch(conf)
    proj = 64 * (2 * 128 + 2 * 16 + 8) + 128 * 64
    head = 256 * 64
    pos = (32 + 1) / 2
    ssd = 2 * pos * 16 + 2 * pos * 8 * 16 + 4 * 8 * 16 * 16
    conv = 2 * 4 * (128 + 2 * 16)
    want = 6 * (proj + head) + 3 * (ssd + conv)
    assert ref.flops_per_token(a, 64, chunk=32) == pytest.approx(want)
