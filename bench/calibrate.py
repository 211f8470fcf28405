"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3,...

For each seed, in one process (the compiled programs are shared): the
program's first three steps exactly as a benchmark run takes them
(``harness.build`` + ``harness.first_steps``), then the float32
reference; the control (the reference one precision step below the
configuration: every matmul operand rounded to float8_e4m3fn for bf16
weights, forward only); and
the reference with half of each batch left out, the fault a training
cell can have. Each is compared with the reference as ``correct``
compares the program. One JSON line per seed, then a summary line: the
largest program reading per number (the lower reading), the smallest
control and half-batch readings (the upper readings).

The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    import jax
    import reflib
    from tokens import first_batches
    harness.use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate.py: no TPU")
    cell = harness.load_cell(args.workload)
    tr, opt = cell.traffic, cell.traffic["optimizer"]
    adam = reflib.AdamW(opt["lr"], opt["b1"], opt["b2"], opt["eps"],
                        opt["wd"])
    control = reflib.Numerics.control(cell.conf["torch_dtype"])
    rows = []
    work = tempfile.mkdtemp(prefix="calibrate_")
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            job = harness.build(cell, seed, work)
            prog = harness.first_steps(job)
            store_dir = job.store_dir
            del job
            gc.collect()
            shutil.rmtree(store_dir, ignore_errors=True)
            ref_mod = harness.reference_module(cell.conf)
            a = ref_mod.arch(cell.conf)
            specs = harness.param_specs(cell.conf)
            loss = ref_mod.loss_fn(a)
            batches = first_batches(a.vocab, tr["batch"], tr["seq"], seed, 3)
            params = reflib.init_from_specs(specs, seed)
            ref = reflib.train_readings(loss, params, batches,
                                        reflib.Numerics(), adam)
            ctl = reflib.train_readings(loss, params, batches,
                                        control, adam)
            half = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
                    for b in batches]
            hb = reflib.train_readings(loss, params, half,
                                       reflib.Numerics(), adam)
            del params, batches, half
            row = {"seed": seed,
                   "program": reflib.compare(prog, ref),
                   "control": reflib.compare(ctl, ref),
                   "half_batch": reflib.compare(hb, ref),
                   "losses": {"program": prog["losses"],
                              "reference": ref["losses"]}}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in NUMBERS:
        summary[k] = {
            "program_max": max(r["program"][k] for r in rows),
            "control_min": min(r["control"][k] for r in rows),
            "half_batch_min": min(r["half_batch"][k] for r in rows)}
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
