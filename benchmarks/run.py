"""Benchmark driver — one section per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7,...]

Prints ``name,us_per_call,derived`` CSV rows, and exits nonzero when any
section raised (its ``<name>_ERROR`` row says what). Sections:

  fig3  — QP iteration cost vs Theorem 3.2 bound        (bench_qp_bound)
  fig5  — MLR random vs adversarial perturbations       (bench_mlr_bound)
  fig6  — reset-to-init perturbations, MLR + LDA        (bench_reset)
  fig7  — partial vs full recovery, 4 models × 3 fracs  (bench_partial_recovery)
  fig8  — priority/round/random checkpoints + headline  (bench_priority)
  fig9  — system overhead (t_dump vs t_step, budget)    (bench_overhead)
  kern  — Pallas kernel microbenches vs jnp oracles     (bench_kernels)
  tier  — tiered recovery fabric vs checkpoint-only     (bench_tiered_recovery)
  maint — fused single-pass maintenance vs seed path    (bench_maintain)
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_kernels, bench_maintain, bench_mlr_bound,
                        bench_overhead, bench_partial_recovery,
                        bench_priority, bench_qp_bound, bench_reset,
                        bench_tiered_recovery)
from repro.launch.compile_cache import enable_compile_cache

SECTIONS = {
    "fig3": bench_qp_bound.run,
    "fig5": bench_mlr_bound.run,
    "fig6": bench_reset.run,
    "fig7": bench_partial_recovery.run,
    "fig8": bench_priority.run,
    "fig9": bench_overhead.run,
    "kern": bench_kernels.run,
    "tier": bench_tiered_recovery.run,
    "maint": bench_maintain.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(SECTIONS)
    enable_compile_cache()
    failed = []

    print("name,us_per_call,derived")
    for name, fn in SECTIONS.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn(quick=args.quick)
        except Exception as e:  # run the other sections, then exit nonzero
            rows = [f"{name}_ERROR,0.0,{type(e).__name__}:{e}"]
            failed.append(name)
        for row in rows:
            print(row, flush=True)
        print(f"_section_{name}_seconds,{(time.time()-t0)*1e6:.0f},"
              f"wall={time.time()-t0:.0f}s", flush=True)
    if failed:
        sys.exit(f"benchmark sections failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
