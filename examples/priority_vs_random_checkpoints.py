"""Reproduce the paper's Figure 8 story on one model, end to end.

Compares priority / round-robin / random partial-checkpoint strategies at
matched write budget, under the same failure, and prints the resulting
rework iterations — the core SCAR claim in one script.

Run:  PYTHONPATH=src python examples/priority_vs_random_checkpoints.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.core.policy import CheckpointPolicy, RecoveryMode, SelectionStrategy
from repro.models.classic import make_model
from repro.training import run_clean, run_with_failure


def main():
    model = make_model("mlr", n=600, dim=64, n_classes=5, batch=200)
    clean = run_clean(model, 150)["losses"]
    print("== Figure-8-style comparison on MLR (fail 50% of blocks @ iter 25)")
    print(f"{'strategy':12s} {'r':>6s} {'rework iters (mean of 5 seeds)':>32s}")

    trad = CheckpointPolicy(fraction=1.0, full_interval=8,
                            strategy=SelectionStrategy.ROUND_ROBIN,
                            recovery=RecoveryMode.FULL,
                            block_rows=model.block_rows)
    costs = [run_with_failure(model, trad, fail_iter=25, fail_fraction=0.5,
                              max_iters=150, seed=s,
                              clean_losses=clean)["iteration_cost"]
             for s in range(5)]
    print(f"{'traditional':12s} {'1':>6s} {np.mean(costs):>32.1f}")

    for strat in (SelectionStrategy.PRIORITY, SelectionStrategy.ROUND_ROBIN,
                  SelectionStrategy.RANDOM):
        for r in (0.25, 0.125):
            pol = CheckpointPolicy(fraction=r, full_interval=8,
                                   strategy=strat,
                                   recovery=RecoveryMode.PARTIAL,
                                   block_rows=model.block_rows)
            costs = [run_with_failure(model, pol, fail_iter=25,
                                      fail_fraction=0.5, max_iters=150,
                                      seed=s, clean_losses=clean)
                     ["iteration_cost"] for s in range(5)]
            print(f"{strat.value:12s} {r:>6} {np.mean(costs):>32.1f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
