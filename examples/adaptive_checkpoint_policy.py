"""Beyond-paper: the §7 'predictive model' — an adaptive checkpoint advisor.

The paper closes by suggesting that approximating c and ‖x⁰−x*‖ yields a
predictive model "evaluated on-the-fly to inform decisions made by a
system during run-time". This example runs a training job, observes its
contraction rate / drift / checkpoint cost, and lets the advisor pick the
(r, C) policy minimizing expected overhead under a given failure rate.

Run:  PYTHONPATH=src python examples/adaptive_checkpoint_policy.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.core.advisor import RunObservations, advise
from repro.models.classic import make_model
from repro.training import run_clean
from repro.core.iteration_cost import estimate_contraction


def main():
    model = make_model("mlr", n=600, dim=64, n_classes=5, batch=200)
    print("== observing an unperturbed run of MLR...")
    res = run_clean(model, 80)
    losses = np.asarray(res["losses"])
    errs = np.sqrt(np.maximum(losses - losses.min() * 0.98, 1e-9))
    c = estimate_contraction(errs[:60], burn_in=3)
    print(f"   fitted contraction c = {c:.4f}; ‖x⁰−x*‖ ≈ {errs[0]:.2f}")

    for fail_rate in (1e-5, 1e-3, 5e-2):
        obs = RunObservations(
            drift_per_iter=float((errs[0] - errs[-1]) / len(errs)),
            x0_err=float(errs[0]), c=c,
            t_iter=0.05, t_dump_full=0.02,
            failure_rate=fail_rate, loss_fraction=0.5, current_iter=60)
        policy, report = advise(obs)
        print(f"   failure_rate={fail_rate:8.0e} -> advise r={policy.fraction}"
              f" C={policy.full_interval}"
              f" (partial ckpt every {policy.partial_interval} iters,"
              f" expected overhead {report['expected_overhead_s']*1e3:.2f}"
              f" ms/iter)")
    print("== higher failure rates push toward smaller, more frequent,"
          " prioritized checkpoints — the paper's §4.2 design, chosen"
          " automatically.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
