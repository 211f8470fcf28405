"""Quickstart: SCAR fault tolerance in 60 lines.

Trains a small classic model (multinomial logistic regression — one of the
paper's §5 workloads), takes prioritized partial checkpoints through the
**arena-resident** fault-tolerance path (the live params feed the arena
maintenance sweep and the partial save as one flat arena — the default),
kills half the parameters mid-training, partially recovers, and reports
the measured iteration cost next to the Theorem 3.2 bound plus the
per-iteration maintenance overhead actually observed.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.core.iteration_cost import (estimate_contraction,
                                       single_perturbation_bound)
from repro.core.policy import CheckpointPolicy
from repro.fabric import FabricConfig
from repro.models.classic import make_model
from repro.telemetry import Recorder, format_report, run_report
from repro.training import run_clean, run_with_failure


def main():
    print("== SCAR quickstart: MLR + priority checkpoints + partial recovery")
    model = make_model("mlr", n=600, dim=64, n_classes=5, batch=200)

    # 1. unperturbed baseline (the κ(x, ε) reference)
    clean = run_clean(model, max_iters=150)["losses"]
    kappa_clean = int(np.argmax(np.asarray(clean) < model.eps))
    print(f"   clean run reaches ε in {kappa_clean} iterations")

    # 2. SCAR: prioritized 1/4-checkpoints at 4× frequency, partial
    # recovery, with the tiered redundancy fabric so the hot path runs
    # arena-resident (maintain + save over one flat arena, no per-step
    # tree pack inside the fault-tolerance machinery)
    scar = CheckpointPolicy.scar(fraction=0.25, interval=32)
    rec = Recorder()   # telemetry: events + spans + perturbation ledger
    res = run_with_failure(model, scar, fail_iter=25, fail_fraction=0.5,
                           max_iters=150, clean_losses=clean,
                           fabric=FabricConfig(), recorder=rec)
    tiers = {k: v for k, v in res["recovery"]["tier_counts"].items() if v}
    print(f"   failure at iter 25 lost 50% of blocks;"
          f" checkpoint-only recovery would apply ||δ'||²="
          f"{res['recovery']['partial_sq']:.2e} (full ||δ||²="
          f"{res['recovery']['full_sq']:.2e}); tiers used: {tiers}, "
          f"applied ||δ||²={res['recovery']['applied_sq']:.2e}")
    print(f"   SCAR iteration cost: {res['iteration_cost']}")
    fstats = res["fabric_stats"]
    print(f"   arena-native maintenance: {res['arena_state']}; overhead "
          f"{res['maint_seconds_per_iter']*1e3:.2f} ms/iter "
          f"({fstats['maintain_bytes_moved'] // max(fstats['parity_encodes'], 1) / 1e6:.2f} "
          f"MB/iter accounted incl. {fstats['live_packs']} runner-side "
          f"packs, {fstats['arena_maintains']} single-dispatch sweeps)")

    # 3. traditional full checkpoint-restore, same failure
    trad = run_with_failure(model, CheckpointPolicy.traditional(32),
                            fail_iter=25, fail_fraction=0.5, max_iters=150,
                            clean_losses=clean)
    print(f"   traditional iteration cost: {trad['iteration_cost']}")

    # 4. Theorem 3.2 bound for the SCAR perturbation
    c = estimate_contraction(np.sqrt(np.maximum(
        np.asarray(clean) - min(clean) * 0.98, 1e-9))[:100], burn_in=3)
    delta = float(np.sqrt(res["recovery"]["applied_sq"]))
    x0 = model.distance(model.init(jax.random.PRNGKey(1)))
    bound = single_perturbation_bound(delta, c, T=25, x0_err=x0)
    print(f"   Theorem 3.2 bound: {bound:.1f} iterations (c={c:.3f})")
    saved = trad["iteration_cost"] - res["iteration_cost"]
    print(f"== SCAR saved {saved} iterations vs traditional recovery")

    # 5. the same run through the telemetry layer: the ledger prices each
    # recovery with the exact bound above; pass out_dir= to Recorder()
    # for events.jsonl + a Perfetto-loadable trace.json
    rec.ledger.set_rates(c, x0)
    print("\n== telemetry run report (SCAR run)")
    print(format_report(run_report(rec, horizon=150)))


if __name__ == "__main__":
    enable_compile_cache()
    main()
