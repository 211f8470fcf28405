"""Correlated failures vs the tiered checkpoint fabric, end to end.

The paper's SCAR assumes blocks die uniformly at random; real clusters lose
whole hosts and racks. This example builds a device→host→rack failure-domain
map over an MLR training job, kills one whole host, and shows how the
fabric resolves every lost block to the cheapest surviving redundancy tier
— peer replicas and XOR parity recover *live* values (zero perturbation),
while checkpoint-only SCAR pays the running checkpoint's staleness.

Run:  PYTHONPATH=src python examples/correlated_failures.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.core.policy import CheckpointPolicy, RecoveryMode, SelectionStrategy
from repro.fabric import FabricConfig, FailureDomainMap, FailureEvent
from repro.models.classic import make_model
from repro.training import run_clean, run_with_failure, run_with_trace

VARIANTS = (
    ("checkpoint-only", dict(replicate=False, parity=False)),
    ("parity (1/g mem)", dict(replicate=False, parity=True)),
    ("replicas+parity", dict(replicate=True, parity=True)),
)


def main():
    dm = FailureDomainMap(n_devices=8, devices_per_host=2, hosts_per_rack=2)
    print("== topology:", f"{dm.n_devices} devices / {dm.n_hosts} hosts /",
          f"{dm.n_racks} racks")
    trace = dm.sample_failure_trace(np.random.default_rng(7), 2000,
                                    {"device": 300.0, "host": 600.0,
                                     "rack": 1500.0})
    kinds = {k: sum(e.kind == k for e in trace)
             for k in ("device", "host", "rack")}
    print("   MTBF trace over 2000 steps:", kinds, "\n")

    model = make_model("mlr", n=600, dim=64, n_classes=5, batch=200)
    clean = run_clean(model, 120)["losses"]
    policy = CheckpointPolicy(fraction=0.25, full_interval=8,
                              strategy=SelectionStrategy.ROUND_ROBIN,
                              recovery=RecoveryMode.PARTIAL,
                              block_rows=model.block_rows)

    print("== one whole host dies at iteration 15 (SCAR r=0.25 checkpoints)")
    print(f"{'fabric variant':18s} {'applied ||δ'+chr(39)+'||²':>14s} "
          f"{'ι (rework iters)':>17s}  recovery tiers")
    for name, kw in VARIANTS:
        costs, sq, tiers = [], [], None
        for seed in range(4):
            r = run_with_failure(
                model, policy, fail_iter=15, fail_fraction=0.5,
                max_iters=120, seed=seed, clean_losses=clean,
                fabric=FabricConfig(n_devices=8, devices_per_host=2,
                                    hosts_per_rack=2, **kw),
                fail_domain="host")
            costs.append(max(r["iteration_cost"], 0))
            sq.append(r["recovery"]["applied_sq"])
            tiers = {k: v for k, v in r["recovery"]["tier_counts"].items()
                     if v and k != "SURVIVOR"}
        print(f"{name:18s} {np.mean(sq):>14.3e} {np.mean(costs):>17.1f}  "
              f"{tiers}")

    print("\nReplica/parity tiers restore live values — the Thm 4.1 "
          "perturbation vanishes,\nso the failure costs (near) zero rework "
          "iterations; checkpoint-only SCAR pays\nthe running checkpoint's "
          "staleness on every correlated loss.")

    # -- degraded-mode soak: hosts die and STAY dead -----------------------
    print("\n== degraded-mode soak: 3 hosts die over a trace and stay dead")
    soak_trace = [FailureEvent(step=15, kind="host", index=0),
                  FailureEvent(step=45, kind="host", index=1),
                  FailureEvent(step=75, kind="host", index=2)]
    print(f"{'placement policy':20s} {'ι (rework)':>11s} "
          f"{'Σ||δ'+chr(39)+'||²':>11s}  per-event recovery tiers")
    for name, kw in (("recover-in-place", dict(elastic=False)),
                     ("elastic re-homing", dict(elastic=True))):
        r = run_with_trace(
            model, policy, max_iters=120, seed=0, clean_losses=clean,
            trace=soak_trace,
            fabric=FabricConfig(n_devices=8, devices_per_host=2,
                                hosts_per_rack=2, **kw))
        per_event = [
            {k: v for k, v in e["tier_counts"].items()
             if v and k != "SURVIVOR"}
            for e in r["events"] if not e.get("skipped")]
        sq = sum(e["applied_sq"] for e in r["events"])
        print(f"{name:20s} {max(r['iteration_cost'], 0):>11.1f} "
              f"{sq:>11.3e}  {per_event}")

    print("\nRecover-in-place leaves replicas and parity homes pointing at "
          "dead devices, so\nlater failures fall through to RUNNING_CKPT/"
          "DISK; the elastic engine re-homes\nblocks, re-seeds replicas, and "
          "re-stripes parity after every loss — each new\nfailure still "
          "finds live redundancy and training continues degraded at "
          "‖δ′‖²≈0.")

    # -- multi-erasure: two hosts die the SAME step ------------------------
    print("\n== multi-erasure: hosts 0 and 2 (one per rack) die at the "
          "same step")
    double = [FailureEvent(step=15, kind="host", index=0),
              FailureEvent(step=15, kind="host", index=2)]
    print(f"{'erasure code':18s} {'ι (rework)':>11s} "
          f"{'||δ'+chr(39)+'||²':>11s} {'fallbacks':>10s}  recovery tiers")
    for name, kw in (("XOR parity (m=1)", dict()),
                     ("RS(k, 2)  (m=2)", dict(rs_parity=2))):
        r = run_with_trace(
            model, policy, max_iters=120, seed=0, clean_losses=clean,
            trace=double,
            fabric=FabricConfig(n_devices=8, devices_per_host=2,
                                hosts_per_rack=2, elastic=True, **kw))
        ev = next(e for e in r["events"] if not e.get("skipped"))
        tiers = {k: v for k, v in ev["tier_counts"].items()
                 if v and k != "SURVIVOR"}
        print(f"{name:18s} {max(r['iteration_cost'], 0):>11.1f} "
              f"{ev['applied_sq']:>11.3e} "
              f"{len(ev.get('tier_fallbacks', [])):>10d}  {tiers}")

    print("\nLosing one host per rack in a single step erases some blocks' "
          "primary AND\nanti-affine replica at once. The XOR code absorbs "
          "one erasure per parity\ngroup — the rest fall back to the "
          "running checkpoint (each fallback is an\nexplained "
          "`tier_fallback` event, never silent) and the failure is priced "
          "at\nthe checkpoint's staleness. RS(k, 2) holds two GF(256) "
          "parity rows on\nhost-disjoint homes per group, decodes both "
          "erasures bit-exactly, and the\nsame double loss costs "
          "‖δ′‖² = 0 — no rework iterations owed.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
