"""CI bench regression guard for the maintenance hot path.

Compares a fresh ``bench_maintain --quick`` JSON against the committed
baseline (``BENCH_maintain.json`` at the repo root) and **fails** when the
analytic bytes-per-step of any guarded row regresses by more than the
allowed ratio (default 1.5×). Cross-row gates additionally pin the
arena-resident paths at ≤1.0× the committed *pack-path* baselines — the
per-step ``pack_arena`` the arena-resident training state eliminated must
stay eliminated. Wall-clock ratios are *recorded* alongside (CI machines
are too noisy to gate on, but the trajectory should be visible in the job
log and artifact), and the headline invariants (bit-exactness, the ≥2×
seed-over-arena floor, near-r byte budget, the e2e bit-equality of the
arena-resident and PyTree training paths, the bit-equality of the async
double-buffered maintenance pipeline against the sync path plus its
overhead halving, and the SPMD rows' same-mesh loss bit-equality /
bytes-at-or-below-pack / elastic shrink-heal cycle) are asserted. The
in-place-save wall-clock inversion is RECORDED with a threshold instead
(see ``RECORDED_THRESHOLD_FLAGS`` for why the quick config legitimately
inverts it); ``overlap_efficiency`` rides along as a recorded value.

Standalone::

    python -m benchmarks.check_maintain_regression \
        --baseline BENCH_maintain.json --fresh BENCH_maintain.new.json
"""
from __future__ import annotations

import argparse
import json
import re
import sys

# rows whose derived "bytes" field is the guarded per-step byte cost
GUARDED_BYTES = {
    "maint_sweep_arena_resident": "bytes_per_step",
    "maint_sweep_arena": "bytes_per_step",
    "maint_sweep_sharded": "bytes_per_step",
    "maint_partial_save_inplace": "bytes_moved_per_save",
    "e2e_step_maintain_arena": "bytes_per_step",
    "e2e_step_maintain_pytree": "bytes_per_step",
}
# cross-row gates: (fresh row, key, BASELINE row, max ratio) — the fresh
# arena-resident e2e bytes/step must stay at or below the committed
# pytree-pack baseline (the pack must stay eliminated: the resident path
# may never regress back to pack-path traffic)
CROSS_GUARDS = [
    ("e2e_step_maintain_arena", "bytes_per_step",
     "e2e_step_maintain_pytree", 1.0),
    ("maint_sweep_arena_resident", "bytes_per_step",
     "maint_sweep_arena", 1.0),
]
# headline flags that must stay true on every run (exactness + analytic
# byte floors only — deterministic on any machine)
REQUIRED_FLAGS = [
    ("maint_arena_kernel", "replica_bit_exact=True"),
    ("maint_arena_kernel", "parity_bit_exact=True"),
    ("maint_arena_kernel", "scores_match=True"),
    ("maint_headline", "meets_2x=True"),
    ("maint_partial_save_headline", "near_r=True"),
    ("maint_store_packed", "compaction_exact=True"),
    ("maint_store_arena", "rekeyed_read_exact=True"),
    ("e2e_step_maintain_headline", "arena_fewer_bytes=True"),
    ("e2e_step_maintain_headline", "loss_bit_equal=True"),
    ("maint_overlap_headline", "overlap_bit_equal=True"),
    ("maint_overlap_headline", "async_overhead_lt_sync=True"),
    ("maint_sweep_sharded", "sharded_loss_bit_equal=True"),
    ("maint_sweep_sharded", "sharded_bytes_le_pack=True"),
    ("tier_soak_elastic_mesh", "elastic_cycle_ok=True"),
    ("maint_telemetry", "ledger_bound_exact=True"),
    # RS(k, 2) must recover the correlated two-host loss through the
    # parity tier bit-exactly (no checkpoint fallback, zero applied
    # perturbation) and the integrity scrub must catch + correct the
    # injected arena bit flip — both deterministic on any machine
    ("tier_soak_multi_erasure", "rs_recovery_bit_equal=True"),
    ("tier_soak_multi_erasure", "silent_error_detected=True"),
    # word-level quantized arena: a bf16 model's redundancy bytes per
    # sweep must stay at or below 0.55x the f32 baseline of the same
    # shapes, and the all-f32 e2e run must stay loss-bit-equal to the
    # PyTree path (the word arena is a bitwise no-op at f32) — both
    # deterministic (analytic bytes + bit comparison)
    ("maint_sweep_quant", "quant_bytes_le_half_f32=True"),
    ("maint_sweep_quant", "f32_loss_bit_equal=True"),
]
# wall-clock flags: recorded loudly, never gated (shared CI runners are
# too noisy — the committed baseline documents the local inversion)
RECORDED_FLAGS = [
    ("e2e_step_maintain_headline", "resident_overhead_faster=True"),
]
# wall-clock flags recorded WITH a loose threshold on an accompanying
# ratio. ``inplace_beats_rewrite_wallclock`` is the canonical case: on
# the quick config the full rewrite is ONE fused XLA program over a tiny
# model, while the in-place save pays fixed per-dispatch overhead that
# cannot amortize at that size — so the boolean legitimately inverts
# (committed baseline: wall 0.95x) even though the byte win (``near_r``,
# REQUIRED above) is intact and the inversion disappears at production
# sizes where the memcpy dominates the dispatch. Gating the boolean
# would make quick-mode CI red on a config artifact; dropping it
# entirely would hide a real dispatch-count regression. The compromise:
# the flag is printed every run, and the run only FAILS when the ratio
# falls below ``min_ratio`` — i.e. the in-place save got catastrophically
# slower than the rewrite, which no config-size effect explains.
RECORDED_THRESHOLD_FLAGS = [
    # (row, flag, ratio key, min ratio)
    ("maint_partial_save_headline", "inplace_beats_rewrite_wallclock=True",
     "wall_rewrite_over_inplace", 1 / 3),
]
# numeric values lifted from the fresh run's derived fields and printed
# for the job log / perf trajectory — never gated (wall-clock noise)
RECORDED_VALUES = [
    ("maint_telemetry", "overhead_p50_us"),
    ("maint_telemetry", "overhead_p95_us"),
    ("maint_overlap_headline", "overlap_efficiency"),
    ("maint_overlap_headline", "async_over_sync_overhead_ratio"),
    # the XOR control's staleness price under the same double loss —
    # the contrast the RS tier's bit-equal gate is measured against
    ("tier_soak_multi_erasure", "xor_fallbacks"),
    ("tier_soak_multi_erasure", "xor_applied_sq"),
    # quantized-arena byte trajectory + tail-packing alignment overhead
    ("maint_sweep_quant", "redundancy_ratio_bf16_over_f32"),
    ("maint_arena_padding", "padding_ratio"),
    ("maint_arena_padding", "padding_ratio_unpacked"),
]


def _rows(path: str) -> dict[str, dict]:
    with open(path) as f:
        data = json.load(f)
    return {r["name"]: r for r in data["rows"]}


def _derived_num(row: dict, key: str) -> float:
    m = re.search(rf"{key}=([0-9.eE+-]+)", row["derived"])
    if m is None:
        raise SystemExit(f"row {row['name']}: no '{key}' in derived field")
    return float(m.group(1))


def check(baseline_path: str, fresh_path: str,
          max_ratio: float = 1.5) -> int:
    base = _rows(baseline_path)
    fresh = _rows(fresh_path)
    failures = []
    for name, key in GUARDED_BYTES.items():
        if name not in base:
            print(f"[guard] {name}: not in baseline yet — skipped")
            continue
        if name not in fresh:
            failures.append(f"{name}: missing from fresh run")
            continue
        b = _derived_num(base[name], key)
        f = _derived_num(fresh[name], key)
        ratio = f / max(b, 1.0)
        wall_b = base[name]["us_per_call"]
        wall_f = fresh[name]["us_per_call"]
        wall = wall_f / max(wall_b, 1e-9)
        status = "OK" if ratio <= max_ratio else "REGRESSION"
        print(f"[guard] {name}: {key} {b:.0f} -> {f:.0f} "
              f"({ratio:.2f}x, limit {max_ratio}x) | wall-clock "
              f"{wall_b:.0f}us -> {wall_f:.0f}us ({wall:.2f}x, recorded) "
              f"[{status}]")
        if ratio > max_ratio:
            failures.append(
                f"{name}: {key} regressed {ratio:.2f}x (> {max_ratio}x)")
    for name, key, base_name, limit in CROSS_GUARDS:
        if base_name not in base:
            print(f"[cross] {name}: baseline row {base_name} missing — "
                  "skipped")
            continue
        if name not in fresh:
            failures.append(f"{name}: missing from fresh run")
            continue
        b = _derived_num(base[base_name], key)
        f = _derived_num(fresh[name], key)
        ratio = f / max(b, 1.0)
        status = "OK" if ratio <= limit else "REGRESSION"
        print(f"[cross] {name}: {key} {f:.0f} vs baseline "
              f"{base_name} {b:.0f} ({ratio:.3f}x, limit {limit}x) "
              f"[{status}]")
        if ratio > limit:
            failures.append(
                f"{name}: {key} {ratio:.3f}x of baseline {base_name} "
                f"(> {limit}x — the eliminated pack came back)")
    for name, flag in REQUIRED_FLAGS:
        if name not in fresh:
            failures.append(f"{name}: row missing from fresh run")
        elif flag not in fresh[name]["derived"]:
            failures.append(f"{name}: expected '{flag}', got "
                            f"'{fresh[name]['derived']}'")
    for name, flag in RECORDED_FLAGS:
        held = name in fresh and flag in fresh[name]["derived"]
        print(f"[recorded] {name}: '{flag}' "
              f"{'held' if held else 'DID NOT HOLD (not gated)'}")
    for name, flag, key, min_ratio in RECORDED_THRESHOLD_FLAGS:
        if name not in fresh:
            failures.append(f"{name}: row missing from fresh run")
            continue
        held = flag in fresh[name]["derived"]
        ratio = _derived_num(fresh[name], key)
        status = "OK" if ratio >= min_ratio else "REGRESSION"
        note = ("held" if held else "did not hold (quick-config "
                "inversion, see RECORDED_THRESHOLD_FLAGS)")
        print(f"[recorded] {name}: '{flag}' {note} | "
              f"{key}={ratio:.2f} (floor {min_ratio:.2f}) [{status}]")
        if ratio < min_ratio:
            failures.append(
                f"{name}: {key} {ratio:.2f} below floor {min_ratio:.2f} "
                "— beyond any quick-config dispatch-overhead inversion")
    for name, key in RECORDED_VALUES:
        if name not in fresh:
            print(f"[recorded] {name}: row missing (not gated)")
            continue
        try:
            v = _derived_num(fresh[name], key)
        except SystemExit:
            print(f"[recorded] {name}: no '{key}' field (not gated)")
            continue
        print(f"[recorded] {name}: {key}={v:g} (not gated)")
    if failures:
        print("\nBENCH REGRESSION GUARD FAILED:")
        for f in failures:
            print("  -", f)
        return 1
    print("\nbench regression guard OK")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default="BENCH_maintain.json")
    ap.add_argument("--fresh", required=True)
    ap.add_argument("--max-ratio", type=float, default=1.5)
    args = ap.parse_args()
    sys.exit(check(args.baseline, args.fresh, args.max_ratio))


if __name__ == "__main__":
    main()
