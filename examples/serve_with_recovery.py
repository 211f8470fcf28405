"""Serving example: batched generation + SCAR-style weight recovery.

Serves a reduced model (batched greedy decode with a KV cache), then
simulates a partial weight-loss event on the serving replica (e.g. a host
dropping out of the inference pod) and restores the lost blocks from the
running checkpoint — generation continues without reloading the full model.

Run:  PYTHONPATH=src python examples/serve_with_recovery.py [--arch yi-9b]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core.controller import FTController
from repro.core.policy import CheckpointPolicy
from repro.data import lm_batch
from repro.models import get_model
from repro.sharding import single_device_ctx
from repro.training.serve import Server


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=8)
    args = ap.parse_args()

    ctx = single_device_ctx()
    cfg = get_config(args.arch, reduced=True)
    ops = get_model(cfg)
    params = ops.init_params(jax.random.PRNGKey(0), cfg)
    srv = Server(cfg, ctx, params)
    batch = lm_batch(jax.random.PRNGKey(1), cfg, args.batch, args.prompt_len)

    print(f"== serving {args.arch} (reduced): batch={args.batch}, "
          f"prompt={args.prompt_len}, +{args.new_tokens} tokens")
    toks0 = srv.generate(batch, args.new_tokens)
    print("   tokens (before failure):", np.asarray(toks0)[0])

    # checkpoint the serving weights, lose 30% of blocks, partially restore
    ctl = FTController(params, CheckpointPolicy.scar(fraction=1.0, interval=1))
    ctl.checkpoint_now(1, params)
    lost = ctl.sample_failure(0.3)
    recovered, info = ctl.on_failure(params, lost)
    print(f"   failure: lost {info['lost_blocks']:.0f} blocks; "
          f"restored from running checkpoint (||δ||²={info['applied_sq']:.2e})")

    srv2 = Server(cfg, ctx, recovered)
    toks1 = srv2.generate(batch, args.new_tokens)
    print("   tokens (after recovery): ", np.asarray(toks1)[0])
    same = bool(jnp.all(toks0 == toks1))
    print(f"== generations identical after lossless recovery: {same}")
    assert same, "checkpoint was fresh — recovery must be exact"


if __name__ == "__main__":
    enable_compile_cache()
    main()
