"""Small cells for the benchmark's CPU tests: the harness's own files with
the published widths replaced by tiny ones, so a whole run (build, first
steps, warm-up, window, checks, reference) takes seconds on the CPU."""
from __future__ import annotations

import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
from tokens import load_traffic  # noqa: E402

DENSE = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 512}
DENSE_PROGRAM = {"n_layers": 2, "d_model": 64, "n_heads": 4,
                 "n_kv_heads": 2, "d_ff": 128, "vocab": 512,
                 "loss_chunk": 32, "attn_chunk": 64}
MAMBA = {"d_model": 64, "n_layer": 2, "vocab_size": 256, "d_state": 16,
         "headdim": 16}
MAMBA_PROGRAM = {"n_layers": 2, "d_model": 64, "vocab": 256,
                 "ssm_state": 16, "ssm_headdim": 16, "ssm_chunk": 32,
                 "loss_chunk": 32}


def small_cell(config: str, traffic: str, batch: int = 2,
               seq: int = 64) -> harness.Cell:
    """A configuration's and a traffic mix's files, shrunk: widths, depth
    and vocabulary to toy sizes, batch and sequence to ``batch x seq``.
    The metrics are those of the benchmark's cell on this pair, if there
    is one, else of its first cell."""
    bm = harness.load_benchmark()
    cell = next((w for w in bm["workloads"] if w["config"] == config
                 and w["traffic"] == traffic), bm["workloads"][0])
    full = harness.load_cell(cell["name"])
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        conf = json.load(f)
    small, prog = ((DENSE, DENSE_PROGRAM) if conf["reference"] == "dense_lm"
                   else (MAMBA, MAMBA_PROGRAM))
    conf.update(small)
    conf["program"].update(prog)
    tr = copy.deepcopy(load_traffic(traffic))
    tr.update(batch=batch, seq=seq)
    return harness.Cell(name=full.name, conf=conf, traffic=tr,
                        per_layer=full.per_layer, end_to_end=full.end_to_end)
