"""The yardstick's arithmetic: sweep bytes against the fabric's own
traffic model, model FLOPs against a hand count, the peaks table."""
import json

import benchcase
import pytest

import flops
import harness
import reflib


def test_sweep_bytes_match_the_fabric_traffic_model():
    """At a reduced size on the CPU the benchmark's bytes of one scoring
    sweep agree with ``maintain_traffic(...)["arena_resident"]`` except
    for the compact staging the implementation writes and reads back
    (``staging_arena`` twice, less the score partials counted once):
    the roofline counts the algorithm's bytes, not its staging."""
    import jax
    from repro.core.policy import CheckpointPolicy
    from repro.fabric import FabricConfig
    from repro.core.controller import FTController
    cell = benchcase.small_cell("qwen2-1.5b", "clean_4x1024")
    params = reflib.init_from_specs(harness.param_specs(cell.conf), 3)
    ctl = FTController(params, CheckpointPolicy.scar(0.125, 32),
                       fabric=FabricConfig())
    fab = ctl.fabric
    t = fab._traffic_model()
    layout = fab.arena_layout
    words = int(layout.total_words)
    parity = int(fab.parity.n_groups * fab.parity.layout.frame_elems)
    assert 4 * words == t["arena_bytes"]
    assert 4 * parity == t["parity"]
    staging = 2 * t["staging_arena"] - 4 * int(layout.n_tiles)
    assert flops.sweep_bytes(words, parity, scored=True) == \
        t["arena_resident"] - staging
    assert flops.sweep_bytes(words, parity, scored=False) == \
        flops.sweep_bytes(words, parity, scored=True) - 4 * words
    arena = ctl.pack_live(params)
    assert arena.size == words
    fab.maintain(1, arena)
    assert jax.numpy.asarray(fab.parity.parity).size == parity


def test_dense_flops_hand_count():
    """One reduced qwen2 layer (d 64, 4 heads of 16, 2 kv heads, ff 128,
    vocab 512, tied) at seq 64."""
    cell = benchcase.small_cell("qwen2-1.5b", "clean_4x1024")
    conf = dict(cell.conf, num_hidden_layers=1)
    ref = harness.reference_module(conf)
    a = ref.arch(conf)
    q = 64 * 64            # wq
    kv = 2 * 64 * 32       # wk, wv: 2 kv heads of 16
    o = 64 * 64            # wo
    mlp = 3 * 64 * 128
    head = 512 * 64
    attn_fwd = 2 * 2 * 64 * (64 + 1) / 2     # QK^T and PV over the keys
    want = 6 * (q + kv + o + mlp + head) + 3 * attn_fwd
    assert ref.flops_per_token(a, 64) == pytest.approx(want)


def test_mamba2_flops_hand_count():
    """One reduced Mamba2 layer (d 64, d_inner 128, state 16, 8 heads of
    16, conv 4, vocab 256, tied) at seq 64 with chunk 32."""
    cell = benchcase.small_cell("mamba2-370m", "clean_4x1024")
    conf = dict(cell.conf, n_layer=1)
    ref = harness.reference_module(conf)
    a = ref.arch(conf)
    proj = 64 * (2 * 128 + 2 * 16 + 8) + 128 * 64
    head = 256 * 64
    pos = (32 + 1) / 2
    ssd = 2 * pos * 16 + 2 * pos * 8 * 16 + 4 * 8 * 16 * 16
    conv = 2 * 4 * 128
    want = 6 * (proj + head) + 3 * (ssd + conv)
    assert ref.flops_per_token(a, 64, chunk=32) == pytest.approx(want)


def test_unknown_device_kind_raises(tmp_path):
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"source": "none", "devices": {}}))
    with pytest.raises(KeyError):
        flops.peaks("TPU v5 lite", str(p))


def test_mfu_is_a_share_of_the_peak():
    assert flops.mfu(1e9, 19_700, 1.0, 1, 197e12) == pytest.approx(10.0)
    assert flops.mfu(1e9, 19_700, 1.0, 4, 197e12) == pytest.approx(2.5)
