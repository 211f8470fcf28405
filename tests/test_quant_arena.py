"""Word-level quantized arena (bf16/fp8/int8) with tail packing.

Covers the quantized-arena subsystem end to end:
- ``pack_arena ∘ unpack_arena`` is bit-exact for every word-packable
  dtype (f32/bf16/f16/fp8/int8/int16/int32/uint8), any shape —
  invariant I3; property tests when hypothesis is available
  (import-guarded, never a hard dependency),
- the tail-packed layout satisfies the word-level invariants I1–I4
  (tile-aligned main region, word-contiguous tail, exact disjoint
  coverage, zero pad words *and* zero sub-word pad bits),
- the value domain: ``decode_values`` matches per-leaf ``astype(f32)``,
  ``encode ∘ decode`` is the arena identity, and ``pack_values`` agrees
  with decoding a packed arena,
- a mixed-dtype model (f32 + bf16 + f16 + int8 + fp8 when available)
  survives a correlated host loss bit-exactly through PEER_REPLICA and,
  on a parity-only fabric, through PARITY — zero perturbation, raw
  words restored, no ``.astype`` round trip anywhere in the path,
- the RS integrity scrub detects, localizes and corrects an injected
  bit flip on a quantized arena, and recovery afterwards is bit-exact,
- a bf16 model's redundancy bytes are ≤ 0.55× the f32 layout of the
  same shapes (the test twin of the ``quant_bytes_le_half_f32`` CI
  gate), and the ``arena_padding_ratio`` gauge surfaces through fabric
  stats and the telemetry run report.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.arena import (ARENA_TILE, arena_compatible,
                              build_arena_layout, decode_values,
                              encode_values, pack_arena, pack_values,
                              unpack_arena)
from repro.core.blocks import partition_pytree, word_packable
from repro.fabric import CheckpointFabric, FabricConfig

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # no pip install in this environment: the
    HAVE_HYPOTHESIS = False  # property tests below are skipped, not failed

    def given(*a, **k):      # decorator stubs so the module still imports
        return lambda f: f

    def settings(*a, **k):
        return lambda f: f

    class _St:
        @staticmethod
        def integers(lo, hi):
            return None
    st = _St()

RNG = np.random.default_rng(23)

FP8 = getattr(jnp, "float8_e4m3fn", None)

# every word-packable dtype the arena admits (fp8 only on jax builds
# that ship ml_dtypes' float8 family)
PACKABLE = [jnp.float32, jnp.bfloat16, jnp.float16,
            jnp.int8, jnp.int16, jnp.int32, jnp.uint8]
if FP8 is not None:
    PACKABLE.append(FP8)


def _leaf(shape, dtype, rng):
    """Random finite leaf with bit patterns representable in ``dtype``."""
    dt = np.dtype(dtype)
    if dt.kind in "iu":
        lo, hi = (0, 200) if dt.kind == "u" else (-100, 100)
        return jnp.asarray(rng.integers(lo, hi, shape), dtype)
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


def _mixed_params(rng=None, with_int=True):
    """Mixed-dtype model: multi-block 2D leaves, a tail 1-D leaf, a
    scalar — every region and width class of the layout."""
    rng = rng or np.random.default_rng(7)
    p = {"w32": _leaf((96, 6), jnp.float32, rng),
         "wbf": _leaf((64, 6), jnp.bfloat16, rng),
         "h16": _leaf((48, 6), jnp.float16, rng),
         "b": _leaf((7,), jnp.float32, rng),
         "s": _leaf((), jnp.bfloat16, rng)}
    if with_int:
        p["q8"] = _leaf((40, 6), jnp.int8, rng)
    if FP8 is not None:
        p["e4m3"] = _leaf((32, 6), FP8, rng)
    return p


def _fabric(part, **kw):
    cfg = FabricConfig(n_devices=8, devices_per_host=2, hosts_per_rack=2,
                       use_pallas=False, **kw)
    return CheckpointFabric(part, cfg)


def _bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    assert a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# I3: pack/unpack round trip per dtype
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", PACKABLE,
                         ids=[np.dtype(d).name for d in PACKABLE])
def test_pack_unpack_roundtrip_bit_exact(dtype):
    rng = np.random.default_rng(3)
    tree = {"w": _leaf((24, 6), dtype, rng),     # multi-block main leaf
            "v": _leaf((5,), dtype, rng),        # tail, sub-word ragged
            "s": _leaf((), dtype, rng)}          # scalar tail
    part = partition_pytree(tree, 8)
    assert arena_compatible(part) and word_packable(dtype)
    lay = build_arena_layout(part)
    out = unpack_arena(pack_arena(tree, lay), lay)
    for k in tree:
        _bits_equal(out[k], tree[k])


def test_roundtrip_extreme_bit_patterns():
    """Denormals, infs, NaNs, sign-zero, INT_MIN: the arena moves raw
    words, so even non-finite payloads round-trip bit-exactly."""
    f32 = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e-42, -1e-42,
                    np.finfo(np.float32).max], np.float32)
    bf = np.arange(8, dtype=np.uint16)
    bf = (bf * 8191 + 3).astype(np.uint16).view(jnp.bfloat16.dtype)
    i8 = np.array([-128, -1, 0, 1, 127], np.int8)
    tree = {"f": jnp.asarray(f32), "b": jnp.asarray(bf),
            "i": jnp.asarray(i8)}
    part = partition_pytree(tree, 8)
    lay = build_arena_layout(part)
    out = unpack_arena(pack_arena(tree, lay), lay)
    for k in tree:
        _bits_equal(out[k], tree[k])


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(1, 9), st.integers(0, 2 ** 31 - 1))
def test_roundtrip_property_mixed_shapes(rows, width, seed):
    rng = np.random.default_rng(seed)
    dtypes = [PACKABLE[int(rng.integers(len(PACKABLE)))] for _ in range(3)]
    tree = {"a": _leaf((rows, width), dtypes[0], rng),
            "b": _leaf((max(1, rows // 3),), dtypes[1], rng),
            "c": _leaf((), dtypes[2], rng)}
    part = partition_pytree(tree, 8)
    lay = build_arena_layout(part)
    out = unpack_arena(pack_arena(tree, lay), lay)
    for k in tree:
        _bits_equal(out[k], tree[k])
    # and the unaligned layout agrees
    lay2 = build_arena_layout(part, tail_pack=False)
    out2 = unpack_arena(pack_arena(tree, lay2), lay2)
    for k in tree:
        _bits_equal(out2[k], tree[k])


# ---------------------------------------------------------------------------
# I1/I2/I4: tail-packed layout invariants
# ---------------------------------------------------------------------------

def test_tail_packed_layout_invariants():
    tree = _mixed_params()
    part = partition_pytree(tree, 16)
    lay = build_arena_layout(part)
    assert lay.has_tail and not lay.uniform_f32

    # I1 — alignment classes
    assert lay.tail_start % ARENA_TILE == 0
    assert lay.data_words % ARENA_TILE == 0
    assert lay.total_words % ARENA_TILE == 0
    for ab in lay.blocks:
        if ab.offset < lay.tail_start:
            assert ab.offset % ARENA_TILE == 0
            assert ab.words % ARENA_TILE == 0
            assert 0 < ab.payload <= ab.words
        else:
            assert ab.words == ab.payload > 0  # word-contiguous tail

    # I2 — disjoint segments covering [0, data_words) except the
    # tail-alignment gap [tail_end, data_words)
    cover = np.zeros(lay.data_words, np.int32)
    for ab in lay.blocks:
        cover[ab.offset:ab.offset + ab.words] += 1
    assert cover.max() == 1
    uncovered = np.nonzero(cover == 0)[0]
    np.testing.assert_array_equal(uncovered,
                                  np.arange(lay.tail_end, lay.data_words))

    # I4 — pad words are zero after pack, and sub-word element pads are
    # zero *bits* (check at byte granularity through an int8 view)
    arena = np.asarray(pack_arena(tree, lay)).view(np.int32)
    payload_bytes = np.zeros(lay.total_words * 4, bool)
    for ab in lay.blocks:
        esz = np.dtype(part.leaves[ab.leaf].dtype).itemsize
        live = int(lay.payload_elems[ab.leaf]) * esz
        b0 = ab.offset * 4
        payload_bytes[b0:b0 + live] = True
    abytes = arena.view(np.int8)
    assert abytes.size == payload_bytes.size
    np.testing.assert_array_equal(abytes[~payload_bytes], 0)
    # and whole pad words in particular
    word_live = payload_bytes.reshape(-1, 4).any(axis=1)
    np.testing.assert_array_equal(arena[~word_live], 0)


def test_tail_pack_shrinks_layout():
    """Tail packing strictly shrinks a small-leaf-heavy model and the
    padding_ratio gauge reflects it."""
    rng = np.random.default_rng(5)
    tree = {f"s{i}": _leaf((3 + i,), jnp.float32, rng) for i in range(6)}
    part = partition_pytree(tree, 16)
    packed = build_arena_layout(part)
    aligned = build_arena_layout(part, tail_pack=False)
    assert packed.total_words < aligned.total_words
    assert packed.padding_ratio < aligned.padding_ratio
    assert not aligned.has_tail and packed.has_tail


# ---------------------------------------------------------------------------
# value domain (optimizer seam)
# ---------------------------------------------------------------------------

def test_decode_encode_value_domain():
    tree = _mixed_params(with_int=False)  # float leaves: values meaningful
    part = partition_pytree(tree, 16)
    lay = build_arena_layout(part)
    arena = pack_arena(tree, lay)
    vals = decode_values(arena, lay)
    assert vals.shape == (lay.total_values,) and vals.dtype == jnp.float32
    v = np.asarray(vals)
    # encode ∘ decode is the identity on the arena (bit-exact)
    back = encode_values(vals, lay)
    _bits_equal(np.asarray(back), np.asarray(arena))
    # pack_values agrees with decoding a packed arena
    gv = pack_values(tree, lay)
    np.testing.assert_array_equal(np.asarray(gv), v)


def test_decode_values_matches_astype_f32():
    """Per-leaf semantics: the decoded f32 values of a bf16 leaf are
    exactly ``leaf.astype(float32)`` (widening, hence lossless), laid out
    planar — the low element of every word, then the high one."""
    rng = np.random.default_rng(17)
    w = _leaf((16, 6), jnp.bfloat16, rng)
    part = partition_pytree({"w": w}, 16)
    lay = build_arena_layout(part)
    vals = np.asarray(decode_values(pack_arena({"w": w}, lay), lay))
    flat = np.asarray(w).astype(np.float32).ravel()
    nw = lay.value_runs()[0][1]          # words of the bf16 run
    np.testing.assert_array_equal(vals[:flat.size // 2], flat[0::2])
    np.testing.assert_array_equal(vals[nw:nw + flat.size // 2], flat[1::2])
    payload = np.zeros(vals.shape, bool)
    payload[:flat.size // 2] = payload[nw:nw + flat.size // 2] = True
    np.testing.assert_array_equal(vals[~payload], 0.0)


def test_value_domain_identity_for_f32():
    rng = np.random.default_rng(9)
    tree = {"w": _leaf((64, 6), jnp.float32, rng),
            "b": _leaf((7,), jnp.float32, rng)}
    part = partition_pytree(tree, 16)
    lay = build_arena_layout(part)
    assert lay.uniform_f32 and lay.total_values == lay.total_words
    arena = pack_arena(tree, lay)
    _bits_equal(np.asarray(decode_values(arena, lay)), np.asarray(arena))


# ---------------------------------------------------------------------------
# mixed-dtype recovery: PEER_REPLICA and PARITY, bit-exact
# ---------------------------------------------------------------------------

def test_mixed_dtype_host_loss_recovers_bit_exact_peer_replica():
    params = _mixed_params()
    part = partition_pytree(params, 16)
    fab = _fabric(part)
    ckpt = {k: jnp.zeros_like(v) for k, v in params.items()}
    fab.maintain(3, params)
    for h in range(4):
        lost, failed = fab.domain_failure("host", h)
        rec, stats = fab.on_failure(params, ckpt, lost,
                                    failed_devices=failed, step=3,
                                    persist_failure=False)
        assert stats["tier_counts"]["PEER_REPLICA"] == int(lost.sum()) > 0
        assert stats["tier_counts"]["RUNNING_CKPT"] == 0
        for k in params:
            _bits_equal(rec[k], params[k])


def test_mixed_dtype_singly_erased_recovers_bit_exact_parity():
    """XOR parity over raw words: one erased member per group XORs back
    bit-exactly — for bf16/fp8/int8 payloads just as for f32 (the words
    are opaque bit patterns to the codec)."""
    params = _mixed_params()
    part = partition_pytree(params, 16)
    fab = _fabric(part, replicate=False)
    ckpt = {k: jnp.zeros_like(v) for k, v in params.items()}
    fab.maintain(3, params)
    # deterministic singly-erased loss: the first member of each group
    members = np.asarray(fab.parity.members)
    lost = np.zeros((part.total_blocks,), bool)
    for row in members:
        ids = row[row >= 0]
        if ids.size:
            lost[ids[0]] = True
    rec, stats = fab.on_failure(params, ckpt, lost,
                                failed_devices=np.empty((0,), np.int32),
                                step=3, persist_failure=False)
    assert stats["tier_counts"]["PARITY"] == int(lost.sum()) > 0
    assert stats["tier_counts"]["RUNNING_CKPT"] == 0
    assert stats["tier_sq"]["PARITY"] == 0.0
    for k in params:
        _bits_equal(rec[k], params[k])


def test_mixed_dtype_rs_two_host_loss_bit_exact():
    """RS(k, 2) over a quantized arena: simultaneous two-host loss
    decodes through GF(256) on raw words — bit-exact for every dtype."""
    params = _mixed_params()
    part = partition_pytree(params, 16)
    fab = _fabric(part, replicate=False, rs_parity=2)
    ckpt = {k: jnp.zeros_like(v) for k, v in params.items()}
    fab.maintain(3, params)
    l0, f0 = fab.domain_failure("host", 0)
    l1, f1 = fab.domain_failure("host", 2)
    lost = l0 | l1
    failed = np.unique(np.concatenate([f0, f1]))
    rec, stats = fab.on_failure(params, ckpt, lost, failed_devices=failed,
                                step=3, persist_failure=False)
    assert stats["tier_counts"]["PARITY"] == int(lost.sum())
    assert stats["tier_fallbacks"] == []
    for k in params:
        _bits_equal(rec[k], params[k])


# ---------------------------------------------------------------------------
# integrity scrub on a quantized arena
# ---------------------------------------------------------------------------

def test_scrub_detects_and_corrects_on_quantized_arena():
    params = _mixed_params()
    part = partition_pytree(params, 16)
    fab = _fabric(part, rs_parity=2)
    ckpt = {k: jnp.zeros_like(v) for k, v in params.items()}
    fab.maintain(4, params)
    where = fab.inject_arena_bit_flip(block=3, word=2, bit=11)
    out = fab.scrub(step=4)
    assert out["checked"] and out["detected"] == 1 and out["corrected"] == 1
    r = out["reports"][0]
    assert r["kind"] == "member" and r["block"] == where["block"]
    assert r["localized"] and r["corrected"]
    assert fab.scrub(step=4)["detected"] == 0
    # corrected snapshot recovers a host loss bit-exactly afterwards
    lost, failed = fab.domain_failure("host", 1)
    rec, _ = fab.on_failure(params, ckpt, lost, failed_devices=failed,
                            step=4, persist_failure=False)
    for k in params:
        _bits_equal(rec[k], params[k])


# ---------------------------------------------------------------------------
# redundancy bytes + padding gauge (CI gate twins)
# ---------------------------------------------------------------------------

def test_bf16_redundancy_bytes_le_half_f32():
    """Layout-level twin of the ``quant_bytes_le_half_f32`` bench gate:
    the same shapes in bf16 need ≤ 0.55× the f32 arena bytes (the slack
    absorbs tile-alignment padding)."""
    rng = np.random.default_rng(13)
    # tile-width blocks (16·128 elems): the precision halving is not
    # swallowed by per-block tile alignment, as in a real model
    shapes = [("w1", (256, 128)), ("w2", (96, 128)), ("b", (9,))]
    t32 = {k: _leaf(s, jnp.float32, rng) for k, s in shapes}
    t16 = {k: _leaf(s, jnp.bfloat16, rng) for k, s in shapes}
    lay32 = build_arena_layout(partition_pytree(t32, 16))
    lay16 = build_arena_layout(partition_pytree(t16, 16))
    assert lay16.nbytes <= 0.55 * lay32.nbytes
    # and the fabric's per-sweep bytes shrink accordingly
    f32 = _fabric(partition_pytree(t32, 16))
    f16 = _fabric(partition_pytree(t16, 16))
    f32.maintain(1, t32)
    f16.maintain(1, t16)
    assert f16.stats["maintain_bytes_moved"] <= \
        0.55 * f32.stats["maintain_bytes_moved"] + 4 * ARENA_TILE


def test_padding_ratio_gauge_in_stats_and_report():
    from repro.telemetry.recorder import Recorder
    from repro.telemetry.report import format_report, run_report
    params = _mixed_params()
    part = partition_pytree(params, 16)
    rec = Recorder()
    cfg = FabricConfig(n_devices=8, devices_per_host=2, hosts_per_rack=2,
                       use_pallas=False)
    fab = CheckpointFabric(part, cfg, recorder=rec)
    assert fab.arena_layout is not None
    want = float(fab.arena_layout.padding_ratio)
    assert fab.stats["arena_padding_ratio"] == want > 0.0
    fab.maintain(1, params)
    report = run_report(rec)
    assert report["bytes"]["arena_padding_ratio"] == want
    assert "arena padding ratio" in format_report(report)
