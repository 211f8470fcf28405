"""Norm plugins + the persistent sharded checkpoint store."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint_io import ShardedCheckpointStore
from repro.core.blocks import LeafMeta, block_scores, partition_pytree
from repro.core.norms import get_norm


def test_l2_norm():
    a = jnp.asarray([[1.0, 2.0], [0.0, 0.0]])
    b = jnp.zeros((2, 2))
    leaf = LeafMeta("x", (2, 2), jnp.float32, 2, 2, 2, 0)
    got = get_norm("l2")(a, b, leaf)
    np.testing.assert_allclose(got, [5.0, 0.0])


def test_scaled_tv_norm_weights():
    # two "documents" (rows) that are distributions over 4 topics
    rows = jnp.asarray([[0.5, 0.5, 0.0, 0.0],
                        [0.25, 0.25, 0.25, 0.25]])
    prev = jnp.asarray([[1.0, 0.0, 0.0, 0.0],
                        [0.25, 0.25, 0.25, 0.25]])
    weights = np.asarray([10.0, 3.0], np.float32)
    params = {"theta": rows}
    ck = {"theta": prev}
    part = partition_pytree(params, block_rows=1)
    norm = get_norm("scaled_tv", aux={"['theta']": weights}, block_rows=1)
    scores = block_scores(params, ck, part, norm)
    # TV(row0) = 0.5 -> 5.0 weighted; TV(row1) = 0
    np.testing.assert_allclose(scores, [5.0, 0.0], rtol=1e-6)


def test_unknown_norm_raises():
    with pytest.raises(KeyError):
        get_norm("nope")


def test_store_roundtrip_partial_writes():
    params = {"w": jnp.arange(60.0, dtype=jnp.float32).reshape(20, 3),
              "b": jnp.ones((4,), jnp.float32)}
    part = partition_pytree(params, block_rows=8)
    with tempfile.TemporaryDirectory() as d:
        store = ShardedCheckpointStore(d)
        store.init(params, part)
        # overwrite one block with new values
        newp = jax.tree_util.tree_map(lambda x: x * 10, params)
        mask = np.zeros((part.total_blocks,), bool)
        w_leaf = [l for l in part.leaves if l.name == "['w']"][0]
        mask[w_leaf.offset + 1] = True   # rows 8..15 of w
        store.write_blocks(mask, newp, step=5, background=True)
        store.flush()
        back = store.read_all()
        w = np.asarray(back["w"])
        np.testing.assert_array_equal(w[:8], np.asarray(params["w"])[:8])
        np.testing.assert_array_equal(w[8:16], np.asarray(newp["w"])[8:16])
        np.testing.assert_array_equal(np.asarray(back["b"]),
                                      np.asarray(params["b"]))
        iters = store.saved_iters()
        assert iters[w_leaf.offset + 1] == 5
        assert iters[w_leaf.offset] == 0


def test_store_packed_append_log_and_compaction():
    """The packed layout appends overwritten blocks to the shard log and
    repoints the offset index at the latest copy; compaction reclaims
    exactly the dead bytes and reads still round-trip."""
    params = {"w": jnp.arange(60.0, dtype=jnp.float32).reshape(20, 3),
              "b": jnp.ones((4,), jnp.float32)}
    part = partition_pytree(params, block_rows=8)
    with tempfile.TemporaryDirectory() as d:
        store = ShardedCheckpointStore(d)
        store.init(params, part)
        assert os.path.exists(os.path.join(d, "blocks.g0000.shard"))
        base = store.disk_nbytes()
        assert base["shard"] == base["live"] > 0
        # three overwrites of the same block grow the log, not the live set
        w_leaf = [l for l in part.leaves if l.name == "['w']"][0]
        mask = np.zeros((part.total_blocks,), bool)
        mask[w_leaf.offset] = True
        for step in (1, 2, 3):
            newp = jax.tree_util.tree_map(lambda x: x * (step + 1), params)
            store.write_blocks(mask, newp, step=step, background=False)
        grown = store.disk_nbytes()
        blk_bytes = 8 * w_leaf.row_width * 4
        assert grown["shard"] == base["shard"] + 3 * blk_bytes
        assert grown["live"] == base["live"]
        # index points at the LAST copy
        np.testing.assert_array_equal(
            np.asarray(store.read_all()["w"])[:8],
            np.asarray(params["w"])[:8] * 4)
        reclaimed = store.compact()
        assert reclaimed == 3 * blk_bytes
        # crash-safe generational rewrite: new file, old one unlinked
        assert os.path.exists(os.path.join(d, "blocks.g0001.shard"))
        assert not os.path.exists(os.path.join(d, "blocks.g0000.shard"))
        after = store.disk_nbytes()
        assert after["shard"] == after["live"] == base["live"]
        np.testing.assert_array_equal(
            np.asarray(store.read_all()["w"])[:8],
            np.asarray(params["w"])[:8] * 4)
        iters = store.saved_iters()
        assert iters[w_leaf.offset] == 3


def test_compact_drops_segments_of_missing_shards(tmp_path):
    """A source shard that vanished (crash orphan / dead host) must have
    its segments dropped from the index during compact() — keeping the
    old offsets would resolve inside the bumped-generation file and read
    another segment's bytes."""
    import json
    import os
    import shutil

    import jax.numpy as jnp

    from repro.checkpoint_io import ShardedCheckpointStore
    from repro.core.blocks import partition_pytree
    from repro.fabric.domains import FailureDomainMap
    from repro.sharding.partition import block_device_homes

    params = {"w": jnp.arange(64, dtype=jnp.float32).reshape(16, 4)}
    part = partition_pytree(params, 4)
    dm = FailureDomainMap(n_devices=8, devices_per_host=2, hosts_per_rack=2)
    homes = block_device_homes(part, 8)
    store = ShardedCheckpointStore(str(tmp_path))
    store.init(params, part, homes=homes, domains=dm)
    lost_host = int(dm.host_of(homes[0]))
    shutil.rmtree(os.path.join(str(tmp_path), f"host_{lost_host:04d}"))
    store.compact()
    with open(os.path.join(str(tmp_path), "MANIFEST.json")) as f:
        segments = json.load(f)["segments"]
    lost_gids = [g for g in range(part.total_blocks)
                 if int(dm.host_of(homes[g])) == lost_host]
    assert lost_gids
    for g in lost_gids:
        assert segments[g] is None          # dropped, not stale
    vals = store.read_all()                 # lost blocks read back zero,
    arr = np.asarray(jax.tree_util.tree_leaves(vals)[0])  # never garbage
    for g in lost_gids:
        assert not arr[g * 4:(g + 1) * 4].any()
    survivors = [g for g in range(part.total_blocks) if g not in lost_gids]
    for g in survivors:
        np.testing.assert_array_equal(arr[g * 4:(g + 1) * 4],
                                      np.asarray(params["w"])[g * 4:(g + 1) * 4])


# ---------------------------------------------------------------------------
# the parity mirror under async_persist: the store's background writer,
# one save in flight
# ---------------------------------------------------------------------------

def _parity_ctl(root, async_persist, recorder=None):
    """An arena controller with an elastic fabric and a domain-keyed
    store, and its (drifted) live parameters."""
    from repro.core.controller import FTController
    from repro.core.policy import (CheckpointPolicy, RecoveryMode,
                                   SelectionStrategy)
    from repro.fabric import FabricConfig
    rng = np.random.default_rng(7)
    params = {"w": jnp.asarray(rng.normal(size=(50, 6)), jnp.float32),
              "emb": jnp.asarray(rng.normal(size=(33, 8)), jnp.float32)}
    pol = CheckpointPolicy(fraction=0.25, full_interval=1,
                           strategy=SelectionStrategy.ROUND_ROBIN,
                           recovery=RecoveryMode.PARTIAL, block_rows=16,
                           async_persist=async_persist)
    store = ShardedCheckpointStore(str(root))
    ctl = FTController(params, pol, store=store, recorder=recorder,
                       fabric=FabricConfig(elastic=True))
    live = jax.tree_util.tree_map(lambda x: x + 0.5, params)
    return ctl, live


def _hold_writer(store):
    """Hold the store's background writer before its next shard write
    until the returned Event is set; everything queued behind waits."""
    import threading
    gate = threading.Event()
    real = store._do_write

    def held(jobs, step):
        assert gate.wait(60), "writer never released"
        return real(jobs, step)

    store._do_write = held
    return gate


def _parity_as_saved(fab):
    """What the save's mirror must hold: a copy of the parity, members
    and homes taken right after the save returned."""
    return (np.array(fab.parity.parity),
            [[int(b) for b in row if b >= 0]
             for row in np.asarray(fab.parity.members)],
            np.asarray(fab.parity.parity_homes).tolist())


@pytest.mark.parametrize("persist", ["sync", "background",
                                     "background_restripe"])
def test_parity_mirror_is_the_saves_snapshot(tmp_path, persist):
    """Without async_persist the mirror is on disk when checkpoint_now
    returns. With it, checkpoint_now returns while the writer is held;
    once released and flushed the mirror holds that save's parity bit for
    bit, with its step, members, homes and host keying as of the save,
    even when a host loss re-stripes the codec in between."""
    background = persist != "sync"
    ctl, live = _parity_ctl(tmp_path, async_persist=background)
    store, fab = ctl.store, ctl.fabric
    gate = _hold_writer(store) if background else None
    ctl.maintain(1, live)
    ctl.checkpoint_now(1, live)
    want, members, homes = _parity_as_saved(fab)
    if background:
        assert store.read_parity() is None      # queued, not written
        if persist == "background_restripe":
            live, _ = ctl.on_domain_event(live, "host", 0, step=1)
            ctl.maintain(2, live)
            now = _parity_as_saved(fab)
            assert now[1] != members or now[2] != homes  # re-striped
        gate.set()
        store.flush()
    parity, meta = store.read_parity()
    assert meta["step"] == 1
    assert parity.dtype == want.dtype
    np.testing.assert_array_equal(parity, want)
    assert meta["members"] == members and meta["parity_homes"] == homes
    hosts = fab.domains.host_of(np.asarray(homes))
    assert meta["paths"] == [f"host_{int(h):04d}/parity_{g:06d}.npy"
                             for g, h in enumerate(hosts)]
    assert not [f for _, _, fs in os.walk(str(tmp_path)) for f in fs
                if f.endswith(".tmp")]


@pytest.mark.parametrize("keyed", [False, True])
def test_parity_background_write_failure_names_step_and_file(tmp_path,
                                                             keyed):
    """A background parity write that fails every retry surfaces on the
    next flush(), with its step and the group file it was writing; the
    error is one-shot."""
    from repro.fabric.domains import FailureDomainMap
    from repro.telemetry.recorder import Recorder
    params = {"w": jnp.arange(96.0, dtype=jnp.float32).reshape(32, 3)}
    part = partition_pytree(params, block_rows=8)
    dm = FailureDomainMap(4, 2, 2)
    homes = np.arange(part.total_blocks, dtype=np.int32) % 4
    store = ShardedCheckpointStore(str(tmp_path))
    store._retry_base_delay = 1e-4
    rec = Recorder()
    store.attach_recorder(rec)
    store.init(params, part, **(dict(homes=homes, domains=dm) if keyed
                                else {}))
    parity = np.arange(3 * 5, dtype=np.int32).reshape(3, 5)
    phomes = np.array([1, 2, 3], np.int32)
    rel = (f"host_{int(dm.host_of(2)):04d}/parity_000001.npy" if keyed
           else "parity_000001.npy")
    bad = os.path.join(str(tmp_path), rel)
    os.makedirs(bad + ".tmp")       # the group's temp file cannot be made
    assert store.write_parity(9, parity, phomes, domains=dm,
                              background=True) == parity.nbytes
    with pytest.raises(RuntimeError, match="background checkpoint write") \
            as ei:
        store.flush()
    msg = str(ei.value)
    assert "step 9" in msg and bad in msg
    assert "attempts" in str(ei.value.__cause__)
    assert isinstance(ei.value.__cause__.__cause__, OSError)
    failed = [e for e in rec.events if e["kind"] == "store_write_failed"]
    assert len(failed) == 1
    assert failed[0]["step"] == 9 and failed[0]["path"] == bad
    assert failed[0]["write"] == "parity"
    retried = [e for e in rec.events if e["kind"] == "store_write_retried"]
    assert len(retried) == store._retry_limit
    assert store.read_parity() is None       # PARITY.json never published
    store.flush()                             # one-shot


def test_second_save_waits_for_the_first_saves_writes(tmp_path):
    """Under async_persist a save queues its writes only once the writer
    has finished every write of the previous save: the wait is booked in
    scar/save/store_wait, and each enqueue finds at most its own save's
    earlier write still in flight."""
    import threading
    from repro.telemetry.recorder import Recorder
    rec = Recorder()
    ctl, live = _parity_ctl(tmp_path, async_persist=True, recorder=rec)
    store = ctl.store
    in_flight = []
    real_put = store._q.put

    def put(item, *a, **kw):
        in_flight.append(store._q.unfinished_tasks)
        return real_put(item, *a, **kw)

    store._q.put = put
    gate = _hold_writer(store)
    ctl.maintain(1, live)
    ctl.checkpoint_now(1, live)          # returns with the writer held
    assert in_flight == [0, 1]           # its shard write, then its parity
    held = 0.3
    threading.Timer(held, gate.set).start()
    ctl.maintain(2, live)
    ctl.checkpoint_now(2, live)          # waits for save 1's two writes
    assert in_flight[2] == 0 and max(in_flight) <= 1
    waits = [s for s in rec.tracer.spans if s.name == "scar/save/store_wait"]
    assert [s.step for s in waits] == [1, 2]
    assert waits[1].duration >= held / 2 > 0
    save = {s.sid: s for s in rec.tracer.spans}[waits[1].parent]
    assert save.name == "scar/save" and save.step == 2
    store.flush()
    assert store.read_parity()[1]["step"] == 2
    writes = [s for s in rec.tracer.spans
              if s.name == "scar/store/parity_write"]
    assert [s.step for s in writes] == [1, 2]
    assert all(s.args["bytes"] == np.asarray(ctl.fabric.parity.parity).nbytes
               for s in writes)


@pytest.mark.parametrize("keyed", [False, True])
@pytest.mark.parametrize("codec", ["xor", "rs"])
def test_parity_background_and_inline_write_the_same_bytes(tmp_path, keyed,
                                                           codec):
    """The background mirror writes the same files, byte for byte, as the
    inline one: every group file and PARITY.json, for XOR ``(groups, E)``
    and RS ``(groups, m, E)`` parity, keyed by host or not."""
    from repro.fabric.domains import FailureDomainMap
    params = {"w": jnp.arange(96.0, dtype=jnp.float32).reshape(32, 3)}
    part = partition_pytree(params, block_rows=8)
    dm = FailureDomainMap(4, 2, 2)
    homes = np.arange(part.total_blocks, dtype=np.int32) % 4
    rng = np.random.default_rng(3)
    if codec == "xor":
        parity = rng.integers(-2**31, 2**31, (3, 5), dtype=np.int32)
        phomes = np.array([1, 2, 3], np.int32)
    else:
        parity = rng.integers(0, 256, (3, 2, 5), dtype=np.uint8)
        phomes = np.array([[1, 2], [2, 3], [3, 0]], np.int32)
    members = np.array([[0, 1, -1], [2, 3, 0], [1, -1, -1]], np.int32)
    trees = {}
    for how in ("inline", "background"):
        root = tmp_path / how
        store = ShardedCheckpointStore(str(root))
        store.init(params, part, **(dict(homes=homes, domains=dm) if keyed
                                    else {}))
        store.write_parity(4, parity, phomes, domains=dm, members=members,
                           background=how == "background")
        store.flush()
        trees[how] = {
            os.path.relpath(os.path.join(d, f), root):
                open(os.path.join(d, f), "rb").read()
            for d, _, fs in os.walk(root) for f in fs
            if f.startswith("parity_") or f == "PARITY.json"}
    assert len(trees["inline"]) == parity.shape[0] + 1
    assert trees["background"] == trees["inline"]


@pytest.mark.parametrize("fails", [False, True])
def test_background_writer_drops_each_snapshot_once_written(tmp_path,
                                                           fails):
    """The writer frees a queued parity snapshot as soon as its write has
    landed or failed, not when the next item arrives: the host holds no
    payload of a finished save."""
    import gc
    import weakref
    params = {"w": jnp.arange(96.0, dtype=jnp.float32).reshape(32, 3)}
    part = partition_pytree(params, block_rows=8)
    store = ShardedCheckpointStore(str(tmp_path))
    store._retry_base_delay = 1e-4
    store.init(params, part)
    parity = np.arange(3 * 5, dtype=np.int32).reshape(3, 5)
    if fails:
        os.makedirs(os.path.join(str(tmp_path), "parity_000000.npy.tmp"))
    store.write_parity(2, parity, np.array([1, 2, 3], np.int32),
                       background=True)
    ref = weakref.ref(parity)
    del parity
    store.wait_writes()
    gc.collect()
    assert ref() is None
    if fails:
        with pytest.raises(RuntimeError, match="step 2"):
            store.flush()
    else:
        store.flush()
        assert store.read_parity()[1]["step"] == 2
