"""On-disk mirror of the running checkpoint (paper §4.3 persistent storage).

Layout: **packed per-shard block files**. Block payloads are appended to a
log-structured shard file (``blocks.shard``, or ``host_NNNN/blocks.shard``
under the fabric-aware domain keying) and MANIFEST.json carries an offset
index — ``segments[gid] = [offset, nbytes]`` points at each block's *latest*
copy. Earlier layouts wrote one ``.npy`` file per block, which costs a
file create + rename + metadata flush per saved block; a fraction-r partial
save of k blocks now appends k contiguous payloads to (at most) a handful
of shard files and publishes one manifest. Reads go through ``np.memmap``
slices of the shard, so a partial DISK-tier read touches only the needed
blocks' byte ranges.

Crash consistency is log-structured: appends land before the manifest is
atomically replaced, so a crash mid-write leaves dangling bytes at the tail
of a shard (unreferenced garbage) but never a torn block — readers follow
the old index until the new one is published. ``compact()`` rewrites each
shard keeping only live segments (the log otherwise grows by the write
volume of overwritten blocks; ``disk_nbytes`` reports both). Compaction
writes a *new generation* file (``blocks.gNNNN.shard``), publishes the
manifest pointing into it, and only then removes older generations — a
crash at any point leaves either the old index over the old file or the
new index over the new file, never live offsets into a rewritten file.

Writes can be deferred to a background thread (``background=True``),
matching §4.3: "the training algorithm can be resumed as soon as the
in-memory caches have been updated, while output to the shared persistent
storage happens asynchronously". Shard writes and parity mirrors share one
FIFO writer, so a save's parity is published after its shard write; each
queued item is a host snapshot taken at enqueue and dropped once written.
:meth:`wait_writes` blocks until the writer is idle, which a caller uses
to keep one save in flight; :meth:`flush` does the same and raises a
parked failure.

**Fabric-aware sharding** (optional ``homes``/``domains`` at ``init``):
shards are keyed by failure domain — ``host_NNNN/blocks.shard`` per the
block's home host — and the manifest records ``host_of_block``. A DISK-tier
read after a domain loss then touches only the surviving domains' shards
(:meth:`read_blocks`), and :meth:`read_surviving` models a host-local
deployment where a dead domain's shard is unreachable. :meth:`write_parity`
mirrors the fabric's XOR parity blocks to disk so blocks whose domain shard
died remain reconstructable offline from the surviving members + parity.
"""
from __future__ import annotations

import dataclasses
import json
import os
import queue
import random
import threading
import time
import traceback
from typing import Any, Optional

import jax
import numpy as np

from repro.core.blocks import BlockPartition
from repro.telemetry.recorder import NULL_RECORDER
from repro.telemetry.spans import current_span, current_tracer, span

PyTree = Any


@dataclasses.dataclass
class _Write:
    """One queued shard write: its segments, and what the writer books
    when it lands (bytes, enqueue time, the enqueuer's tracer). Each kind
    of queued write names its span and says how to run itself, what
    to do once landed, and what a failure names."""
    jobs: list
    step: int
    nbytes: int
    t_enqueue: float
    tracer: Any
    span = "scar/store/write"

    def run(self, store: "ShardedCheckpointStore") -> None:
        store._do_write(self.jobs, self.step)

    def landed(self) -> None:
        # the manifest is published: the write is durable (the
        # enqueue-to-publish lag is the shard writes' alone)
        if self.tracer is not None:
            self.tracer.add_lag(time.perf_counter() - self.t_enqueue)

    def context(self, store: "ShardedCheckpointStore") -> dict:
        """The batch's first job: enough to name the shard that broke."""
        ctx = {"write": "shard", "step": int(self.step), "segment": None,
               "host": None, "path": None}
        if self.jobs:
            seg = int(self.jobs[0][0])
            ctx.update(segment=seg, path=store._shard_path(seg))
            if store.host_of_block is not None:
                ctx["host"] = int(store.host_of_block[store._seg_gid(seg)])
        return ctx


@dataclasses.dataclass
class _ParityWrite:
    """One parity mirror, snapshotted when it is made: the host parity
    array and its ``PARITY.json`` (step, per-group file paths, homes and
    members as of the save), and the tracer its span books into. ``path``
    is the file being written, which a failure names."""
    parity: np.ndarray
    meta: dict
    tracer: Any
    path: Optional[str] = None
    span = "scar/store/parity_write"

    @property
    def step(self) -> int:
        return self.meta["step"]

    @property
    def nbytes(self) -> int:
        return int(self.parity.nbytes)

    def run(self, store: "ShardedCheckpointStore") -> None:
        store._put_parity(self)

    def landed(self) -> None:
        pass

    def context(self, store: "ShardedCheckpointStore") -> dict:
        return {"write": "parity", "step": int(self.step), "segment": None,
                "host": None, "path": self.path}


def _shard_name(gen: int) -> str:
    return f"blocks.g{gen:04d}.shard"


def _is_shard_name(name: str) -> bool:
    return name.startswith("blocks.") and name.endswith(".shard")


class ShardedCheckpointStore:
    def __init__(self, root: str):
        self.root = root
        self.partition: Optional[BlockPartition] = None
        self.must_reload = False
        self.host_of_block: Optional[np.ndarray] = None
        # flat-arena layout (optional): segments are keyed by arena-block
        # id — one row per (leaf, block), so colocated leaves (which share
        # global block ids) each persist their own payload
        self.arena_layout = None
        self._leaf_first_seg: Optional[np.ndarray] = None
        # per shard-directory compaction generation (segments index offsets
        # are only valid within their generation's file)
        self._gen: dict = {}
        self._q: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._worker_error: Optional[BaseException] = None
        self._worker_error_ctx: Optional[dict] = None
        self.recorder = NULL_RECORDER
        os.makedirs(root, exist_ok=True)

    def attach_recorder(self, recorder: Any) -> None:
        """Late-bind a recorder (events only — the store keeps no stats
        dict). No-op if ``recorder`` is null or one is already attached."""
        if recorder is None or not getattr(recorder, "enabled", False) \
                or self.recorder.enabled:
            return
        self.recorder = recorder

    # -- lifecycle ----------------------------------------------------------

    def init(self, params: PyTree, partition: BlockPartition,
             homes: Optional[np.ndarray] = None,
             domains: Optional[Any] = None,
             arena_layout=None,
             arena_values: Optional[np.ndarray] = None) -> None:
        """``homes``/``domains`` (a block→device map + ``FailureDomainMap``)
        switch on the domain-keyed layout. The keying snapshots the homes at
        init — the *initial* placement; elastic re-homing moves the in-memory
        tiers, while the disk mirror keeps its stable layout until a
        re-keying :meth:`compact` migrates segments to their current homes.

        ``arena_layout`` (+ ``arena_values``, the packed word arena of
        ``params``) switches on the **arena segment layout**: segments are
        the arena block table's rows (word payloads — raw leaf-dtype bytes
        for word-packable dtypes, the f32 image otherwise; one per
        (leaf, block)), a save appends one contiguous buffer per host
        shard, and partial reads memmap exactly the needed byte ranges."""
        self.partition = partition
        self.arena_layout = arena_layout
        self._gen = {}
        if arena_layout is not None:
            # arena-block index of each leaf's first block. The block
            # table is offset-ordered (tail-packed leaves after the main
            # region), NOT flatten-ordered — derive from the table, where
            # each leaf's blocks are contiguous and in b order.
            first = np.full((len(partition.leaves),), -1, np.int64)
            for idx, ab in enumerate(arena_layout.blocks):
                if first[ab.leaf] < 0:
                    first[ab.leaf] = idx
            self._leaf_first_seg = first
        if homes is not None and domains is not None:
            self.host_of_block = np.asarray(
                domains.host_of(np.asarray(homes)), np.int32)
            for h in np.unique(self.host_of_block):
                os.makedirs(os.path.join(self.root, f"host_{int(h):04d}"),
                            exist_ok=True)
        n_segments = (len(arena_layout.blocks) if arena_layout is not None
                      else partition.total_blocks)
        manifest = {
            "block_rows": partition.block_rows,
            "leaves": [
                {"name": l.name, "shape": list(l.shape), "dtype": str(np.dtype(l.dtype)),
                 "rows": l.rows, "row_width": l.row_width,
                 "n_blocks": l.n_blocks, "offset": l.offset}
                for l in partition.leaves
            ],
            "saved_iter": [0] * partition.total_blocks,
            "segments": [None] * n_segments,
        }
        if arena_layout is not None:
            # per-segment stored dtype: word-packable leaves persist raw
            # element bytes in that dtype, everything else the f32 image —
            # an offline reader needs no partition object to decode
            from repro.core.blocks import word_packable
            seg_dtype = [
                str(np.dtype(partition.leaves[ab.leaf].dtype))
                if word_packable(partition.leaves[ab.leaf].dtype)
                else "float32"
                for ab in arena_layout.blocks]
            manifest["arena"] = {"n_segments": n_segments,
                                 "segment_dtype": seg_dtype}
        if self.host_of_block is not None:
            manifest["host_of_block"] = [int(h) for h in self.host_of_block]
        self._write_manifest(manifest)
        # initial full mirror (x^(0)) — the running checkpoint's base
        full_mask = np.ones((partition.total_blocks,), bool)
        if arena_layout is not None:
            assert arena_values is not None, \
                "arena-layout init needs the packed arena values"
            from repro.core.arena import ARENA_TILE
            tiles = arena_layout.tiles_for_blocks(
                np.arange(partition.total_blocks))
            data = np.asarray(arena_values, np.float32).reshape(
                -1, ARENA_TILE)[tiles]
            self.write_arena(full_mask, tiles, data, step=0,
                             background=False)
        else:
            self.write_blocks(full_mask, params, step=0, background=False)

    # -- arena segment helpers ----------------------------------------------

    def _seg_gid(self, seg: int) -> int:
        """Global block id owning segment ``seg`` (identity without an
        arena layout)."""
        if self.arena_layout is None:
            return int(seg)
        return int(self.arena_layout.blocks[seg].gid)

    def _manifest_path(self) -> str:
        return os.path.join(self.root, "MANIFEST.json")

    def _write_manifest(self, manifest: dict) -> None:
        """Atomic replace: a crash mid-write can never leave a torn manifest
        (readers either see the old complete file or the new one)."""
        tmp = self._manifest_path() + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f)
        os.replace(tmp, self._manifest_path())

    def _shard_dir(self, seg: int) -> str:
        """Shard directory of a segment (arena-block id in arena mode,
        global block id otherwise)."""
        if self.host_of_block is not None:
            gid = self._seg_gid(seg)
            host_dir = f"host_{int(self.host_of_block[gid]):04d}"
            return os.path.join(self.root, host_dir)
        return self.root

    def _shard_path(self, seg: int) -> str:
        d = self._shard_dir(seg)
        return os.path.join(d, _shard_name(self._gen.get(d, 0)))

    # -- write path ---------------------------------------------------------

    def write_blocks(self, mask, values: PyTree, step: int,
                     background: bool = True) -> int:
        """Persist the masked blocks. Returns bytes written (scheduled)."""
        assert self.partition is not None, "call init() first"
        mask_np = np.asarray(mask)
        # materialize only the selected blocks on host
        leaves = jax.tree_util.tree_leaves(values)
        jobs: list[tuple[int, np.ndarray]] = []
        nbytes = 0
        br = self.partition.block_rows
        if self.arena_layout is not None:
            # arena-layout store fed from a PyTree: convert each selected
            # (leaf, block) to its word arena payload so the on-disk
            # format stays uniform (and colocated leaves each keep their
            # own segment instead of overwriting a shared gid key).
            # Word-packable dtypes store raw little-endian element bytes
            # zero-padded to whole words; legacy dtypes (f64/int64/bool)
            # keep the f32-image convention, one word per element.
            from repro.core.blocks import word_packable
            for li, (leaf_meta, x) in enumerate(
                    zip(self.partition.leaves, leaves)):
                seg = mask_np[leaf_meta.offset:
                              leaf_meta.offset + leaf_meta.n_blocks]
                if not seg.any():
                    continue
                packable = word_packable(leaf_meta.dtype)
                arr = (np.asarray(x) if packable
                       else np.asarray(x, np.float32)).reshape(
                    max(leaf_meta.rows, 1), -1)
                payload = self.arena_layout.payload_words[li]
                for b in np.nonzero(seg)[0]:
                    lo = int(b) * br
                    hi = min(lo + br, max(leaf_meta.rows, 1))
                    blk = np.ascontiguousarray(arr[lo:hi]).reshape(-1)
                    full = np.zeros((payload,), np.float32)
                    if packable:
                        full.view(np.dtype(leaf_meta.dtype))[:blk.size] = blk
                    else:
                        full[:blk.size] = blk
                    ab = int(self._leaf_first_seg[li]) + int(b)
                    jobs.append((ab, full))
                    nbytes += full.nbytes
        else:
            for leaf_meta, x in zip(self.partition.leaves, leaves):
                seg = mask_np[leaf_meta.offset:leaf_meta.offset + leaf_meta.n_blocks]
                if not seg.any():
                    continue
                arr = np.asarray(x).reshape(max(leaf_meta.rows, 1), -1)
                for b in np.nonzero(seg)[0]:
                    lo, hi = b * br, min((b + 1) * br, leaf_meta.rows)
                    blk = arr[lo:hi] if hi > lo else arr[:1]
                    jobs.append((leaf_meta.offset + int(b), blk))
                    nbytes += blk.nbytes
        self._submit(jobs, step, nbytes, background)
        return nbytes

    def write_arena(self, mask, tiles: np.ndarray, data: np.ndarray,
                    step: int, background: bool = True) -> int:
        """Persist arena segments straight from gathered arena tiles.

        ``tiles``/``data``: the ascending tile indices covering the
        selected blocks and their ``(len(tiles), ARENA_TILE)`` float32
        payloads (the controller gathers them off-device in one O(k)
        transfer). Each selected arena block's payload is sliced out
        contiguously; the write path batches all of a host's payloads
        into **one** append write per shard file."""
        assert self.arena_layout is not None, "store not in arena mode"
        mask_np = np.asarray(mask, bool)
        tiles = np.asarray(tiles, np.int64)
        from repro.core.arena import ARENA_TILE
        flat = np.asarray(data, np.float32).reshape(-1)
        jobs: list[tuple[int, np.ndarray]] = []
        nbytes = 0
        # O(selected): only the masked gids' arena blocks are visited
        for ab_index in self.arena_layout.blocks_for_gids(
                np.nonzero(mask_np)[0]):
            ab = self.arena_layout.blocks[ab_index]
            t0 = ab.offset // ARENA_TILE
            # tail-packed blocks start mid-tile and may straddle two tiles;
            # their (consecutive-integer) tiles sit at adjacent positions
            # of the unique ascending gather, so one flat slice from the
            # intra-tile start still covers the payload
            last = (ab.offset + max(ab.words, 1) - 1) // ARENA_TILE
            nt = int(last - t0 + 1)
            pos = int(np.searchsorted(tiles, t0))
            assert pos + nt <= tiles.size and tiles[pos] == t0, \
                "gathered tiles do not cover the selected blocks"
            start = pos * ARENA_TILE + (ab.offset - t0 * ARENA_TILE)
            payload = flat[start:start + ab.payload]
            jobs.append((int(ab_index), payload))
            nbytes += payload.nbytes
        self._submit(jobs, step, nbytes, background)
        return nbytes

    def _submit(self, jobs, step: int, nbytes: int, background: bool,
                ) -> None:
        """Write ``jobs`` now, or queue them for the background writer
        with the time and the tracer of the enqueue (the writer books the
        write's span, bytes and enqueue-to-publish lag into that tracer).
        The queue depth at enqueue rides on the caller's open span and on
        the ``mirror`` event."""
        depth = None
        if background:
            self._ensure_worker()
            depth = self._q.qsize()
            sp = current_span()
            if sp is not None:
                sp.set(queue_depth=depth)
            self._q.put(_Write(jobs, int(step), int(nbytes),
                               time.perf_counter(),
                               current_tracer(self.recorder.tracer)))
        else:
            with self.recorder.span("scar/store/write",
                                    step=int(step)) as sp:
                self._do_write(jobs, step)
                sp.add_bytes(nbytes)
        if self.recorder.enabled:
            self.recorder.event("mirror", step=int(step), bytes=nbytes,
                                segments=len(jobs), background=background,
                                queue_depth=depth)

    def write_parity(self, step: int, parity: np.ndarray,
                     parity_homes: np.ndarray,
                     domains: Optional[Any] = None,
                     members: Optional[np.ndarray] = None,
                     background: bool = False) -> int:
        """Mirror the fabric's parity blocks to disk for offline
        reconstruction. One file per group, keyed by the parity home's host
        when the store is domain-keyed, plus a small ``PARITY.json``
        manifest (step, frame width, per-group paths, and — essential for
        reconstruction after a restart — each group's member block ids as
        of encode time, which elastic re-striping changes). Returns the
        parity bytes written (scheduled).

        The parity covers every group on every call, so it can be several
        times the size of a partial save's block write. ``background=True``
        queues it behind the shard writes already queued: the host copy of
        ``parity``, the homes, members and file paths are taken now, so a
        later re-stripe, loss or heal cannot change what is written or
        where. Its failures surface on :meth:`flush` like a shard write's."""
        parity = np.asarray(parity)
        item = _ParityWrite(parity, self._parity_meta(
            step, parity.shape, parity_homes, domains, members),
            current_tracer(self.recorder.tracer))
        if background:
            self._ensure_worker()
            self._q.put(item)
        else:
            with self.recorder.span("scar/store/parity_write",
                                    step=int(step)) as sp:
                self._put_parity(item)
                sp.add_bytes(item.nbytes)
        return item.nbytes

    def _parity_meta(self, step: int, shape: tuple, parity_homes, domains,
                     members) -> dict:
        """``PARITY.json`` of a mirror of parity ``shape``, its file paths
        keyed by the homes as they are now."""
        # XOR homes are (n_groups,); RS(k, m) homes are (n_groups, m) with
        # a (n_groups, m, E) parity array — each group's rows share a file,
        # keyed by row 0's host (the primary fingerprint row)
        homes = np.asarray(parity_homes, np.int32)
        if self.host_of_block is not None and domains is not None:
            keys = homes.reshape(homes.shape[0], -1)[:, 0]
            hosts = np.asarray(domains.host_of(keys)).reshape(-1)
            paths = [os.path.join(f"host_{int(hosts[g]):04d}",
                                  f"parity_{g:06d}.npy")
                     for g in range(shape[0])]
        else:
            paths = [f"parity_{g:06d}.npy" for g in range(shape[0])]
        meta = {"step": int(step), "n_groups": int(shape[0]),
                "frame_elems": int(shape[-1]) if len(shape) > 1 else 1,
                "n_parity": int(shape[1]) if len(shape) == 3 else 1,
                "paths": paths,
                "parity_homes": homes.tolist()}
        if members is not None:
            meta["members"] = [[int(b) for b in row if b >= 0]
                               for row in np.asarray(members)]
        return meta

    def _put_parity(self, item: _ParityWrite) -> None:
        """Write each group's file (``.tmp``, then replaced), then
        replace ``PARITY.json`` last."""
        paths = [os.path.join(self.root, rel) for rel in item.meta["paths"]]
        for d in sorted({os.path.dirname(p) for p in paths}):
            os.makedirs(d, exist_ok=True)
        for g, path in enumerate(paths):
            item.path = path
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                np.save(f, item.parity[g])
            os.replace(tmp, path)
        item.path = os.path.join(self.root, "PARITY.json")
        tmp = item.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(item.meta, f)
        os.replace(tmp, item.path)

    def read_parity(self) -> Optional[tuple[np.ndarray, dict]]:
        """(parity array, manifest) from the last mirror, or None."""
        meta_path = os.path.join(self.root, "PARITY.json")
        if not os.path.exists(meta_path):
            return None
        with open(meta_path) as f:
            meta = json.load(f)
        groups = [np.load(os.path.join(self.root, rel))
                  for rel in meta["paths"]]
        return np.stack(groups), meta

    def _ensure_worker(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(target=self._drain, daemon=True)
            self._worker.start()

    # background-write retry budget: a failed batch is re-attempted this
    # many times with jittered exponential backoff (base * 2^attempt *
    # U[0.5, 1.5)) before the error is parked for flush(). Shared-FS blips
    # (NFS timeouts, transient ENOSPC during log rotation) usually clear
    # within one backoff; anything persistent still surfaces — never
    # silently. Tests shrink the base delay to keep the suite fast.
    _retry_limit = 2
    _retry_base_delay = 0.05

    def _drain(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                with span(item.span, item.tracer, step=item.step) as sp:
                    self._write_with_retry(item)
                    sp.add_bytes(item.nbytes)
                item.landed()
            except BaseException as e:  # keep draining; surface on flush()
                # the parked error keeps its traceback, not the frames'
                # locals: a failed write's snapshot is freed too
                cause = e
                while cause is not None:
                    traceback.clear_frames(cause.__traceback__)
                    cause = cause.__cause__
                if self._worker_error is None:
                    # keep the FIRST failure's context — later failures
                    # are usually cascades of the same root cause
                    self._worker_error = e
                    self._worker_error_ctx = self._job_context(item)
                    if self.recorder.enabled:
                        # name the ROOT cause, not the retry-budget
                        # wrapper — that's what names the broken disk
                        root = e
                        while root.__cause__ is not None:
                            root = root.__cause__
                        self.recorder.event("store_write_failed",
                                            error=repr(root),
                                            **self._worker_error_ctx)
            finally:
                # the snapshot is freed once written, not when the next
                # item arrives; task_done even on failure — otherwise
                # q.join() in flush() deadlocks forever on the first bad
                # write
                item = None
                self._q.task_done()

    def _write_with_retry(self, item) -> None:
        for attempt in range(self._retry_limit + 1):
            try:
                item.run(self)
                return
            except BaseException as e:
                if attempt >= self._retry_limit:
                    raise RuntimeError(
                        f"background write failed after "
                        f"{self._retry_limit + 1} attempts") from e
                delay = (self._retry_base_delay * (2 ** attempt)
                         * (0.5 + random.random()))
                if self.recorder.enabled:
                    self.recorder.event(
                        "store_write_retried", attempt=attempt + 1,
                        delay_seconds=delay, error=repr(e),
                        **self._job_context(item))
                time.sleep(delay)

    def _job_context(self, item) -> dict:
        """write kind, step, segment, host and path of a failed background
        write, for the error ``flush()`` raises and the
        ``store_write_failed`` event."""
        try:
            return item.context(self)
        except BaseException:
            # diagnostics must never mask the original failure
            return {"write": None, "step": None, "segment": None,
                    "host": None, "path": None}

    def _do_write(self, jobs, step: int) -> None:
        """Append the segments' payloads to their shards, then publish the
        new offset index atomically — the log-structured write path.
        Each shard's payloads are coalesced into one buffer first, so a
        partial save costs ONE append write per touched host shard."""
        by_shard: dict[str, list[tuple[int, np.ndarray]]] = {}
        for seg, blk in jobs:
            by_shard.setdefault(self._shard_path(seg), []).append((seg, blk))
        new_segments: dict[int, list[int]] = {}
        for path, batch in by_shard.items():
            with open(path, "ab") as f:
                off = f.tell()
                chunks = []
                for seg, blk in batch:
                    payload = np.ascontiguousarray(blk)
                    new_segments[seg] = [off, int(payload.nbytes)]
                    off += int(payload.nbytes)
                    chunks.append(payload.tobytes())
                f.write(b"".join(chunks))
                f.flush()
                os.fsync(f.fileno())
        with open(self._manifest_path()) as f:
            manifest = json.load(f)
        for seg, _ in jobs:
            manifest["saved_iter"][self._seg_gid(seg)] = int(step)
            manifest["segments"][seg] = new_segments[seg]
        self._write_manifest(manifest)

    def wait_writes(self) -> None:
        """Block until the background writer has finished every queued
        write, landed or failed. Never raises: a failure stays parked for
        the next :meth:`flush`."""
        if self._worker is not None and self._worker.is_alive():
            self._q.join()

    def flush(self) -> None:
        """Block until all background writes have landed.

        Raises if any background write failed since the last flush — a
        silently-lost mirror write would otherwise surface only at recovery
        time, when the data is already gone.
        """
        self.wait_writes()
        if self._worker_error is not None:
            err, self._worker_error = self._worker_error, None
            ctx, self._worker_error_ctx = self._worker_error_ctx, None
            detail = ", ".join(f"{k} {v}" for k, v in (ctx or {}).items()
                               if v is not None)
            detail = f" ({detail})" if detail else ""
            raise RuntimeError(
                f"background checkpoint write failed{detail}") from err

    def compact(self, rekey_homes: Optional[np.ndarray] = None,
                domains: Optional[Any] = None) -> int:
        """Rewrite every shard keeping only the live (indexed) segments.

        The append log grows by the write volume of overwritten blocks;
        compaction reclaims it. Returns the bytes reclaimed. Synchronous
        and exclusive — callers stop writing around it (the background
        queue is flushed first).

        ``rekey_homes`` (+ ``domains``) re-keys the domain layout during
        the same generational rewrite: each live segment is copied into
        the shard of its block's *current* home host, so after long
        elastic degradation the on-disk locality matches the placement
        engine's view again — the move rides the rewrite the compaction
        was paying for anyway. Subsequent writes land on the new homes.

        Crash-safe ordering: the live segments are copied into the *next
        generation's* files, the manifest (new offsets + generation +
        re-keyed ``host_of_block``) is published atomically, and only
        then are older generation files unlinked — stale offsets never
        point into a rewritten file; a crash before the unlink merely
        leaves an orphan generation that the next compaction sweeps up."""
        assert self.partition is not None
        self.flush()
        with open(self._manifest_path()) as f:
            manifest = json.load(f)
        segments = manifest["segments"]
        # source paths are resolved under the OLD keying, targets under
        # the new one — a re-key changes host_of_block between the two
        src_path = {seg: self._shard_path(seg)
                    for seg in range(len(segments))
                    if segments[seg] is not None}
        old_dirs = {self._shard_dir(seg) for seg in src_path}
        if rekey_homes is not None:
            assert domains is not None, "re-keying needs the domain map"
            self.host_of_block = np.asarray(
                domains.host_of(np.asarray(rekey_homes)), np.int32)
            manifest["host_of_block"] = [int(h) for h in self.host_of_block]
            for h in np.unique(self.host_of_block):
                os.makedirs(os.path.join(self.root, f"host_{int(h):04d}"),
                            exist_ok=True)
        by_dir: dict[str, list[int]] = {}
        for seg in src_path:
            by_dir.setdefault(self._shard_dir(seg), []).append(seg)
        old_sizes = {d: (os.path.getsize(os.path.join(
            d, _shard_name(self._gen.get(d, 0)))) if os.path.exists(
            os.path.join(d, _shard_name(self._gen.get(d, 0)))) else 0)
            for d in old_dirs | set(by_dir)}
        mmaps: dict[str, Optional[np.memmap]] = {}
        new_size = 0
        cleanup: list[str] = []
        for d, segs in by_dir.items():
            new_gen = self._gen.get(d, 0) + 1
            new_path = os.path.join(d, _shard_name(new_gen))
            os.makedirs(d, exist_ok=True)   # source dir may have vanished
            with open(new_path, "wb") as f:
                # preserve source order so compaction stays a sequential
                # read of the live bytes per source shard
                for seg in sorted(segs,
                                  key=lambda s: (src_path[s],
                                                 segments[s][0])):
                    path = src_path[seg]
                    if path not in mmaps:
                        ok = os.path.exists(path) and os.path.getsize(path)
                        mmaps[path] = (np.memmap(path, np.uint8, mode="r")
                                       if ok else None)
                    mm = mmaps[path]
                    if mm is None:
                        # source shard unreachable (crash orphan / dead
                        # host): the segment's data is gone — drop it from
                        # the index. Keeping the old offset would resolve
                        # inside the NEW generation file after the bump
                        # below and read another segment's bytes.
                        segments[seg] = None
                        continue
                    off, n = segments[seg]
                    new_off = f.tell()
                    f.write(mm[off:off + n].tobytes())
                    segments[seg] = [new_off, n]
                f.flush()
                os.fsync(f.fileno())
            self._gen[d] = new_gen
            new_size += os.path.getsize(new_path)
            cleanup.append(d)
        mmaps.clear()
        manifest["segments"] = segments
        manifest["shard_gen"] = {os.path.relpath(d, self.root): g
                                 for d, g in self._gen.items()}
        self._write_manifest(manifest)
        keep = {os.path.join(d, _shard_name(self._gen[d]))
                for d in cleanup}
        for d in set(cleanup) | old_dirs:   # old gens (and crash orphans)
            if not os.path.isdir(d):        # vanished with its host
                continue
            for name in os.listdir(d):      # die last
                p = os.path.join(d, name)
                if _is_shard_name(name) and p not in keep:
                    os.unlink(p)
        reclaimed = int(sum(old_sizes.values()) - new_size)
        if self.recorder.enabled:
            self.recorder.event("compact", reclaimed=reclaimed,
                                rekeyed=rekey_homes is not None)
        return reclaimed

    def disk_nbytes(self) -> dict[str, int]:
        """On-disk footprint: shard bytes (the append log), the subset of
        those bytes the index still references (live), and the parity
        mirror."""
        shard_bytes = 0
        parity_bytes = 0
        for dirpath, _, files in os.walk(self.root):
            for name in files:
                p = os.path.join(dirpath, name)
                if _is_shard_name(name):
                    shard_bytes += os.path.getsize(p)
                elif name.startswith("parity_") and name.endswith(".npy"):
                    parity_bytes += os.path.getsize(p)
        live = 0
        if self.partition is not None and os.path.exists(self._manifest_path()):
            with open(self._manifest_path()) as f:
                for seg in json.load(f)["segments"]:
                    if seg is not None:
                        live += seg[1]
        return {"shard": int(shard_bytes), "live": int(live),
                "parity": int(parity_bytes)}

    # -- read path ----------------------------------------------------------

    def _read_masked(self, block_mask: Optional[np.ndarray]) -> PyTree:
        """Reassemble from disk; ``block_mask=None`` reads every block.

        Blocks whose shard is unreachable (or that were never indexed)
        come back zero — callers select by the mask they asked for."""
        assert self.partition is not None
        self.flush()
        with open(self._manifest_path()) as f:
            segments = json.load(f)["segments"]
        br = self.partition.block_rows
        mmaps: dict[str, Optional[np.memmap]] = {}

        def _payload(seg, dtype):
            if segments[seg] is None:
                return None
            path = self._shard_path(seg)
            if path not in mmaps:
                ok = os.path.exists(path) and os.path.getsize(path) > 0
                mmaps[path] = (np.memmap(path, np.uint8, mode="r")
                               if ok else None)
            mm = mmaps[path]
            if mm is None:
                return None
            off, n = segments[seg]
            return np.frombuffer(mm[off:off + n].tobytes(), dtype)

        out = []
        for li, leaf_meta in enumerate(self.partition.leaves):
            rows = max(leaf_meta.rows, 1)
            width = max(leaf_meta.row_width, 1)
            dtype = np.dtype(leaf_meta.dtype)
            arr = np.zeros((rows, width), dtype)
            for b in range(leaf_meta.n_blocks):
                gid = leaf_meta.offset + b
                if block_mask is not None and not block_mask[gid]:
                    continue
                if self.arena_layout is not None:
                    # arena segment keyed by arena-block id: word-packable
                    # dtypes store raw element bytes (view the payload
                    # directly as the leaf dtype — bit-exact), legacy
                    # dtypes the f32 image (value cast back). Trim the
                    # zero padding the ragged/sub-word tail carries.
                    from repro.core.blocks import word_packable
                    seg = int(self._leaf_first_seg[li]) + b
                    packable = word_packable(dtype)
                    blk = _payload(seg, dtype if packable else np.float32)
                    if blk is None:
                        continue
                    lo = b * br
                    n_rows = min(br, rows - lo) if leaf_meta.n_blocks > 1 \
                        else rows
                    blk = blk[:n_rows * width].reshape(-1, width)
                    arr[lo:lo + blk.shape[0]] = (blk if packable
                                                 else blk.astype(dtype))
                else:
                    blk = _payload(gid, dtype)
                    if blk is None:
                        continue
                    blk = blk.reshape(-1, width)
                    arr[b * br:b * br + blk.shape[0]] = blk
            out.append(arr.reshape(leaf_meta.shape))
        return jax.tree_util.tree_unflatten(self.partition.treedef, out)

    def read_all(self) -> PyTree:
        """Reassemble the full running checkpoint from disk (total-failure
        recovery)."""
        return self._read_masked(None)

    def read_blocks(self, block_mask) -> PyTree:
        """Partial DISK-tier read: only the masked blocks' byte ranges are
        touched — with the domain-keyed layout, a post-domain-loss recovery
        memmaps only the shards its DISK blocks live in, not the whole
        mirror. Off-mask blocks come back zero (callers select by the same
        mask)."""
        return self._read_masked(np.asarray(block_mask, bool))

    def read_surviving(self, failed_hosts) -> tuple[PyTree, np.ndarray]:
        """Host-local-deployment read: blocks whose shard sits on a failed
        host are unreadable. Returns (values, present_mask) — missing
        blocks are zero in ``values`` and False in the mask; the parity
        mirror (:meth:`read_parity`) reconstructs them offline."""
        assert self.partition is not None
        if self.host_of_block is None:
            present = np.ones((self.partition.total_blocks,), bool)
            return self.read_all(), present
        failed = np.asarray(failed_hosts, np.int32)
        present = ~np.isin(self.host_of_block, failed)
        return self._read_masked(present), present

    def saved_iters(self) -> np.ndarray:
        with open(self._manifest_path()) as f:
            return np.asarray(json.load(f)["saved_iter"], np.int32)
