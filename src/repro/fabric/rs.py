"""Reed-Solomon RS(k, m) erasure codec + CodeNet-style integrity tier.

Generalizes :class:`~repro.fabric.parity.ParityCodec` from one XOR
parity block per group to ``m`` GF(256) parity rows over the *same*
striping, frames, and ARENA_TILE-aligned :class:`FrameLayout` — so the
arena sweep's snapshot lands bit-exactly in coded frames and every
recovery path (PyTree pack or arena gather) is shared with the XOR tier.

Three capabilities the XOR tier lacks:

- **Multi-erasure recovery**: any ≤ m simultaneous member losses per
  group decode bit-exactly (Cauchy coefficients: every square submatrix
  is nonsingular, so any erasure pattern against any surviving parity
  rows is solvable). A simultaneous host + replica-domain loss that
  previously fell back to RUNNING_CKPT (paying checkpoint staleness in
  the ledger) recovers at ‖δ′‖² ≈ 0.
- **Silent-error detection**: recomputing the parity rows over the
  replica arena and XOR-ing against the stored rows yields per-group
  syndromes that are all-zero iff the coded redundancy state is
  uncorrupted — a failure class (soft errors) the fabric otherwise
  cannot see.
- **Localization + correction** (m ≥ 2): parity row 0 is normalized to
  all-ones, so for a single corrupted member the row-0 syndrome *is*
  the error pattern and row r is that pattern scaled by the member's
  coefficient — matching the scaling fingerprints identifies the
  member, and XOR-ing the pattern back out corrects it in place. A
  single nonzero row with the rest zero fingerprints a corrupted
  stored parity row instead.

Row 0's all-ones normalization also makes RS(k, 1) encode bit-identical
to the XOR tier's parity blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import BlockPartition
from repro.fabric.parity import ParityCodec, pack_frames
from repro.fabric.placement import ClusterView, rs_parity_homes
from repro.kernels.gf256_mac.ops import rs_decode, rs_encode
from repro.kernels.gf256_mac.tables import (gf_scale_words_np,
                                            rs_coefficients,
                                            rs_decode_weights)


class RSCodec(ParityCodec):
    """RS(k, m) over GF(256) on the shared grouped-frames layout.

    ``group_size`` is k (data members per group, subject to the same
    topology clamp and tail-fold as the XOR codec); ``n_parity`` is m.
    The arena sweep only emits XOR parity, so this codec re-encodes
    its rows from the snapshot arena each maintenance
    (``needs_arena_encode``) — m extra MAC passes over the frame bytes.
    """

    needs_arena_encode = True
    supports_integrity = True

    def __init__(self, partition: BlockPartition, view: ClusterView,
                 group_size: int = 4, n_parity: int = 2,
                 use_pallas: bool | None = None):
        if n_parity < 1:
            raise ValueError("rs n_parity must be >= 1")
        self.n_parity = int(n_parity)
        self._arena_encode_fn = None
        self._arena_encode_layout = None
        super().__init__(partition, view, group_size, use_pallas)

    def _build(self) -> None:
        self._stripe()
        self.parity_homes = rs_parity_homes(self.members, self.view,
                                            self.n_parity)
        width = self.members.shape[1]
        self.coeff = rs_coefficients(width, self.n_parity)  # (m, width)
        # padding members carry coefficient 0 (dropped from the fold)
        self._coeff_rows = np.where(self.valid[None],
                                    self.coeff[:, None, :],
                                    0).astype(np.int32)  # (m, n_groups, g)
        self._build_encode()

    def _build_encode(self) -> None:
        gather = jnp.asarray(self._gather_ids)
        coeff_rows = jnp.asarray(self._coeff_rows)

        def _encode(values):
            frames = pack_frames(values, self.partition, self.layout)
            return rs_encode(frames[gather], coeff_rows,
                             use_pallas=self.use_pallas)
        self._encode_fn = jax.jit(_encode)
        self._arena_encode_fn = None
        self._arena_encode_layout = None

    # -- arena encode / integrity -------------------------------------------

    def _arena_encode(self, arena: jnp.ndarray, arena_layout) -> jnp.ndarray:
        """All parity rows recomputed from a snapshot arena:
        (n_groups, m, E) int32."""
        gather_idx = self._ensure_arena_gather(arena_layout)
        if self._arena_encode_fn is None \
                or self._arena_encode_layout is not arena_layout:
            from repro.core.arena import frames_from_arena
            gi = gather_idx  # numpy: frames_from_arena masks host-side
            gids = jnp.asarray(self._gather_ids)
            coeff_rows = jnp.asarray(self._coeff_rows)

            def _enc(buf):
                frames = frames_from_arena(buf, gi)
                return rs_encode(frames[gids], coeff_rows,
                                 use_pallas=self.use_pallas)
            self._arena_encode_fn = jax.jit(_enc)
            self._arena_encode_layout = arena_layout
        return self._arena_encode_fn(arena)

    def encode_from_arena(self, step: int, arena: jnp.ndarray,
                          arena_layout) -> None:
        """Encode from the maintenance sweep's snapshot arena — the same
        buffer the replica tier stores, so ``refreshed_step ==
        encoded_step`` holds and the arena recovery route stays open."""
        self.parity = self._arena_encode(arena, arena_layout)
        self.encoded_step = int(step)

    def syndromes_from_arena(self, arena: jnp.ndarray,
                             arena_layout) -> jnp.ndarray:
        """(n_groups, m, E) syndromes of the coded redundancy state: the
        parity recomputed from the replica arena XOR the stored parity.
        All-zero unless a silent error corrupted the arena snapshot or a
        stored parity row since encode."""
        assert self.parity is not None, "no parity encoded yet"
        return self._arena_encode(arena, arena_layout) ^ self.parity

    def localize_corruption(self, syndromes) -> list[dict]:
        """Turn nonzero syndromes into per-group corruption reports.

        Each report carries ``kind`` ("member" or "parity"), the guilty
        ``block``/``member`` slot or parity ``row`` when localization
        succeeds, ``localized``, and the raw error pattern ``delta``
        (the row-0 syndrome) that :meth:`correct_in_arena` XORs back
        out. m = 1 degenerates to detect-only (no fingerprint to match).
        """
        synd = np.asarray(syndromes)
        reports: list[dict] = []
        for j in np.nonzero(synd.any(axis=(1, 2)))[0]:
            s = synd[j]                       # (m, E)
            rows_nz = np.nonzero(s.any(axis=1))[0]
            if self.n_parity >= 2 and rows_nz.size == 1:
                # a member error perturbs every row (all coefficients are
                # nonzero), so a single nonzero row is the stored parity
                # row itself gone bad
                r = int(rows_nz[0])
                reports.append(dict(group=int(j), kind="parity", row=r,
                                    member=-1, block=-1, localized=True,
                                    delta=s[r]))
                continue
            delta = s[0]  # row 0 is all-ones: the raw error pattern
            cand = []
            if self.n_parity >= 2:
                for slot in np.nonzero(self.valid[j])[0]:
                    if all(np.array_equal(
                            gf_scale_words_np(delta,
                                              int(self.coeff[r, slot])),
                            s[r]) for r in range(1, self.n_parity)):
                        cand.append(int(slot))
            if len(cand) == 1:
                slot = cand[0]
                reports.append(dict(group=int(j), kind="member", row=-1,
                                    member=slot,
                                    block=int(self.members[j, slot]),
                                    localized=True, delta=delta))
            else:
                # zero or multiple fingerprints match: multi-symbol or
                # multi-member corruption — detected, not localized
                reports.append(dict(group=int(j), kind="member", row=-1,
                                    member=-1, block=-1, localized=False,
                                    delta=delta))
        return reports

    def correct_in_arena(self, arena: jnp.ndarray,
                         report: dict) -> jnp.ndarray:
        """Apply one localized correction: XOR the error pattern out of
        the replica arena (member corruption; returns the corrected
        arena) or out of the stored parity row (parity corruption;
        returns the arena unchanged)."""
        delta = np.asarray(report["delta"])
        if report["kind"] == "parity":
            assert self.parity is not None
            j, r = report["group"], report["row"]
            cur = np.asarray(self.parity[j, r])
            self.parity = self.parity.at[j, r].set(
                jnp.asarray(cur ^ delta))
            return arena
        assert report["localized"] and report["block"] >= 0
        gather = np.asarray(self._arena_gather)[report["block"]]
        cols = np.nonzero(delta)[0]
        cols = cols[gather[cols] >= 0]
        if cols.size == 0:
            return arena
        idx = jnp.asarray(gather[cols])
        bits = np.asarray(arena[idx]).view(np.int32) ^ delta[cols]
        return arena.at[idx].set(jnp.asarray(bits.view(np.float32)))

    # -- recovery ------------------------------------------------------------

    def _reconstruct_frames(self, frames: jnp.ndarray,
                            recover_mask: np.ndarray,
                            available_mask: np.ndarray) -> jnp.ndarray:
        assert self.parity is not None
        recover = np.asarray(recover_mask, bool)
        available = np.asarray(available_mask, bool)
        width = self.members.shape[1]
        m = self.n_parity
        member_unavail = self.valid & ~available[self._gather_ids]
        member_recover = self.valid & recover[self._gather_ids]
        # host-solved decode weights per targeted group: one (width + m)
        # coefficient row per erased ordinal, folding survivors and
        # parity rows in a single MAC
        weights = np.zeros((self.n_groups, m, width + m), np.int32)
        ordinal_of = np.full((self.n_groups, width), -1, np.int32)
        for j in np.nonzero(member_recover.any(axis=1))[0]:
            erased = np.nonzero(member_unavail[j])[0]
            if erased.size == 0 or erased.size > m:
                continue  # planner never routes such a group here
            survivors = np.nonzero(self.valid[j] & ~member_unavail[j])[0]
            # prefer parity rows homed on currently-alive devices; the
            # planner already guaranteed at least ``erased.size`` of them
            rows_alive = self.view.alive[self.parity_homes[j]]
            rows = np.concatenate([np.nonzero(rows_alive)[0],
                                   np.nonzero(~rows_alive)[0]])
            weights[j, :erased.size] = rs_decode_weights(
                self.coeff, erased, survivors, rows)
            for q, slot in enumerate(erased):
                ordinal_of[j, slot] = q
        grouped = frames[jnp.asarray(self._gather_ids)]
        ext = jnp.concatenate([grouped, self.parity], axis=1)
        out = jnp.zeros_like(frames)
        for q in range(m):  # one MAC dispatch per erased ordinal
            wq = weights[:, q, :]
            if not wq.any():
                continue
            rec = rs_decode(ext, jnp.asarray(wq),
                            use_pallas=self.use_pallas)
            gids, slots = np.nonzero(member_recover
                                     & (ordinal_of == q))
            if gids.size:
                ids = self.members[gids, slots]
                out = out.at[jnp.asarray(ids)].set(
                    rec[jnp.asarray(gids)])
        return out
