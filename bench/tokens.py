"""Traffic: the seeded token stream and the failure schedule of a cell.

A traffic file (``traffic/<name>.json``) holds only parameters; this one
generator reads all of them. The token stream is a copy of the program's
synthetic ``ShardedLMDataset`` (uniform ids from ``numpy``'s default
generator, ``batch x (seq + 1)`` per step, shifted by one for labels),
kept here so that no change to the program moves the traffic.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


class TokenStream:
    """Batches ``{"tokens", "labels"}`` of ``(batch, seq)`` int32 ids in
    ``[0, vocab)``, the same sequence for the same seed. Every row of
    every batch is drawn afresh, so no two steps see the same rows."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.batch, self.seq = vocab, batch, seq
        self._rng = np.random.default_rng(seed)

    def next_numpy(self) -> dict:
        t = self._rng.integers(0, self.vocab, (self.batch, self.seq + 1),
                               dtype=np.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def first_batches(vocab: int, batch: int, seq: int, seed: int, n: int):
    """The stream's first ``n`` batches as device arrays (the reference's
    input: the same rows the program trained its first steps on)."""
    import jax.numpy as jnp
    s = TokenStream(vocab, batch, seq, seed)
    return [{k: jnp.asarray(v) for k, v in s.next_numpy().items()}
            for _ in range(n)]


def loss_steps(traffic: dict, seed: int, first: int, count: int) -> list:
    """Host-loss events of global steps ``first .. first + count - 1``:
    ``[(step, host)]``. Losses fall on the steps where
    ``step % every == offset``; the hosts rotate through an order drawn
    from the seed, each host once per round."""
    spec = traffic.get("host_loss")
    if not spec:
        return []
    hosts = int(spec["hosts"])
    order = np.random.default_rng([seed, 7]).permutation(hosts)
    out = []
    for step in range(first, first + count):
        if step % int(spec["every"]) == int(spec["offset"]):
            k = (step - int(spec["offset"])) // int(spec["every"])
            out.append((step, int(order[k % hosts])))
    return out
