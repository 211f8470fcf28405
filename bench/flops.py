"""The yardstick's arithmetic: chip peaks, model FLOPs and sweep bytes.

Model FLOPs per token come from the configuration's reference module
(``configs/<reference>.py``: ``flops_per_token``), so a configuration
brings its own count. The bytes of one maintenance sweep are the
algorithm's: what the sweep must read and write, from the sizes of the
buffers it reads and writes, not what an implementation stages between.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")
          ) -> dict:
    """The published peaks of one chip of ``device_kind``. A device the
    table does not hold is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add them with their source")
    return table[device_kind]


def sweep_bytes(arena_words: int, parity_words: int, scored: bool) -> int:
    """HBM bytes one arena maintenance sweep needs: read the live arena,
    write the replica copy and the parity, and on a scoring sweep (a save
    step's) also read the running-checkpoint arena. Words are 4 bytes."""
    reads = arena_words * (2 if scored else 1)
    return 4 * (reads + arena_words + parity_words)


def mfu(flops_per_token: float, tokens: int, seconds: float, chips: int,
        peak_flops: float) -> float:
    """Model FLOP/s over the chips' bf16 peak, as a percentage."""
    return 100.0 * flops_per_token * tokens / (seconds * chips * peak_flops)
