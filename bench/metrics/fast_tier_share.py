"""Share of the blocks lost in the window's host losses that came back
from the fast tiers (PEER_REPLICA or PARITY), in percent of all lost
blocks, from each failure's ``tier_counts`` in ``TrainLoop.metrics``."""

FAST = ("PEER_REPLICA", "PARITY")


def read(ctx):
    fast = lost = 0
    for m in ctx["steps"]:
        for f in m.get("failures", []):
            counts = f.get("tier_counts") or {}
            fast += sum(int(counts.get(t, 0)) for t in FAST)
            lost += int(f.get("lost_blocks", 0))
    if not lost:
        return None
    return 100.0 * fast / lost
