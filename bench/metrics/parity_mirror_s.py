"""Mean seconds per save step spent mirroring the parity tier to disk: the
program's ``scar/save/parity_to_host`` span (the parity copied to the
host) plus its ``scar/store/parity_write`` span (the files written), from
each save step's ``spans`` in ``TrainLoop.metrics``. None where the
program keeps neither span."""

NAMES = ("scar/save/parity_to_host", "scar/store/parity_write")


def read(ctx):
    d = [sum(m["spans"].get(n, 0.0) for n in NAMES)
         for m in ctx["steps"] if m.get("checkpointed")
         and any(n in m.get("spans", {}) for n in NAMES)]
    return sum(d) / len(d) if d else None
