"""Seconds the store spent writing per save step over the window: the
program's ``scar/store/write`` (the shard log) and
``scar/store/parity_write`` (the parity mirror) spans summed over every
step's ``spans`` in ``TrainLoop.metrics``, wherever the writer's span
ended (the window's closing flush books on its last step), divided by
the save steps. Where the writes run on a background writer, this is
how busy the writer is per save period, which paces training once it
exceeds the period. None where the program keeps neither span."""

NAMES = ("scar/store/write", "scar/store/parity_write")


def read(ctx):
    steps = ctx["steps"]
    saves = sum(1 for m in steps if m.get("checkpointed"))
    spans = [m["spans"][n] for m in steps for n in NAMES
             if n in m.get("spans", {})]
    return sum(spans) / saves if saves and spans else None
