"""Mean wall seconds of one save step's ``FTController.maybe_checkpoint``
call, from the benchmark's ``save`` span: the in-memory save that
``save_s`` times, plus what the controller does after it before training
may go on (the checkpoint tiles copied to the host for the store, the
parity mirrored to disk)."""


def read(ctx):
    spans = sorted((t0, t1) for n, t0, t1 in ctx["probe"].spans
                   if n == "save" and t0 >= ctx["w0"] and t1 <= ctx["w1"])
    steps = ctx["steps"]
    if len(spans) != len(steps):
        return None
    d = [t1 - t0 for (t0, t1), m in zip(spans, steps)
         if m.get("checkpointed")]
    return sum(d) / len(d) if d else None
