"""Mean seconds per save step that the controller spent copying the saved
checkpoint tiles to the host for the store: the program's own
``scar/save/tiles_to_host`` span (``tiles_for_blocks``, the gather, and
``np.asarray``) from each save step's ``spans`` in ``TrainLoop.metrics``.
None where the program keeps no such span."""


def read(ctx):
    d = [m["spans"]["scar/save/tiles_to_host"] for m in ctx["steps"]
         if m.get("checkpointed")
         and "scar/save/tiles_to_host" in m.get("spans", {})]
    return sum(d) / len(d) if d else None
