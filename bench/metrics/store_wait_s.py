"""Mean seconds per save step that the save waited for the store's
background writer to finish the previous save's writes before queueing
its own (one save in flight): the program's ``scar/save/store_wait`` span
from each save step's ``spans`` in ``TrainLoop.metrics``. None where the
program keeps no such span."""


def read(ctx):
    d = [m["spans"]["scar/save/store_wait"] for m in ctx["steps"]
         if m.get("checkpointed")
         and "scar/save/store_wait" in m.get("spans", {})]
    return sum(d) / len(d) if d else None
