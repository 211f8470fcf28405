"""Mean seconds per save step of backend compiles booked under the
program's ``scar/save`` span and its ``scar/save/...`` children (each
compile is booked to the innermost open span), from each save step's
``compiles`` ({span: [count, seconds, cache hits]}) in
``TrainLoop.metrics``. None where the program books no compiles."""


def _under(name):
    return name == "scar/save" or name.startswith("scar/save/")


def read(ctx):
    d = [sum(v[1] for k, v in m["compiles"].items() if _under(k))
         for m in ctx["steps"]
         if m.get("checkpointed") and "compiles" in m]
    return sum(d) / len(d) if d else None
