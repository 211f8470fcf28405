"""Mean seconds per step of backend compiles booked under the program's
``scar/maintain`` span (the fabric's maintenance call: a sweep program
lowered again after the cluster view moved books here), from each step's
``compiles`` ({span: [count, seconds, cache hits]}) in
``TrainLoop.metrics``. None where the program books no compiles."""


def _under(name):
    return name == "scar/maintain" or name.startswith("scar/maintain/")


def read(ctx):
    d = [sum(v[1] for k, v in m["compiles"].items() if _under(k))
         for m in ctx["steps"] if "compiles" in m]
    return sum(d) / len(d) if d else None
