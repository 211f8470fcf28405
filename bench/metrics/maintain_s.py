"""Mean seconds per step of the loop's fault-tolerance overhead spent
outside the save call: ``TrainLoop.metrics[i]["overhead_seconds"]``
(maintain, save and, in sync mode, the loop's fence on the sweep) less
the benchmark's ``save`` span of the same step. In sync mode that is the
sweep's dispatch and whatever of it the save did not already wait for;
with an asynchronous sweep, the part of it left exposed."""


def read(ctx):
    spans = sorted((t0, t1) for n, t0, t1 in ctx["probe"].spans
                   if n == "save" and t0 >= ctx["w0"] and t1 <= ctx["w1"])
    steps = ctx["steps"]
    if len(spans) != len(steps):
        return None
    d = [m["overhead_seconds"] - (t1 - t0)
         for (t0, t1), m in zip(spans, steps) if "overhead_seconds" in m]
    return sum(d) / len(d) if d else None
