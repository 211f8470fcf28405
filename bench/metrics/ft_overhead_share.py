"""Share of the window's wall time the loop spent on fault tolerance:
the sum of ``TrainLoop.metrics[i]["overhead_seconds"]`` (maintain + save,
fenced on the sweep in sync mode) over the window seconds, in percent."""


def read(ctx):
    over = [m["overhead_seconds"] for m in ctx["steps"]
            if "overhead_seconds" in m]
    if not over:
        return None
    return 100.0 * sum(over) / ctx["window_s"]
