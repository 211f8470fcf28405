"""Mean seconds of one train step in the window, as the loop times it:
``TrainLoop.metrics[i]["seconds"]``, fenced by reading the loss."""


def read(ctx):
    steps = [m["seconds"] for m in ctx["steps"]]
    return sum(steps) / len(steps) if steps else None
