"""Mean seconds from a background store write's enqueue until its
manifest is published, over the writes that completed in the window
(``store_lag_s`` of each step in ``TrainLoop.metrics``; the writes the
window's closing flush drains are on its last step). None where the
program records no lags or the cell has no store."""


def read(ctx):
    lags = [x for m in ctx["steps"] for x in m.get("store_lag_s", ())]
    return sum(lags) / len(lags) if lags else None
