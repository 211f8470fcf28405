"""The ``store_wait_s`` reader on hand-built windows: the mean of the
program's ``scar/save/store_wait`` span over the save steps that book it,
and no reading from a program that keeps no such span."""
import benchcase  # noqa: F401  (puts bench/ and src/ on the path)
import pytest

import harness


def _steps(waits):
    steps = [{"step": 1, "spans": {"scar/step": 0.5}, "bytes": {},
              "store_lag_s": []}]
    for i, w in enumerate(waits, start=2):
        spans = {"scar/save": 1.0, "scar/save/tiles_to_host": 0.1}
        if w is not None:
            spans["scar/save/store_wait"] = w
        steps.append({"step": i, "checkpointed": True, "spans": spans,
                      "bytes": {}, "store_lag_s": []})
    # a writer's span that lands on a step without a save is not a wait
    steps.append({"step": 9, "spans": {"scar/save/store_wait": 7.0},
                  "bytes": {}, "store_lag_s": []})
    return steps


@pytest.mark.parametrize("waits,want", [
    ([0.5, 1.5], 1.0),
    ([0.0, 0.25, 0.5], 0.25),
    ([None, 0.6], 0.6),
    ([None, None], None),
])
def test_store_wait_reader(waits, want):
    got = harness.metric_reader("store_wait_s").read({"steps": _steps(waits)})
    assert got == (None if want is None else pytest.approx(want))


def test_store_wait_reader_on_a_program_without_spans():
    bare = [{"step": 1, "loss": 2.0, "seconds": 0.3,
             "overhead_seconds": 0.1},
            {"step": 2, "loss": 2.0, "seconds": 0.3,
             "overhead_seconds": 3.0, "checkpointed": True}]
    assert harness.metric_reader("store_wait_s").read({"steps": bare}) \
        is None


@pytest.mark.parametrize("writes,want", [
    # (per step: shard write, parity write, a save step?)
    ([(0.5, 1.5, True), (None, None, False)], 2.0),
    # the writer's spans land on later steps, and the closing flush's on
    # the last step: all count, over the save steps
    ([(None, None, True), (0.5, None, False), (None, 1.5, True),
      (0.7, 1.3, False)], 2.0),
    ([(0.6, None, True), (None, None, True)], 0.3),
    ([(None, None, True), (None, None, False)], None),
    ([(0.6, 2.0, False)], None),
])
def test_store_writer_reader(writes, want):
    steps = []
    for i, (shard, parity, save) in enumerate(writes, start=1):
        spans = {"scar/step": 1.0}
        if shard is not None:
            spans["scar/store/write"] = shard
        if parity is not None:
            spans["scar/store/parity_write"] = parity
        steps.append({"step": i, "checkpointed": save, "spans": spans})
    got = harness.metric_reader("store_writer_s").read({"steps": steps})
    assert got == (None if want is None else pytest.approx(want))
