"""The control: the plain reference computed one precision step down
(bfloat16 arithmetic) in the program's place must come out as not
correct under the cell's limits, while the program comes out correct."""

import tiny


def _fails(readings: dict, limits: dict) -> bool:
    return any(readings[k] > limits[k] for k in limits)


def test_train_control_is_not_correct():
    run = tiny.run("stream", control=True)
    assert run.correct
    assert _fails(run.counters["control"]["bfloat16"], run.cell.limits)


def test_serve_control_is_not_correct():
    run = tiny.run("poisson", control=True)
    assert run.correct
    assert _fails(run.counters["control"]["bfloat16"], run.cell.limits)
