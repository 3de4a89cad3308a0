"""The reduction from a trace to device numbers, on hand-made events and
on a small trace recorded on the chip."""

import gzip
from pathlib import Path

import pytest

from bench.trace import Event, Trace, find_xspace, op_name, read_xspace

HERE = Path(__file__).resolve().parent


def _trace():
    ops = {"/device:TPU:0": [Event("bp_update_tokens.1", 100, 300),
                             Event("fusion.7", 250, 400),      # overlaps
                             Event("scatter_add_rows.3", 600, 700),
                             Event("all-reduce.2", 800, 900)],
           "/device:TPU:1": [Event("bp_update_tokens.1", 100, 500)]}
    modules = {"/device:TPU:0": [Event("jit_step(1)", 100, 900)],
               "/device:TPU:1": [Event("jit_step(1)", 100, 500)]}
    host = [Event("window", 0, 1000), Event("dispatch", 0, 90),
            Event("feed", 420, 590), Event("wait", 700, 1000),
            Event("unrelated", 0, 1000)]
    return Trace.from_events(ops, modules, host)


def test_busy_is_the_union_of_op_intervals_averaged_over_chips():
    tr = _trace()
    # chip 0: [100, 400] + [600, 700] + [800, 900] = 500 ns; chip 1: 400
    assert tr.busy_s == pytest.approx(450e-9)
    assert tr.window_s == pytest.approx(1000e-9)


def test_kernel_and_module_seconds():
    tr = _trace()
    calls, s = tr.kernel_seconds("bp_update_tokens")
    assert calls == 1 and s == pytest.approx(300e-9)    # (200 + 400) / 2
    assert tr.kernel_seconds("scatter_add_rows") == (0.5, pytest.approx(
        50e-9))
    assert tr.kernel_seconds("bp_update") == (0, 0)     # whole names only
    assert tr.module_seconds("jit_step") == (1, pytest.approx(600e-9))
    assert tr.op_seconds(lambda n: "all-reduce" in n)[1] == pytest.approx(
        50e-9)


def test_idle_gaps_are_named_by_the_covering_host_span():
    gaps = _trace().idle_gaps()
    # chip 0 idle: [0, 100] dispatch, [400, 600] feed, [700, 800] and
    # [900, 1000] wait
    assert gaps == {"dispatch": pytest.approx(100e-9),
                    "feed": pytest.approx(200e-9),
                    "wait": pytest.approx(200e-9)}
    bd = _trace().breakdown()
    assert bd["device_ops"][0][0] == "bp_update_tokens.1"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_a_gap_under_nested_spans_goes_to_the_innermost():
    ops = {"/device:TPU:0": [Event("a", 0, 100), Event("b", 400, 1000)]}
    host = [Event("window", 0, 1000), Event("slab_step", 50, 500),
            Event("harvest", 100, 450)]
    gaps = Trace.from_events(ops, {}, host).idle_gaps()
    assert gaps == {"harvest": pytest.approx(300e-9)}


def test_events_outside_the_window_are_clipped():
    ops = {"/device:TPU:0": [Event("a", 0, 50), Event("b", 90, 200)]}
    tr = Trace.from_events(ops, {}, [Event("window", 100, 150)])
    assert tr.busy_s == pytest.approx(50e-9)


def test_a_trace_without_window_is_refused():
    with pytest.raises(ValueError):
        Trace.from_events({}, {}, [])


def test_op_names_are_hlo_instruction_names():
    assert op_name("%scatter_add_rows.7 = f32[8,128]{1,0} custom-call(%a)"
                   ) == "scatter_add_rows.7"
    assert op_name("while.28") == "while.28"


def test_recorded_chip_trace(tmp_path):
    """Two NYTimes training steps traced on one TPU v5e (the training
    cell's window, 2 s)."""
    src = HERE / "data" / "train_nytimes_window.xplane.pb.gz"
    dst = tmp_path / "plugins" / "profile" / "t" / "chip.xplane.pb"
    dst.parent.mkdir(parents=True)
    dst.write_bytes(gzip.decompress(src.read_bytes()))
    tr = read_xspace(find_xspace(str(tmp_path)))
    assert tr.chips == 1
    assert 0 < tr.busy_s <= tr.window_s
    assert tr.kernel_seconds("bp_update_tokens")[0] == 2
    # one packed scatter per selective iteration: 11 per minibatch
    assert tr.kernel_seconds("scatter_add_rows")[0] == 22
    assert tr.module_seconds("jit_step")[0] == 2
    assert sum(v for _, v in tr.breakdown()["idle_gaps"]) == pytest.approx(
        tr.window_s - tr.busy_s, rel=1e-6)
