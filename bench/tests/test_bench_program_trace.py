"""The readers of the program's own tracing (`bench/program_trace.py`
and the eight metrics on it) on hand-made trace events and ring
records; the join of a step compiled on the CPU to a trace of its
instructions; the serving readers after a traced tiny run."""

import pytest

from bench import harness, program_trace as pt
from bench.tests import tiny
from bench.trace import Event, Trace, find_xspace, read_xspace
from repro import obs

OFF = 7_000_000            # the trace's clock minus the ring's
SERVE_METRICS = ("queue_wait_ms.serve", "harvest_lag_ms.serve",
                 "harvest_block_ms.serve", "idle_in_harvest_ms.serve")
TRAIN_METRICS = ("dense_phase_ms.train", "select_ms.train",
                 "selective_sweep_ms.train", "scatter_ms.train")


@pytest.fixture(autouse=True)
def empty_rings():
    obs.clear()
    yield
    obs.clear()


def _read(name, run):
    return harness.load_reader(name)(run)


def _span(name, s, e):
    obs._spans.append((name, s, e))


def _run(traffic, ops, modules, ring_steps, traced_steps, name="slab_step"):
    """A run whose benchmark spans ``ring_steps`` are on the ring's clock
    and, from ``traced_steps`` on, on the trace's (window 1,000 to
    100,000 on the ring's clock)."""
    run = harness.Run(tiny.cell(traffic), 0, 1.0)
    run.spans.records = [(name, s, e) for s, e in ring_steps]
    host = [Event("window", 1_000 + OFF, 100_000 + OFF)]
    host += [Event(name, s + OFF, e + OFF)
             for s, e in ring_steps[traced_steps:]]
    run.trace = Trace.from_events(ops, modules, host)
    return run


# ---------------------------------------------------------------- clock


def test_the_offset_pairs_the_traced_spans_with_their_run_in_the_ring():
    # set-up spans before the trace, then the traced ones; each traced
    # span is 300 ns shorter on the trace (the annotation sits inside)
    ring = [(100, 600), (700, 800), (900, 990), (2_000, 12_000),
            (20_000, 21_000), (30_000, 45_000), (50_000, 50_500)]
    run = _run("poisson", {}, {}, ring, 3)
    run.trace.host = [Event(e.name, e.start + 100, e.end - 200)
                      if e.name == "slab_step" else e
                      for e in run.trace.host]
    assert pt.clock_offset(run) == OFF + 100


def test_no_offset_without_spans_on_both_clocks():
    run = _run("poisson", {}, {}, [(2_000, 3_000)], 1)
    assert pt.clock_offset(run) is None
    run.trace = None
    assert pt.clock_offset(run) is None


# -------------------------------------------------------------- serving


def _serve_run():
    chip = [Event("k", 1_000 + OFF, 32_000 + OFF),
            Event("k", 46_000 + OFF, 60_000 + OFF),
            Event("k", 70_000 + OFF, 100_000 + OFF)]
    run = _run("poisson", {"/device:TPU:0": chip}, {},
               [(500, 900), (2_000, 12_000), (20_000, 36_000),
                (40_000, 50_000)], 1)
    for name, s, e in [("slab.dispatch", 500, 600),     # before the window
                       ("slab.dispatch", 3_000, 3_100),
                       ("slab.dispatch", 20_000, 20_100),
                       ("slab.harvest.block", 30_000, 34_000),
                       ("slab.harvest.fetch", 34_000, 34_500),
                       ("slab.harvest.retire", 34_500, 35_000),
                       ("slab.harvest.block", 45_000, 49_000)]:
        _span(name, s, e)
    for key, stamps in {
            -1: (100, 500, 600, 800),                   # done before it
            1: (1_000, 3_000, 20_000, 35_000),
            2: (2_000, 20_000, 40_000, 50_000),
            3: (30_000, 40_000, 90_000, 200_000)}.items():  # done after
        for kind, t in zip(("submit", "refill", "retire_dispatch", "done"),
                           stamps):
            obs.stamp(kind, key, t)
    return run


def test_queue_wait_and_harvest_lag_are_medians_over_the_window():
    run = _serve_run()
    # requests 1 and 2: refill - submit 2,000 and 18,000 ns
    assert _read("queue_wait_ms.serve", run) == pytest.approx(0.010)
    # done - retire_dispatch 15,000 and 10,000 ns
    assert _read("harvest_lag_ms.serve", run) == pytest.approx(0.0125)


def test_harvest_block_per_dispatch_inside_the_window():
    # two blocks of 4,000 ns over the window's two dispatches
    assert _read("harvest_block_ms.serve", _serve_run()) == pytest.approx(
        0.004)


def test_idle_time_is_charged_to_the_harvest_that_holds_it():
    # idle [32,000, 46,000] and [60,000, 70,000]; the harvest holds
    # [32,000, 35,000] and [45,000, 46,000] of it
    assert _read("idle_in_harvest_ms.serve", _serve_run()) == \
        pytest.approx(0.002)


def test_serving_readers_report_nothing_without_program_records(
        monkeypatch):
    run = _serve_run()
    obs.clear()
    assert [_read(m, run) for m in SERVE_METRICS] == [None] * 4
    monkeypatch.setattr(pt, "_obs", lambda: None)      # no repro.obs
    assert [_read(m, _serve_run()) for m in SERVE_METRICS] == [None] * 4


# ------------------------------------------------------------- training

HLO = """HloModule jit_step, entry_computation_layout={()->()}

%f3 (q.0: f32[8]) -> f32[8] {
  %q.0 = f32[8]{0} parameter(0)
  %mul.5 = f32[8]{0} multiply(f32[8]{0} %q.0, f32[8]{0} %q.0), metadata={op_name="jit(step)/while/body/pobp.selective_sweep/mul"}
  ROOT %copy.6 = f32[8]{0} copy(f32[8]{0} %mul.5)
}

%body (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %sort.1 = f32[8]{0} sort(f32[8]{0} %p.1), dimensions={0}, metadata={op_name="jit(step)/while/body/pobp.select/sort"}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %sort.1), kind=kLoop, calls=%f3
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %fusion.3), kind=kLoop, calls=%f4, metadata={op_name="jit(step)/while/body/pobp.power_sync/psum"}
  ROOT %scatter_add_rows.1 = f32[8]{0} custom-call(f32[8]{0} %fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/pobp.scatter/jit(scatter_add_rows)/pallas_call"}
}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/pobp.init/mul"}
  %bp_update_tokens.1 = (f32[8]{0}, f32[8]{0}) custom-call(f32[8]{0} %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pobp.dense_sweep/jit(bp_update_tokens)/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(%bp_update_tokens.1), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/pobp.dense_sync/add"}
  %while.28 = f32[8]{0} while(f32[8]{0} %fusion.2), condition=%cond, body=%body, metadata={op_name="jit(step)/while"}
  %fusion.9 = f32[8]{0} fusion(f32[8]{0} %while.28), kind=kLoop, calls=%f9, metadata={op_name="jit(step)/pobp.accumulate/add"}
  ROOT %copy.1 = f32[8]{0} copy(f32[8]{0} %fusion.9)
}
"""
# one call, ns from its start; the loop's event encloses its body's;
# fusion.3 carries no metadata and takes its fused multiply's scope
CALL = [("fusion.1", 0, 10), ("bp_update_tokens.1", 10, 110),
        ("fusion.2", 110, 130), ("while.28", 130, 930),
        ("sort.1", 130, 230), ("fusion.3", 230, 630),
        ("scatter_add_rows.1", 630, 830), ("fusion.4", 830, 930),
        ("fusion.9", 930, 980), ("copy.1", 980, 1_000)]


def _train_trace(extra=()):
    ops, mods = [], []
    for c in range(2):
        t = 1_000 + OFF + 1_000 * c
        mods.append(Event("jit_step(42)", t, t + 1_000))
        ops += [Event(n, t + s, t + e) for n, s, e in CALL]
    ops += list(extra)
    return ({"/device:TPU:0": ops}, {"/device:TPU:0": mods})


def _train_run(text=HLO, extra=()):
    ops, mods = _train_trace(extra)
    run = _run("stream", ops, mods, [(2_000, 2_100)], 0, name="dispatch")
    run.counters["step_split"] = pt.split_ops(run.trace, text,
                                              pt.STEP_SCOPES)
    return run


def test_phase_readers_charge_leaf_ops_to_their_scope():
    run = _train_run()
    ms = {m: _read(m, run) for m in TRAIN_METRICS}
    assert ms == {"dense_phase_ms.train": pytest.approx(130e-6),
                  "select_ms.train": pytest.approx(100e-6),
                  "selective_sweep_ms.train": pytest.approx(400e-6),
                  "scatter_ms.train": pytest.approx(200e-6)}


def test_a_loop_is_not_counted_beside_its_body():
    run = _train_run()
    split = pt.step_split(run)
    assert split[None] == pytest.approx(20e-6)          # copy.1 alone
    # the leaves add up to the step's device time per call
    assert sum(split.values()) == pytest.approx(
        _read("train_step_ms", run))


@pytest.mark.parametrize("text, extra", [
    (HLO.replace("HloModule jit_step", "HloModule jit_other"), ()),
    (HLO, [Event("fusion.77", 1_500 + OFF, 1_510 + OFF)]),  # not in text
    (HLO.replace("pobp.", "other."), ()),                # no scoped op
])
def test_a_module_that_is_not_the_traced_one_reads_nothing(text, extra):
    run = _train_run(text, extra)
    assert [_read(m, run) for m in TRAIN_METRICS] == [None] * 4


def test_ops_outside_the_step_are_left_out():
    run = _train_run(extra=[Event("fusion.77", 5_000 + OFF, 5_010 + OFF)])
    assert _read("select_ms.train", run) == pytest.approx(100e-6)


def test_the_join_on_a_step_compiled_on_the_cpu():
    """The cell's step lowered and compiled from its configuration, its
    instructions traced one after another: each phase reads the time of
    its own instructions."""
    run = harness.Run(tiny.cell("stream"), 0, 1.0)
    text = pt._compiled_text(pt._lower_step(run), pt.STEP_SCOPES)
    ops = obs.hlo_ops(text)
    scopes = obs.op_scopes(text, pt.STEP_SCOPES)
    leaves = [n for n, (op, _) in ops.items()
              if op in ("fusion", "custom-call", "sort") and scopes[n]]
    t0 = 1_000 + OFF
    events = [Event(n, t0 + 10 * i, t0 + 10 * i + 10)
              for i, n in enumerate(leaves)]
    mods = [Event("jit_step(1)", t0, t0 + 10 * len(leaves))]
    run.trace = Trace.from_events({"/device:TPU:0": events},
                                  {"/device:TPU:0": mods},
                                  [Event("window", 0, 10**12)])
    split = pt.step_split(run)
    for scope in ("pobp.init", "pobp.dense_sweep", "pobp.select",
                  "pobp.selective_sweep", "pobp.scatter"):
        n = sum(1 for x in leaves if scopes[x] == scope)
        assert n and split[scope] == pytest.approx(10e-6 * n), scope
    assert sum(split.values()) == pytest.approx(
        _read("train_step_ms", run))
    # the same trace under another module's name reads nothing
    run.counters.pop("step_split")
    run.trace.modules = {"/device:TPU:0": [Event("jit_other(1)", t0,
                                                 mods[0].end)]}
    assert pt.step_split(run) is None


def test_a_cache_entry_without_scopes_is_compiled_anew():
    import jax
    lowered = []

    def lower():
        lowered.append(1)
        return jax.jit(lambda x: x + 1).lower(jax.ShapeDtypeStruct(
            (4,), "float32"))

    was = jax.config.jax_enable_compilation_cache
    text = pt._compiled_text(lower, ("pobp.none",))
    assert text.startswith("HloModule") and len(lowered) == 2
    assert jax.config.jax_enable_compilation_cache == was
    assert pt._compiled_text(lower, ("HloModule",)) and len(lowered) == 3


# ------------------------------------------------- a traced tiny serving


def test_serving_readers_after_a_traced_tiny_run(tmp_path):
    run = harness.Run(tiny.cell("poisson"), 5, 1.0,
                      device={"platform": "cpu"},
                      peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    run.compiles = harness.CompileCounter()
    harness.runner_for("serve")(run, True, str(tmp_path), control=False)
    run.trace = read_xspace(find_xspace(str(tmp_path)))
    assert pt.clock_offset(run) is not None
    values = {m: _read(m, run) for m in SERVE_METRICS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    # on the ring's clock, the requests' waits are those of the engine
    reqs = pt.window_requests(run, ("submit", "refill", "retire_dispatch"))
    assert reqs and all(r["submit"] <= r["refill"] <= r["retire_dispatch"]
                        <= r["done"] for r in reqs)
