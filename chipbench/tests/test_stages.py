"""Host-phase and slot-stage attribution (``chipbench/stages.py``) on
synthetic recordings whose answers are known, and on a real profiler trace
of one short call on the CPU."""
import importlib

import numpy as np
import pytest

from chipbench import stages
from chipbench import trace as tr

MS = 1_000_000.0
LINE = ("/host:CPU", 0)
NEW_READERS = ("host_prep_us_per_slot", "host_upload_us_per_slot",
               "host_dispatch_us_per_slot", "host_reduce_us_per_slot",
               "h2d_bytes_per_slot", "decide_device_us_per_slot",
               "drain_device_us_per_slot", "land_device_us_per_slot")


def _reader(name):
    return importlib.import_module(f"chipbench.metrics.{name}").read


#: a compiled module's text, cut down: fusion.1 goes to its root's stage
#: (drain) over its own metadata, fusion.2 to its own where the root has
#: none, fusion.8 to the stage its other instructions name where neither
#: root nor fusion has one, copy.4 has no stage, and fusion.5 nests one
#: fusion in another
HLO = """HloModule jit__scan_cohort_fused, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(_scan_cohort_fused)/vmap()/while/body/closed_call/drain/mul"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0.1, %param_0.1)
}

%fused_computation.3 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %fusion.6 = f32[8]{0} fusion(%param_0.2), kind=kLoop, calls=%fused_computation.1
}

%fused_computation.4 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  %reshape.2 = f32[8]{0} reshape(%param_0.3), metadata={op_name="jit(_scan_cohort_fused)/while/body/land/reshape"}
  ROOT %scatter.3 = f32[8]{0} scatter(%param_0.3, %reshape.2)
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0:T(128)} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_scan_cohort_fused)/vmap()/while/body/closed_call/decide/mul"}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2, metadata={op_name="decide/vmap()/add"}
  %gather.3 = f32[4]{0} gather(%p), metadata={op_name="jit(_scan_cohort_fused)/while/body/land/gather"}
  %copy.4 = f32[4,4]{1,0} copy(%p)
  %fusion.5 = f32[8]{0} fusion(%p), kind=kCustom, calls=%fused_computation.3
  %fusion.8 = f32[8]{0} fusion(%p), kind=kCustom, calls=%fused_computation.4
  ROOT %admit.7 = f32[8]{0} negate(%p), metadata={op_name="jit(_scan_cohort_fused)/while/body/admit/neg"}
}
"""


def _recording():
    """One call in a 100 ms window: the request span 10-90 holds prep 10-20,
    upload 20-25 (and a nested upload 21-23), chunk 25-27, fetch 27-70 and
    reduce 70-85. A span that starts after the window is not counted. The
    scan runs 27-60; an operation of another program at 22 shares a name
    with one of the scan's and is not the scan's."""
    spans = [(tr.WINDOW_SPAN, 0.0, 100 * MS, LINE),
             ("chipbench/call", 5 * MS, 90 * MS, LINE),
             ("potus/engine/simulate", 10 * MS, 80 * MS, LINE),
             ("potus/cohort-fused/prep", 10 * MS, 10 * MS, LINE),
             ("potus/cohort-fused/upload", 20 * MS, 5 * MS, LINE),
             ("potus/cohort-fused/upload", 21 * MS, 2 * MS, LINE),
             ("potus/cohort-fused/chunk", 25 * MS, 2 * MS, LINE),
             ("potus/cohort-fused/fetch", 27 * MS, 43 * MS, LINE),
             ("potus/cohort-fused/reduce", 70 * MS, 15 * MS, LINE),
             ("potus/cohort-fused/prep", 101 * MS, 5 * MS, LINE)]
    ops = [(0, "fusion.2 f32[8]", 22 * MS, 1 * MS),  # another program's
           (0, "fusion.2 f32[8]", 27 * MS, 10 * MS), (0, "gather.3 f32[4]", 37 * MS, 4 * MS),
           (0, "fusion.1 f32[8]", 41 * MS, 8 * MS), (0, "fusion.5 f32[8]", 49 * MS, 1 * MS),
           (0, "admit.7 f32[8]", 50 * MS, 4 * MS), (0, "fusion.8 f32[8]", 54 * MS, 1 * MS),
           (0, "copy.4 f32[4,4]", 55 * MS, 2 * MS),
           (0, "fusion.2 f32[8]", 120 * MS, 5 * MS)]  # after the window
    modules = [(0, "jit__scan_cohort_fused(3)", 27 * MS, 33 * MS),
               (0, "jit_scan_cohort_fused(3)", 120 * MS, 10 * MS),
               (0, "jit_broadcast_in_dim(1)", 22 * MS, 1 * MS)]
    return stages.Recording(spans, ops, modules)


def test_span_self_time_leaves_out_the_children():
    got = stages.span_self_s(_recording(), 0.0, 100 * MS)
    assert got["potus/engine/simulate"] == pytest.approx(0.080 - 0.075)
    assert got["potus/cohort-fused/prep"] == pytest.approx(0.010)  # the later one is out
    assert got["potus/cohort-fused/upload"] == pytest.approx(0.003 + 0.002)
    assert got["potus/cohort-fused/chunk"] == pytest.approx(0.002)
    assert got["potus/cohort-fused/fetch"] == pytest.approx(0.043)
    assert got["chipbench/call"] == pytest.approx(0.090 - 0.080)


def test_instructions_take_the_stage_of_their_root():
    assert stages.hlo_stages(HLO) == {
        "param_0 f32[8]": None, "mul.1 f32[8]": "drain", "param_0.1 f32[8]": None,
        "add.1 f32[8]": None, "param_0.2 f32[8]": None, "fusion.6 f32[8]": "drain",
        "p f32[8]": None, "fusion.1 f32[8]": "drain", "fusion.2 f32[8]": "decide",
        "gather.3 f32[4]": "land", "copy.4 f32[4,4]": None, "fusion.5 f32[8]": "drain",
        "param_0.3 f32[8]": None, "reshape.2 f32[8]": "land", "scatter.3 f32[8]": None,
        "fusion.8 f32[8]": "land", "admit.7 f32[8]": "admit"}


def test_stage_time_and_the_share_no_stage_claims():
    per, scan, unclaimed = stages.stage_s(_recording(), stages.hlo_stages(HLO),
                                          0.0, 100 * MS)
    assert per == pytest.approx({"decide": 0.010, "land": 0.005, "drain": 0.009,
                                 "admit": 0.004})
    assert scan == pytest.approx(0.033)
    assert unclaimed == pytest.approx(0.005)  # copy.4 and the gaps


@pytest.mark.parametrize("text,stage", [
    ("jit(_scan_cohort_fused)/vmap()/while/body/closed_call/drain/mul", "drain"),
    ("decide/vmap()/or", "decide"),
    ("jit(_scan_cohort_fused)/while/body/land/dot_general;jit(f)/x", "land"),
    ("jit(_scan_cohort_fused)/while/cond/lt", None),
    ("jit(_drain_ages)/landing/x", None),  # only whole parts name a stage
])
def test_stage_of_a_name_stack(text, stage):
    assert stages.stage_of(text) == stage


def _ctx(monkeypatch, rec, counters, hlo=HLO):
    monkeypatch.setattr(stages, "load", lambda log_dir: rec)
    monkeypatch.setattr(stages, "program_counters", lambda: counters)
    monkeypatch.setattr(stages, "program_scan_hlo", lambda: hlo)
    return {"trace": {}, "slots": 10, "compiles_in_window": 0, "log_dir": "unused"}


def test_readers_per_slot(monkeypatch):
    ctx = _ctx(monkeypatch, _recording(), {"h2d_bytes": 270_000, "d2h_bytes": 900})
    got = {name: _reader(name)(ctx) for name in NEW_READERS}
    assert got == pytest.approx({
        "host_prep_us_per_slot": 1000.0, "host_upload_us_per_slot": 500.0,
        "host_dispatch_us_per_slot": 200.0, "host_reduce_us_per_slot": 1500.0,
        "h2d_bytes_per_slot": 27_000.0, "decide_device_us_per_slot": 1000.0,
        "drain_device_us_per_slot": 900.0, "land_device_us_per_slot": 500.0})


def test_readers_read_nothing_where_the_program_has_no_spans_scopes_or_counters(
        monkeypatch):
    rec = _recording()
    bare = stages.Recording([s for s in rec.spans if not s[0].startswith("potus/")],
                            rec.ops, rec.modules)
    unscoped = HLO.replace("drain/", "").replace("decide/", "").replace("land/", "")
    for hlo in (None, unscoped):
        ctx = _ctx(monkeypatch, bare, None, hlo)
        assert {name: _reader(name)(ctx) for name in NEW_READERS} == dict.fromkeys(NEW_READERS)


def test_readers_read_nothing_without_a_trace(monkeypatch):
    def missing(log_dir):
        raise FileNotFoundError(log_dir)

    ctx = _ctx(monkeypatch, None, {"h2d_bytes": 1})
    monkeypatch.setattr(stages, "load", missing)
    assert {name: _reader(name)(ctx) for name in NEW_READERS} == dict.fromkeys(NEW_READERS)


def test_a_real_trace_of_one_call(tmp_path):
    """The program's phases, read back from the profiler's own trace of one
    short call of the k4 deployment (CPU: host spans only)."""
    import jax

    from chipbench import deploy, traffic
    from chipbench import run as bench
    from repro.obs import trace as program_trace

    mix = dict(traffic.load_mix("potus-poisson"), T=16, warmup=0)
    dep = deploy.build_deployment("potus-paper-k4")
    call, slots = bench.make_call(mix, *deploy.program_inputs(dep))
    inputs = traffic.draw(mix, dep.rates, 5)[0]
    call(inputs)
    program_trace.take_counters()
    program_trace.enable_tracing()
    try:
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            call(inputs)
        jax.profiler.stop_trace()
    finally:
        program_trace.disable_tracing()
    ctx = {"trace": {}, "slots": slots, "compiles_in_window": 0, "log_dir": str(tmp_path)}
    for name in NEW_READERS[:4]:
        assert _reader(name)(ctx) > 0, name
    stage_of_op = stages.hlo_stages(stages.program_scan_hlo())
    assert set(stage_of_op.values()) == {None, *stages.STAGES}
    # a call uploads the arrivals packed on the spout streams' lanes: T+W+1
    # rows of one float32 per lane (under perfect prediction the actuals are
    # those rows), not dense (I, C) arrivals, predictions and next windows
    counters = stages.of(ctx)["counters"]
    lanes = np.count_nonzero(dep.rates)
    assert counters["packed_stream_bytes"] == traffic.n_slots(mix) * lanes * 4
    I, C = dep.rates.shape
    assert _reader("h2d_bytes_per_slot")(ctx) < 3 * I * C * 4
    assert counters["d2h_bytes"] > 0
