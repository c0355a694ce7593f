"""The trace reducer on a synthetic trace whose answers are known."""
import pytest

from chipbench import trace as tr
from chipbench.metrics import device_idle_share, scan_device_us_per_slot


def _trace():
    ms = 1_000_000.0
    ops = [(0, "%fusion.1 = f32[8]{0} fusion(%a)", 10 * ms, 20 * ms),  # 10-35
           (0, "%fusion.2 = f32[8]{0} fusion(%b)", 25 * ms, 10 * ms),
           (0, "%while.4 = (s32[]) while(%t)", 60 * ms, 10 * ms),  # 60-70
           (0, "%copy.3 = f32[4,4]{1,0:T(8,128)} copy(%c)", 60 * ms, 10 * ms),
           (0, "%fusion.1 = f32[8]{0} fusion(%a)", 95 * ms, 20 * ms)]  # 95-115, clipped
    modules = [(0, "jit__scan_cohort_fused(7)", 10 * ms, 25 * ms),
               (0, "jit_other(1)", 60 * ms, 10 * ms)]
    spans = [(tr.WINDOW_SPAN, 0.0, 100 * ms), ("chipbench/call", 0.0, 50 * ms),
             ("potus/cohort-fused/chunk", 5 * ms, 40 * ms), ("chipbench/call", 50 * ms, 50 * ms)]
    return tr.Trace(ops, modules, spans)


def test_busy_union_idle_share_and_scan_time():
    r = tr.reduce(_trace(), "_scan_cohort_fused")
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.025 + 0.010 + 0.005)
    assert r["module_s"] == pytest.approx(0.025)
    ctx = {"trace": r, "slots": 50, "compiles_in_window": 0}
    assert device_idle_share.read(ctx) == pytest.approx(0.6)
    assert scan_device_us_per_slot.read(ctx) == pytest.approx(500.0)
    ops = dict(r["device_ops"])
    assert ops["fusion.1 f32[8]"] == pytest.approx(0.025)
    assert ops["copy.3 f32[4,4]"] == pytest.approx(0.010)
    assert not any(name.startswith("while") for name in ops)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = dict(tr.reduce(_trace(), "_scan_cohort_fused")["idle_gaps"])
    # 0-10 lies inside the chunk span; 35-60 has its midpoint in the first
    # call after the chunk; 70-95 lies inside the second call
    assert gaps["potus/cohort-fused/chunk"] == pytest.approx(0.010)
    assert gaps["chipbench/call"] == pytest.approx(0.025 + 0.025)


def test_a_trace_without_device_ops_reads_nothing():
    t = _trace()
    r = tr.reduce(tr.Trace([], [], t.spans), "_scan_cohort_fused")
    ctx = {"trace": r, "slots": 50, "compiles_in_window": 0}
    assert device_idle_share.read(ctx) is None
    assert scan_device_us_per_slot.read(ctx) is None
