"""Disruption benchmark + paper-style figure (DESIGN.md §9).

Drives a failure/recovery transient through the fused cohort engine: a
k-instance failure hits the paper system one third into the run and recovers
after a sixth of the horizon. POTUS (at several predictive windows W) races
the reactive Shuffle baseline — which in the fluid model is exactly what a
round-robin dispatcher converges to, so the shuffle rows double as RR.

Shuffle is work-conserving at maximum rate (it dumps the entire lookahead
window every slot, paying the communication cost POTUS exists to avoid), so
raw response comparisons flatter it; the disruption metric is therefore each
scheduler's **degradation against its own undisturbed run** — the grid
crosses ``events=("none", "kfail")`` and every transient number is reported
as disturbed minus undisturbed over the same arrival slots.

Two sections share one sweep grid:

* ``disruption`` — bench rows + ``BENCH_disruption.json`` (shared schema,
  ``benchmarks/common.py``): per (scheduler, W), transient response
  degradation, peak-backlog inflation and recovery time through the
  failure, with ``speedup`` = shuffle's degradation over POTUS's at the
  same W (how much less the predictive scheduler is hurt).
* ``figD`` — the figure: response degradation of cohorts *arriving during
  the outage* vs W. The predictive window absorbs the disruption
  (pre-admitted tuples ride out the dead interval, and the window sees the
  recovered fleet before reactive queues do), so POTUS's degradation falls
  with W.
"""
from __future__ import annotations

import numpy as np

from repro.core import SweepSpec, k_failures, run_sweep

from .common import QUICK, SMOKE, T_COHORT, Row, arrivals_for, bench_row, paper_system, timer

# machine-readable rows for BENCH_disruption.json (written by benchmarks/run.py)
DISRUPTION_BENCH: list[dict] = []

_CACHE: dict = {}


def _transient_grid():
    """One (scheduler x W x {none, kfail}) grid through the k-failure
    transient; cached so the bench and figure sections share the compile."""
    if "grid" in _CACHE:
        return _CACHE["grid"]
    sys = paper_system("fat-tree")
    T = T_COHORT
    t0, dur = T // 3, max(T // 6, 4)
    k = max(int(0.2 * len(sys.topo.bolt_instances)), 2)
    scen = k_failures(sys.topo, k=k, start=t0, duration=dur,
                      rng=np.random.default_rng(11))
    arr = arrivals_for(sys, "poisson", T)
    Ws = (0, 2, 6) if (QUICK or SMOKE) else (0, 1, 2, 4, 6, 10)
    spec = SweepSpec(V=1.0, window=Ws, scheduler=("potus", "shuffle"),
                     events=("none", "kfail"))
    ev = {"kfail": scen}
    # transient aggregation window: cohorts arriving while instances are down
    # (plus the immediate recovery tail); age_cap must cover outage + queueing.
    # One sweep covers everything: responses are windowed to the transient,
    # while the backlog trajectories it returns are whole-run regardless of
    # the aggregation window, so peaks/recovery need no second execution.
    age_cap = max(4 * dur, 48)
    warm = max(t0 - 1, 1)
    margin = T - min(t0 + dur + 10, T - 1)
    with timer() as t:
        transient = run_sweep(sys.topo, sys.net, sys.placement, arr, T, spec,
                              engine="cohort-fused", events=ev,
                              engine_opts={"age_cap": age_cap, "warmup": warm,
                                           "drain_margin": margin})
    _CACHE["grid"] = (sys, T, t0, dur, scen, Ws, transient, t.dt)
    return _CACHE["grid"]


def _recovery_slots(backlog: np.ndarray, t0: int, t1: int) -> int:
    """Slots after recovery until backlog returns within 10% of the
    pre-failure mean (horizon end if it never does)."""
    pre = backlog[max(t0 - 20, 0):t0].mean()
    post = backlog[t1:]
    ok = np.nonzero(post <= 1.1 * pre)[0]
    return int(ok[0]) if ok.size else int(len(post))


def _degradation(transient, sched: str, W: int) -> float:
    """Transient response under the failure minus the same scheduler/window's
    undisturbed transient response (same arrival slots)."""
    hurt = transient.result(scheduler=sched, window=W, events="kfail").avg_response
    base = transient.result(scheduler=sched, window=W, events="none").avg_response
    return float(hurt - base)


def _dump_obs(sys, T, t0, dur, scen, W) -> None:
    """Metrics-on POTUS run through the same transient (DESIGN.md §14).

    Re-runs the kfail scenario with every cohort-fused stream enabled, under
    ``jax.profiler`` with the program's spans on, then dumps
    ``OBS_disruption.json`` (``repro-obs/v1``) and ``TRACE_disruption.json``
    (the profiler's Perfetto trace, decompressed: Chrome-trace JSON).  The recovery
    story in BENCH_disruption — peak-backlog slot, recovery slot — is
    re-derivable from the streams alone via
    ``python tools/obs_report.py OBS_disruption.json --recovery``.
    """
    import glob
    import gzip
    import os
    import shutil
    import tempfile

    import jax

    from repro.obs.trace import disable_tracing, enable_tracing, tracing_enabled

    obs_path = os.environ.get("REPRO_OBS_DISRUPTION_JSON", "OBS_disruption.json")
    trace_path = os.environ.get("REPRO_OBS_TRACE_JSON", "TRACE_disruption.json")
    arr = arrivals_for(sys, "poisson", T)
    spec = SweepSpec(V=1.0, window=(W,), scheduler=("potus",), events=("kfail",))
    age_cap = max(4 * dur, 48)
    warm = max(t0 - 1, 1)
    margin = T - min(t0 + dur + 10, T - 1)
    streams = ("backlog", "queue_depth", "price", "dispatch", "transit",
               "backlog_comp", "held", "window", "saturation", "payload")
    log_dir = tempfile.mkdtemp(prefix="trace-disruption-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # spans and ops, not every Python call
    try:
        jax.profiler.start_trace(log_dir, create_perfetto_trace=True,
                                 profiler_options=options)
        profiling = True
    except RuntimeError:
        # one session at a time: under ``benchmarks.run --profile`` that
        # session's trace holds these spans, and no TRACE file is written
        profiling = False
    was_tracing = tracing_enabled()
    enable_tracing()
    try:
        swept = run_sweep(sys.topo, sys.net, sys.placement, arr, T, spec,
                          engine="cohort-fused", events={"kfail": scen},
                          engine_opts={"age_cap": age_cap, "warmup": warm,
                                       "drain_margin": margin,
                                       "metrics": streams})
    finally:
        if not was_tracing:
            disable_tracing()
        if profiling:
            jax.profiler.stop_trace()
    swept.result(scheduler="potus", window=W, events="kfail").metrics.save(obs_path)
    if profiling:
        perfetto, = glob.glob(os.path.join(log_dir, "**", "perfetto_trace.json.gz"),
                              recursive=True)
        with gzip.open(perfetto, "rb") as src, open(trace_path, "wb") as dst:
            shutil.copyfileobj(src, dst)
    shutil.rmtree(log_dir, ignore_errors=True)


def disruption_bench() -> list[Row]:
    """Bench rows + BENCH_disruption.json through the failure transient."""
    sys, T, t0, dur, scen, Ws, transient, wall = _transient_grid()
    I = sys.topo.n_instances
    rows = []
    shuffle_deg = {W: _degradation(transient, "shuffle", W) for W in Ws}
    for sched in ("potus", "shuffle"):
        for W in Ws:
            deg = _degradation(transient, sched, W)
            tr = transient.result(scheduler=sched, window=W, events="kfail")
            tr0 = transient.result(scheduler=sched, window=W, events="none")
            rec = _recovery_slots(tr.backlog, t0, t0 + dur)
            peak = float(tr.backlog[t0:t0 + dur + 10].max())
            peak0 = float(tr0.backlog[t0:t0 + dur + 10].max())
            speedup = (shuffle_deg[W] / deg
                       if sched == "potus" and deg > 1e-9 else 1.0)
            rows.append(Row(
                f"disruption/{sched}/W{W}", wall / (len(transient) * T) * 1e6,
                f"resp_transient={tr.avg_response:.2f};resp_degradation={deg:.2f};"
                f"peak_backlog={peak:.0f};peak_backlog_undisturbed={peak0:.0f};"
                f"recovery_slots={rec};degradation_vs_shuffle={speedup:.2f}x",
            ))
            DISRUPTION_BENCH.append(bench_row(
                "disruption", "cohort-fused", sched, I, T, wall / len(transient),
                speedup=speedup, scenario=scen.name, W=W,
                resp_transient=round(float(tr.avg_response), 3),
                resp_degradation=round(deg, 3),
                peak_backlog=round(peak, 1),
                peak_backlog_undisturbed=round(peak0, 1),
                recovery_slots=rec,
                saturated_frac=round(float(tr.saturated_frac), 4),
            ))
    _dump_obs(sys, T, t0, dur, scen, max(Ws))
    return rows


def figd_disruption() -> list[Row]:
    """FigD: transient response degradation vs W — the predictive window
    absorbs the outage (POTUS degradation falls with W)."""
    sys, T, t0, dur, scen, Ws, transient, wall = _transient_grid()
    rows = []
    for sched in ("potus", "shuffle"):
        derived = ";".join(
            f"W{W}={_degradation(transient, sched, W):.2f}" for W in Ws
        )
        rows.append(Row(f"figD/{sched}/{scen.name}",
                        wall / (len(transient) * T) * 1e6, derived))
    return rows
