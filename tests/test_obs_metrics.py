"""Observability (DESIGN.md §14): metric-stream transparency + tooling.

The load-bearing contract is **bitwise transparency**: ``metrics=None``
compiles the exact program that existed before the obs subsystem — streams
are extra scan *outputs*, never carry state — so every engine must produce
array-equal trajectories with metrics on and off. The matrix below walks
potus/shuffle/jsq through all four engines crossed with ``chunk=``,
``events=`` and the 1-shard mesh (where the collectives are identities).

The nightly runs this file by name (``.github/workflows/nightly.yml``) so a
marker or collection change can't silently drop the transparency contract.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    Component,
    EngineSpec,
    UnsupportedEngineOption,
    build_topology,
    container_costs,
    fat_tree,
    k_failures,
    simulate,
    spout_rate_matrix,
    t_heron_placement,
)
from repro.obs import (
    DEFAULT_STREAMS,
    ENGINE_STREAMS,
    STREAMS,
    FlightRecorder,
    MetricsFrame,
    MetricsSpec,
    stream_engines,
    unsupported_streams,
)

# the CLI dashboards are scripts, not a package; import them by path so the
# recovery-story / bench-diff logic CI gates on is unit-tested here
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
import bench_diff  # noqa: E402
import obs_report  # noqa: E402

T = 24
W = 1


@pytest.fixture(scope="module")
def system():
    """Dyadic-tier system: pow-2 parallelism, dyadic selectivity, pow-2
    arrival masses — exact f32 arithmetic for the bitwise assertions."""
    apps = [
        [
            Component("src", 0, True, 2, successors=(1,)),
            Component("mid", 0, False, 4, 4.0, successors=(2,)),
            Component("sink", 0, False, 2, 4.0),
        ],
    ]
    topo = build_topology(apps, gamma=64.0)
    sd, _ = fat_tree(4)
    net = container_costs("fat-tree", sd)
    rates = np.ones((topo.n_instances, topo.n_components))
    placement = t_heron_placement(topo, net, rates, max_per_container=4)
    rng = np.random.default_rng(7)
    unit = spout_rate_matrix(topo, 1.0)
    arr = (2.0 ** rng.integers(-1, 2, size=(T + W + 1, *unit.shape))).astype(np.float32)
    arr *= rng.random((T + W + 1, *unit.shape)) < 0.8
    arr = (arr * (unit > 0)).astype(np.float32)
    return topo, net, placement, arr


def _spec(system, **kw):
    topo, net, placement, arr = system
    return EngineSpec(topo=topo, net=net, placement=placement, arrivals=arr,
                      T=T, V=2.0, window=W, **kw)


def _kfail(system):
    topo = system[0]
    return k_failures(topo, k=2, start=T // 3, duration=4,
                      rng=np.random.default_rng(3)).compile(topo, T)


#: engine × option cells of the transparency matrix; every cell must be
#: bitwise-identical with metrics on and off
CASES = [
    ("jax", {}),
    ("jax", {"chunk": 8}),
    ("sharded", {}),  # 1-host mesh: every collective is the identity
    ("cohort", {"warmup": 5, "drain_margin": 8}),
    ("cohort-fused", {"warmup": 5}),
    ("cohort-fused", {"warmup": 5, "chunk": 8}),
    ("cohort-fused", {"warmup": 5, "sharded": True}),
]


class TestTransparency:
    """metrics=None vs metrics-on: array-equal trajectories everywhere."""

    @pytest.mark.parametrize("scheduler", ["potus", "shuffle", "jsq"])
    @pytest.mark.parametrize("engine,opts", CASES,
                             ids=[f"{e}-{'-'.join(o) or 'plain'}" for e, o in CASES])
    def test_bitwise_transparent(self, system, engine, opts, scheduler):
        if engine == "sharded" and scheduler != "potus":
            pytest.skip("the sharded scan engine only runs Algorithm 1")
        off = simulate(_spec(system, engine=engine, scheduler=scheduler, **opts))
        on = simulate(_spec(system, engine=engine, scheduler=scheduler,
                            metrics=True, **opts))
        np.testing.assert_array_equal(np.asarray(off.backlog), np.asarray(on.backlog))
        np.testing.assert_array_equal(np.asarray(off.comm_cost), np.asarray(on.comm_cost))
        assert off.metrics is None
        frame = on.metrics
        assert frame is not None and frame.n_slots == T
        assert set(frame.streams) == set(DEFAULT_STREAMS)

    @pytest.mark.parametrize("engine", ["jax", "cohort", "cohort-fused"])
    def test_bitwise_transparent_under_events(self, system, engine):
        trace = _kfail(system)
        kw = {} if engine == "jax" else {"warmup": 5}
        off = simulate(_spec(system, engine=engine, events=trace, **kw))
        on = simulate(_spec(system, engine=engine, events=trace, metrics=True, **kw))
        np.testing.assert_array_equal(np.asarray(off.backlog), np.asarray(on.backlog))
        np.testing.assert_array_equal(np.asarray(off.comm_cost), np.asarray(on.comm_cost))

    def test_backlog_stream_is_the_result_backlog(self, system):
        """The 'backlog' stream must be the h(t) trajectory itself, so the
        disruption recovery story is derivable from the dump alone."""
        res = simulate(_spec(system, engine="cohort-fused", warmup=5,
                             events=_kfail(system), metrics=("backlog",)))
        h = res.metrics.streams["backlog"][:, 0]
        np.testing.assert_allclose(h, np.asarray(res.backlog, np.float64),
                                   rtol=0, atol=1e-4)
        story = obs_report.recovery_story(list(h), 1.1)
        assert story["peak_backlog_slot"] == int(np.argmax(res.backlog))

    def test_engine_specific_streams(self, system):
        """cohort engines serve held/window; only the fused engine serves
        saturation (its age-tagged arrays define the cap boundary)."""
        co = simulate(_spec(system, engine="cohort", warmup=5,
                            metrics=ENGINE_STREAMS["cohort"]))
        fu = simulate(_spec(system, engine="cohort-fused", warmup=5,
                            metrics=sorted(ENGINE_STREAMS["cohort-fused"])))
        assert {"held", "window"} <= set(co.metrics.streams)
        assert {"held", "window", "saturation"} <= set(fu.metrics.streams)
        assert fu.metrics.streams["saturation"].shape == (T, 2)


class TestStreamAvailability:
    """Unsupported streams raise the one normalized error, naming the
    nearest engine that serves the stream."""

    def test_saturation_on_jax_raises(self, system):
        with pytest.raises(UnsupportedEngineOption, match="saturation") as exc:
            simulate(_spec(system, engine="jax",
                           metrics=("backlog", "saturation")))
        assert exc.value.nearest in stream_engines("saturation")

    def test_held_on_sharded_raises(self, system):
        with pytest.raises(UnsupportedEngineOption, match="held"):
            simulate(_spec(system, engine="sharded", metrics=("held",)))

    def test_unknown_stream_rejected(self):
        with pytest.raises(ValueError, match="unknown metric stream"):
            MetricsSpec(streams=("backlog", "nope"))
        with pytest.raises(ValueError, match="duplicate"):
            MetricsSpec(streams=("backlog", "backlog"))

    def test_engine_stream_tables_consistent(self):
        for engine, ok in ENGINE_STREAMS.items():
            assert ok <= set(STREAMS)
            assert unsupported_streams(engine, MetricsSpec()) == ()
            for name in STREAMS:
                assert (engine in stream_engines(name)) == (name in ok)


class TestFrameAndRecorder:
    def test_frame_json_roundtrip(self, tmp_path, system):
        res = simulate(_spec(system, engine="cohort-fused", warmup=5, metrics=True))
        path = tmp_path / "obs.json"
        res.metrics.save(str(path))
        loaded = MetricsFrame.load(str(path))
        assert loaded.spec == res.metrics.spec
        assert loaded.n_slots == res.metrics.n_slots == T
        for name, arr in res.metrics.streams.items():
            assert loaded.columns[name] == res.metrics.columns[name]
            np.testing.assert_allclose(loaded.streams[name], arr,
                                       rtol=0, atol=1e-6)

    def test_frame_rejects_wrong_schema(self):
        with pytest.raises(ValueError, match="schema"):
            MetricsFrame.from_json({"schema": "repro-bench/v2", "streams": {}})

    def test_flight_recorder_ring(self):
        rec = FlightRecorder(capacity=4)
        for t in range(10):
            rec.record(slot=t, h=np.float32(t))
        assert len(rec) == 4 and rec.dropped == 6
        rows = rec.rows()
        assert [r["slot"] for r in rows] == [6, 7, 8, 9]
        assert isinstance(rows[0]["h"], float)  # numpy scalars land as JSON-able
        dump = rec.dump()
        assert dump["schema"] == "repro-bench/v2" and dump["dropped"] == 6

    def test_flight_recorder_fields_filter_and_save(self, tmp_path):
        rec = FlightRecorder(capacity=8, fields=("slot", "h"))
        rec.record(slot=0, h=1.0, secret=42.0)
        assert "secret" not in rec.rows()[0]
        path = tmp_path / "rec.json"
        rec.save(str(path))
        assert json.loads(path.read_text())["rows"] == [{"slot": 0, "h": 1.0}]
        with pytest.raises(ValueError, match="capacity"):
            FlightRecorder(capacity=0)

    def test_fleet_recorder_rows(self):
        from repro.serving.fleet import FleetRequest, ReplicaFleet, SimReplica

        rec = FlightRecorder(capacity=16)
        fleet = ReplicaFleet([SimReplica(4.0), SimReplica(4.0)], recorder=rec)
        fleet.dispatch(0, FleetRequest(rid=0, tokens=8.0, submitted=0))
        for t in range(3):
            fleet.step(t=t)
        assert len(rec) == 3
        assert rec.rows()[1]["backlog_tokens"] > 0  # request landed at t=1


def _profiled(fn):
    """Run ``fn`` under a ``jax.profiler`` trace; (its result, the host
    events of the trace whose name starts with ``potus/``, as (name, start,
    end, stats) tuples)."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        data = ProfileData.from_file(path)
        spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                 for plane in data.planes if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name.startswith("potus/")]
    return out, spans


class TestSpanTracing:
    """Program spans live in the profiler's trace, counters beside them."""

    PHASES = ("prep", "upload", "chunk", "fetch", "reduce")

    def test_span_noop_when_disabled(self, system):
        from repro.obs import take_counters, tracing_enabled

        assert not tracing_enabled()
        take_counters()
        _, spans = _profiled(lambda: simulate(_spec(system, engine="cohort-fused",
                                                    warmup=5)))
        assert spans == []
        assert take_counters() == {}

    def test_phases_nest_in_the_request_span(self, system):
        from repro.obs import disable_tracing, enable_tracing

        enable_tracing()
        try:
            _, spans = _profiled(lambda: [
                simulate(_spec(system, engine="cohort-fused", warmup=5))
                for _ in range(2)])
        finally:
            disable_tracing()
        requests = [s for s in spans if s[0] == "potus/engine/simulate"]
        assert len(requests) == 2
        calls = [r[3]["call"] for r in requests]
        assert calls[1] == calls[0] + 1  # the per-process call index
        assert all(r[3]["engine"] == "cohort-fused" for r in requests)
        for _, r0, r1, _ in requests:
            inside = [n for n, s0, s1, _ in spans if r0 <= s0 and s1 <= r1]
            for phase in self.PHASES:
                assert f"potus/cohort-fused/{phase}" in inside, phase
        starts = {n.rsplit("/", 1)[1]: s0 for n, s0, _, _ in spans
                  if n.startswith("potus/cohort-fused/") and s0 < requests[0][2]}
        assert sorted(starts, key=starts.get) == list(self.PHASES)

    def test_counters_reckon_the_bytes_from_shapes(self, system):
        from repro.obs import disable_tracing, enable_tracing, take_counters

        topo, net, _, _ = system
        I, C, K = topo.n_instances, topo.n_components, net.U.shape[0]
        S = max(len(topo.successors_of_comp(c)) for c in range(C))
        age_cap = 64
        take_counters()
        enable_tracing()
        try:
            simulate(_spec(system, engine="cohort-fused", warmup=5, age_cap=age_cap))
        finally:
            disable_tracing()
        got = take_counters()
        f32 = 4
        h2d = (3 * T * I * C * f32  # act, pred, nxt: (T, I, C) float32
               + I * S * (W + 1) * f32  # the window's initial contents
               + 2 * f32  # V, beta
               + K * K * f32 + I * f32 + 4 * I * S * f32 + I * f32 + I * C * f32  # constants
               + 3 * I * f32 + C * f32 + I)  # the problem; is_spout is bool
        d2h = (2 * T * f32 + 2 * f32  # backlog, cost; capped, served
               + 2 * C * (T + age_cap + W + 1) * f32)  # response accumulators
        assert got == {"h2d_bytes": h2d, "d2h_bytes": d2h}
        assert take_counters() == {}  # read-and-reset

    def test_tracing_compiles_nothing_new(self, system):
        from repro.core.cohort_fused import _scan_cohort_fused
        from repro.obs import disable_tracing, enable_tracing

        simulate(_spec(system, engine="cohort-fused", warmup=5))
        n = _scan_cohort_fused._cache_size()
        enable_tracing()
        try:
            simulate(_spec(system, engine="cohort-fused", warmup=5))
        finally:
            disable_tracing()
        assert _scan_cohort_fused._cache_size() == n

    @pytest.mark.parametrize("scheduler", ["potus", "potus-loop"])  # compact, dense
    def test_slot_step_stages_are_named(self, system, scheduler):
        from repro.core.cohort_fused import scan_hlo_text
        from repro.obs import disable_tracing, enable_tracing

        enable_tracing()
        try:
            simulate(_spec(system, engine="cohort-fused", warmup=5, scheduler=scheduler,
                           metrics=True))
        finally:
            disable_tracing()
        hlo = scan_hlo_text()
        for stage in ("reconcile", "decide", "drain", "land", "admit", "metrics"):
            assert f"/{stage}/" in hlo, stage


class TestCLITools:
    def test_recovery_story(self):
        h = [10.0, 10.0, 10.0, 50.0, 40.0, 30.0, 11.0, 10.0]
        story = obs_report.recovery_story(h, 1.1)
        assert story["peak_backlog_slot"] == 3 and story["peak_backlog"] == 50.0
        assert story["recovery_slot"] == 6 and story["recovery_slots"] == 3
        never = obs_report.recovery_story([1.0, 9.0, 9.0], 1.1)
        assert never["recovery_slot"] == -1 and never["recovery_slots"] == -1

    def test_obs_report_cli_on_real_dump(self, tmp_path, capsys, system):
        res = simulate(_spec(system, engine="cohort-fused", warmup=5, metrics=True))
        path = tmp_path / "obs.json"
        res.metrics.save(str(path))
        assert obs_report.main([str(path), "--stream", "backlog", "--recovery"]) == 0
        out = capsys.readouterr().out
        assert "stream 'backlog'" in out and "recovery story" in out
        assert obs_report.main([str(path), "--stream", "nope"]) == 1

    def test_bench_diff_logic(self):
        base = [{"section": "s", "engine": "e", "scheduler": "potus",
                 "I": 4, "T": 10, "wall_s": 1.0}]
        ok = [dict(base[0], T=20, wall_s=2.4)]
        reg, imp, un = bench_diff.diff(base, ok, tol=1.5)
        assert not reg and not imp and not un  # per-slot: 0.1 vs 0.12
        slow = [dict(base[0], wall_s=10.0)]
        reg, _, _ = bench_diff.diff(base, slow, tol=1.5)
        assert len(reg) == 1 and "10.00x" in reg[0]
        fast = [dict(base[0], wall_s=0.1)]
        _, imp, _ = bench_diff.diff(base, fast, tol=1.5)
        assert len(imp) == 1
        extra = base + [dict(base[0], scheduler="shuffle")]
        _, _, un = bench_diff.diff(extra, base, tol=1.5)
        assert un == ["baseline-only: section=s engine=e scheduler=shuffle I=4"]

    def test_bench_diff_cli(self, tmp_path, capsys):
        payload = {"schema": "repro-bench/v2",
                   "rows": [{"section": "s", "engine": "e", "scheduler": "p",
                             "I": 4, "T": 10, "wall_s": 1.0}]}
        a = tmp_path / "a.json"
        a.write_text(json.dumps(payload))
        assert bench_diff.main([str(a), str(a)]) == 0
        payload["rows"][0]["wall_s"] = 99.0
        b = tmp_path / "b.json"
        b.write_text(json.dumps(payload))
        assert bench_diff.main([str(a), str(b), "--tol", "2.0"]) == 1
        assert "SLOW" in capsys.readouterr().out
