"""Fused cohort engine vs the Python event-loop oracle (DESIGN.md §8).

Differential testing is tiered by what float arithmetic permits:

* **Exact tier** — on systems whose quantities are all dyadic rationals
  (powers-of-two arrivals, parallelism in {2, 4}, selectivity in {1, 0.5}),
  f32 and f64 arithmetic are both exact, so the two engines must produce
  bit-identical backlog/cost trajectories for every scheduler (POTUS within
  one ulp: its proportional split ``X / shipped`` is the one inherently
  non-dyadic value). Shuffle is feedback-free (its decision ignores queue
  state), so it gets the exact treatment on the paper-profile system too.
* **Statistical tier** — on the paper-profile system, queue-feedback
  schedulers (POTUS, JSQ) amplify f64-vs-f32 ulp noise through price
  near-ties into chaotically divergent trajectories (the phenomenon
  ``test_core_dynamics.py`` documents between the JAX and cohort engines),
  so only long-run means are compared, with tolerances set by that noise
  floor — not by the fused engine's approximations, which the exact tier
  shows are ~0.2% on matched trajectories.
"""
import numpy as np
import pytest

from repro.core import (
    Component,
    FleetEvent,
    FleetScenario,
    SimConfig,
    SweepSpec,
    build_topology,
    container_costs,
    fat_tree,
    poisson_arrivals,
    run_sweep,
    spout_rate_matrix,
    t_heron_placement,
)

from helpers import run_cohort_fused, run_cohort_sim

T = 240


@pytest.fixture(scope="module")
def arrivals(small_system):
    topo, net, rates, placement = small_system
    return poisson_arrivals(np.random.default_rng(7), rates, T + 16)


# ---------------------------------------------------------------------------
# exact tier: dyadic-arithmetic system
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dyadic_system():
    """Diamond + chain with parallelism in {2, 4}, selectivity in {1, 0.5},
    integer mu/gamma and hop-count U: every queue/price value is a dyadic
    rational, so f32 and f64 trajectories agree bitwise."""
    apps = [
        [
            Component("src", 0, True, 2, successors=(1, 2), selectivity=(0.5, 0.5)),
            Component("left", 0, False, 2, 4.0, successors=(3,)),
            Component("right", 0, False, 4, 4.0, successors=(3,)),
            Component("sink", 0, False, 2, 8.0),
        ],
        [
            Component("src", 1, True, 2, successors=(1,)),
            Component("mid", 1, False, 4, 4.0, successors=(2,)),
            Component("sink", 1, False, 2, 4.0),
        ],
    ]
    topo = build_topology(apps, gamma=64.0)
    sd, _ = fat_tree(4)
    net = container_costs("fat-tree", sd)
    rates = np.ones((topo.n_instances, topo.n_components))
    placement = t_heron_placement(topo, net, rates, max_per_container=4)
    return topo, net, placement


def _pow2_arrivals(topo, T, seed):
    """Arrivals whose values are powers of two (exact in f32 and f64)."""
    rng = np.random.default_rng(seed)
    unit = spout_rate_matrix(topo, 1.0)
    arr = (2.0 ** rng.integers(-1, 2, size=(T, *unit.shape))).astype(np.float32)
    arr *= rng.random((T, *unit.shape)) < 0.8
    return (arr * (unit > 0)).astype(np.float32)


class TestExactDyadic:
    @pytest.mark.parametrize("scheduler", ["potus", "shuffle", "jsq"])
    @pytest.mark.parametrize("window", [0, 2])
    def test_trajectories_bit_comparable(self, dyadic_system, scheduler, window):
        topo, net, placement = dyadic_system
        arr = _pow2_arrivals(topo, 300 + 16, seed=3)
        cfg = SimConfig(V=2.0, beta=0.5, window=window, scheduler=scheduler)
        py = run_cohort_sim(topo, net, placement, arr, None, 300, cfg)
        fu = run_cohort_fused(topo, net, placement, arr, None, 300, cfg)
        # POTUS' proportional split (X / shipped) is the one non-dyadic value;
        # everything else must match to the bit
        atol = 1e-4 if scheduler == "potus" else 0.0
        np.testing.assert_allclose(fu.backlog, py.backlog, rtol=0, atol=atol)
        np.testing.assert_allclose(fu.comm_cost, py.comm_cost, rtol=0, atol=atol)
        assert fu.avg_response == pytest.approx(py.avg_response, rel=0.02, abs=0.05)
        assert fu.n_cohorts == py.n_cohorts

    @pytest.mark.parametrize("window", [0, 2])
    def test_mispredicted_arrivals_match(self, dyadic_system, window):
        """TP/FP/TN reconciliation, phantom pre-serves and admission backlog
        agree when a distinct (still dyadic) prediction stream is supplied.
        Shuffle keeps the comparison exact (no queue feedback)."""
        topo, net, placement = dyadic_system
        arr = _pow2_arrivals(topo, 300 + 16, seed=3)
        pred = _pow2_arrivals(topo, 300 + 16, seed=9)
        cfg = SimConfig(V=2.0, beta=0.5, window=window, scheduler="shuffle")
        py = run_cohort_sim(topo, net, placement, arr, pred, 300, cfg)
        fu = run_cohort_fused(topo, net, placement, arr, pred, 300, cfg)
        np.testing.assert_array_equal(fu.backlog, py.backlog)
        np.testing.assert_array_equal(fu.comm_cost, py.comm_cost)
        # partially-drained mixed-age queues attribute responses slightly
        # differently (oldest-source-slot-first vs push-order FIFO, §8)
        assert fu.avg_response == pytest.approx(py.avg_response, rel=0.05, abs=0.05)
        assert fu.p95_response == pytest.approx(py.p95_response, rel=0.10, abs=0.2)


def _dyadic_trace(topo, T):
    """A disruption trace that PRESERVES dyadic arithmetic: alive counts per
    component stay powers of two (kill 2 of comp 2's 4 instances), and
    straggler/throttle factors are 0.5 — so the bitwise differential tier
    extends across the events axis (DESIGN.md §9)."""
    right = topo.instances_of(2)  # app0 "right", parallelism 4
    mid = topo.instances_of(5)  # app1 "mid", parallelism 4
    return FleetScenario((
        FleetEvent("failure", 40, 90, instances=(int(right[0]), int(right[1]))),
        FleetEvent("failure", 120, 160, instances=(int(mid[0]), int(mid[1]))),
        FleetEvent("straggler", 60, 140, instances=(int(mid[2]),), factor=0.5),
        FleetEvent("throttle", 30, 100, instances=(int(topo.instances_of(1)[0]),),
                   factor=0.5),
    ), name="dyadic-chaos").compile(topo, T)


class TestExactDyadicEvents:
    """The §8 differential tiers extended across an events axis: with a
    dyadicity-preserving disruption trace the Python event loop and the
    fused engine must still produce bit-comparable trajectories."""

    @pytest.mark.parametrize("scheduler", ["shuffle", "jsq"])
    @pytest.mark.parametrize("window", [0, 2])
    def test_trajectories_bit_identical_under_disruption(
            self, dyadic_system, scheduler, window):
        topo, net, placement = dyadic_system
        arr = _pow2_arrivals(topo, 300 + 16, seed=3)
        trace = _dyadic_trace(topo, 300)
        cfg = SimConfig(V=2.0, beta=0.5, window=window, scheduler=scheduler)
        py = run_cohort_sim(topo, net, placement, arr, None, 300, cfg, events=trace)
        fu = run_cohort_fused(topo, net, placement, arr, None, 300, cfg,
                              events=trace, age_cap=128)
        np.testing.assert_array_equal(fu.backlog, py.backlog)
        np.testing.assert_array_equal(fu.comm_cost, py.comm_cost)
        assert fu.avg_response == pytest.approx(py.avg_response, rel=0.05, abs=0.05)
        assert fu.n_cohorts == py.n_cohorts
        assert fu.completed_mass == pytest.approx(py.completed_mass, rel=1e-5)

    @pytest.mark.parametrize("window", [0, 2])
    def test_potus_means_agree_under_disruption(self, dyadic_system, window):
        """POTUS' drain-split ratio (X/shipped) is non-dyadic, and the
        disruption-grown queues push its price comparisons through f64-vs-f32
        near-ties (the module-docstring chaos floor) — so under events POTUS
        gets the statistical treatment even on the dyadic system."""
        topo, net, placement = dyadic_system
        arr = _pow2_arrivals(topo, 300 + 16, seed=3)
        trace = _dyadic_trace(topo, 300)
        cfg = SimConfig(V=2.0, beta=0.5, window=window)
        py = run_cohort_sim(topo, net, placement, arr, None, 300, cfg, events=trace)
        fu = run_cohort_fused(topo, net, placement, arr, None, 300, cfg,
                              events=trace, age_cap=128)
        assert fu.avg_backlog == pytest.approx(py.avg_backlog, rel=0.05)
        assert fu.avg_cost == pytest.approx(py.avg_cost, rel=0.05)
        assert fu.avg_response == pytest.approx(py.avg_response, rel=0.10)
        assert fu.completed_mass == pytest.approx(py.completed_mass, rel=1e-3)

    def test_fused_sweep_events_axis_matches_per_scenario(self, dyadic_system):
        topo, net, placement = dyadic_system
        Tg = 120
        arr = _pow2_arrivals(topo, Tg + 16, seed=3)
        trace = _dyadic_trace(topo, Tg)
        spec = SweepSpec(V=(1.0, 2.0), window=(0, 2), events=("none", "chaos"))
        sw = run_sweep(topo, net, placement, arr, Tg, spec, engine="cohort-fused",
                       events={"chaos": trace})
        assert len(sw) == 8
        assert sw.n_batches == 4  # (window, events) partitions
        for scn, res in sw:
            ev = None if scn.events == "none" else trace
            ref = run_cohort_fused(topo, net, placement, arr, None, Tg,
                                   scn.config(), events=ev)
            np.testing.assert_allclose(res.backlog, ref.backlog, rtol=1e-6, atol=1e-4)
            np.testing.assert_allclose(res.comm_cost, ref.comm_cost, rtol=1e-6,
                                       atol=1e-4)


# ---------------------------------------------------------------------------
# exact tier: feedback-free scheduler on the paper-profile system
# ---------------------------------------------------------------------------

class TestShufflePaperSystem:
    @pytest.mark.parametrize("window", [0, 2])
    @pytest.mark.parametrize("mispredicted", [False, True])
    def test_response_and_dynamics_match(self, small_system, arrivals, window, mispredicted):
        topo, net, rates, placement = small_system
        pred = np.maximum(arrivals - 1, 0.0).astype(np.float32) if mispredicted else None
        cfg = SimConfig(V=1.0, window=window, scheduler="shuffle")
        py = run_cohort_sim(topo, net, placement, arrivals, pred, T, cfg)
        fu = run_cohort_fused(topo, net, placement, arrivals, pred, T, cfg)
        np.testing.assert_allclose(fu.backlog, py.backlog, rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(fu.comm_cost, py.comm_cost, rtol=1e-5, atol=1e-3)
        assert fu.avg_response == pytest.approx(py.avg_response, rel=1e-3)
        assert fu.p95_response == pytest.approx(py.p95_response, rel=1e-3)
        assert fu.avg_backlog == pytest.approx(py.avg_backlog, rel=1e-5)
        assert fu.avg_cost == pytest.approx(py.avg_cost, rel=1e-5)
        assert fu.n_cohorts == py.n_cohorts
        assert 0.0 <= fu.completed_frac <= 1.0
        assert fu.saturated_frac == 0.0  # responses ~ O(W+depth) << age_cap


# ---------------------------------------------------------------------------
# statistical tier: POTUS on the paper-profile system
# ---------------------------------------------------------------------------

class TestPotusPaperSystem:
    @pytest.mark.parametrize("window", [0, 2])
    def test_means_agree_within_noise_floor(self, small_system, arrivals, window):
        """Trajectories diverge chaotically on f64-vs-f32 near-tie noise
        (module docstring), so compare long-run means: the fused engine's own
        approximation error is ~0.2% (exact tier); the bounds here are the
        measured chaos floor at this T."""
        topo, net, rates, placement = small_system
        cfg = SimConfig(V=1.0, window=window)
        py = run_cohort_sim(topo, net, placement, arrivals, None, T, cfg)
        fu = run_cohort_fused(topo, net, placement, arrivals, None, T, cfg)
        assert fu.avg_response == pytest.approx(py.avg_response, rel=0.10)
        assert fu.p95_response == pytest.approx(py.p95_response, rel=0.25)
        assert fu.avg_backlog == pytest.approx(py.avg_backlog, rel=0.10)
        assert fu.avg_cost == pytest.approx(py.avg_cost, rel=0.02)
        assert fu.n_cohorts == py.n_cohorts

    def test_high_v_needs_deeper_age_cap(self, small_system, arrivals):
        """Responses grow ~O(V); the A-cap truncation rule (§8) saturates the
        fused metric when age_cap is exceeded, and a deeper cap removes the
        bias."""
        topo, net, rates, placement = small_system
        cfg = SimConfig(V=10.0, window=1)
        py = run_cohort_sim(topo, net, placement, arrivals, None, T, cfg)
        shallow = run_cohort_fused(topo, net, placement, arrivals, None, T, cfg,
                                   age_cap=16)
        deep = run_cohort_fused(topo, net, placement, arrivals, None, T, cfg,
                                age_cap=256)
        assert shallow.avg_response < py.avg_response  # truncation bias, one-sided
        assert deep.avg_response == pytest.approx(py.avg_response, rel=0.10)
        # the saturation diagnostic flags the biased run and clears the deep one
        assert shallow.saturated_frac > 0.05
        assert deep.saturated_frac < 0.01

    def test_saturation_emits_warning_with_suggested_cap(self, small_system, arrivals):
        """A saturated run warns loudly (DESIGN.md §11): the warning names
        the offending age_cap and suggests a doubled one; a clean run stays
        silent."""
        import warnings

        from repro.core import AgeCapSaturationWarning

        topo, net, rates, placement = small_system
        cfg = SimConfig(V=10.0, window=1)
        with pytest.warns(AgeCapSaturationWarning, match="age_cap=16.*age_cap=32"):
            run_cohort_fused(topo, net, placement, arrivals, None, T, cfg, age_cap=16)
        with warnings.catch_warnings():
            warnings.simplefilter("error", AgeCapSaturationWarning)
            run_cohort_fused(topo, net, placement, arrivals, None, T, cfg, age_cap=256)


# ---------------------------------------------------------------------------
# sweep integration: vmapped grid == per-scenario fused calls
# ---------------------------------------------------------------------------

class TestFusedSweep:
    def test_grid_matches_per_scenario_calls(self, dyadic_system):
        """run_sweep(engine='cohort-fused') batches each (scheduler, window)
        partition into one vmapped scan; every scenario must reproduce its
        standalone run_cohort_fused result (dyadic system: exactly)."""
        topo, net, placement = dyadic_system
        Tg = 120
        arr = _pow2_arrivals(topo, Tg + 16, seed=3)
        pred = _pow2_arrivals(topo, Tg + 16, seed=9)
        arrs = {"perfect": arr, "mis": (arr, pred)}
        spec = SweepSpec(V=(1.0, 2.0), window=(0, 2), scheduler=("potus", "shuffle"),
                         arrival=("perfect", "mis"))
        sw = run_sweep(topo, net, placement, arrs, Tg, spec, engine="cohort-fused")
        assert len(sw) == 16
        assert sw.n_batches == 4  # (scheduler, window) partitions
        for scn, res in sw:
            predicted = None if scn.arrival == "perfect" else pred
            ref = run_cohort_fused(topo, net, placement, arr, predicted, Tg,
                                   scn.config())
            np.testing.assert_allclose(res.backlog, ref.backlog, rtol=1e-6, atol=1e-4)
            np.testing.assert_allclose(res.comm_cost, ref.comm_cost, rtol=1e-6, atol=1e-4)
            if np.isnan(ref.avg_response):
                assert np.isnan(res.avg_response)
            else:
                assert res.avg_response == pytest.approx(ref.avg_response, rel=1e-5)

    def test_engine_opts_and_guards(self, small_system, arrivals):
        topo, net, rates, placement = small_system
        with pytest.raises(ValueError):
            run_sweep(topo, net, placement, arrivals, 40, SweepSpec(),
                      engine="cohort-fused", mu=np.ones(topo.n_instances))
        with pytest.raises(ValueError):
            run_sweep(topo, net, placement, arrivals, 40, SweepSpec(),
                      engine="jax", engine_opts={"age_cap": 8})
        with pytest.raises(ValueError):
            run_cohort_fused(topo, net, placement, arrivals, None, 40,
                             SimConfig(), age_cap=1)
        sw = run_sweep(topo, net, placement, arrivals, 60, SweepSpec(V=(1.0, 2.0)),
                       engine="cohort-fused",
                       engine_opts={"age_cap": 24, "warmup": 10, "drain_margin": 20})
        assert np.isfinite(sw.results[0].avg_response)


# ---------------------------------------------------------------------------
# Pallas drain kernel path
# ---------------------------------------------------------------------------

class TestPallasDrain:
    def test_use_pallas_invokes_kernel_and_matches(self, dyadic_system):
        """``potus-loop`` keeps the dense reference path, whose ``use_pallas``
        hot op is the drain+split kernel (the compact schedulers have no
        kernel path, DESIGN.md §12)."""
        import repro.kernels.ops as kops
        from repro.core.cohort_fused import _scan_cohort_fused

        topo, net, placement = dyadic_system
        Tp = 40
        arr = _pow2_arrivals(topo, Tp + 8, seed=5)
        calls = {"n": 0}
        orig = kops.cohort_drain_split

        def spy(*args, **kwargs):
            calls["n"] += 1
            return orig(*args, **kwargs)

        kops.cohort_drain_split = spy
        try:
            _scan_cohort_fused.clear_cache()
            cfg = SimConfig(V=2.0, window=1, scheduler="potus-loop")
            plain = run_cohort_fused(topo, net, placement, arr, None, Tp, cfg,
                                     age_cap=16)
            assert calls["n"] == 0
            via = run_cohort_fused(topo, net, placement, arr, None, Tp,
                                   SimConfig(V=2.0, window=1, scheduler="potus-loop",
                                             use_pallas=True),
                                   age_cap=16)
            assert calls["n"] > 0, "use_pallas=True never reached the drain kernel"
            np.testing.assert_allclose(via.backlog, plain.backlog, rtol=1e-5, atol=1e-3)
            np.testing.assert_allclose(via.comm_cost, plain.comm_cost, rtol=1e-5,
                                       atol=1e-3)
        finally:
            kops.cohort_drain_split = orig

    def test_kernel_matches_xla_reference(self):
        """Direct kernel parity on random (non-contiguous-component) inputs."""
        import jax.numpy as jnp

        from repro.kernels.ops import cohort_drain_split

        rng = np.random.default_rng(0)
        I, C, Atot, A = 24, 5, 13, 8
        comp = rng.integers(0, C, I).astype(np.int32)
        src = (rng.uniform(0, 4, (I, C, Atot + 1))
               * (rng.random((I, C, Atot + 1)) < 0.4)).astype(np.float32)
        ship = rng.uniform(0, 10, (I, C)).astype(np.float32)
        ratio = (rng.uniform(0, 1, (I, I)) * (rng.random((I, I)) < 0.3)).astype(np.float32)

        cum = np.cumsum(src, -1)
        drained = np.clip(ship[:, :, None] - (cum - src), 0.0, src)
        dl = drained[:, :, :Atot].copy()
        dl[:, :, A] += drained[:, :, Atot]
        ref = np.einsum("ij,icb->jcb", ratio, dl)[np.arange(I), comp, :]
        got = np.asarray(cohort_drain_split(
            jnp.asarray(src), jnp.asarray(ship), jnp.asarray(ratio),
            jnp.asarray(comp), A))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# chunked differential: dyadic tier against the event-loop oracle
# ---------------------------------------------------------------------------

class TestChunkedDifferential:
    """The dyadic tier across chunk boundaries inside the lookahead window:
    the compact scan streamed 1, 4 or 7 slots at a time (W=2, so chunks of
    1 end inside every slot's window) must agree with the Python event-loop
    oracle on trajectories (POTUS within the documented 1-ulp split
    tolerance)."""

    @pytest.mark.parametrize("chunk", [1, 4, 7])
    def test_chunked_bitwise_dyadic(self, dyadic_system, chunk):
        topo, net, placement = dyadic_system
        Tm = 120
        arr = _pow2_arrivals(topo, Tm + 16, seed=3)
        cfg = SimConfig(V=2.0, beta=0.5, window=2, scheduler="potus")
        py = run_cohort_sim(topo, net, placement, arr, None, Tm, cfg)
        fu = run_cohort_fused(topo, net, placement, arr, None, Tm, cfg, chunk=chunk)
        np.testing.assert_allclose(fu.backlog, py.backlog, rtol=0, atol=1e-4)
        np.testing.assert_allclose(fu.comm_cost, py.comm_cost, rtol=0, atol=1e-4)
        assert fu.avg_response == pytest.approx(py.avg_response, rel=0.02, abs=0.05)

    @pytest.mark.parametrize("scheduler", ["potus", "shuffle", "jsq"])
    def test_compact_path_exact_across_use_pallas(self, dyadic_system, scheduler):
        """No compact scheduler has a Pallas kernel path — ``use_pallas`` is
        a no-op on the compact scan, so the two flags must match bit for
        bit."""
        topo, net, placement = dyadic_system
        Tm = 120
        arr = _pow2_arrivals(topo, Tm + 16, seed=3)
        runs = [
            run_cohort_fused(topo, net, placement, arr, None, Tm,
                             SimConfig(V=2.0, beta=0.5, window=2,
                                       scheduler=scheduler, use_pallas=up))
            for up in (False, True)
        ]
        np.testing.assert_array_equal(runs[0].backlog, runs[1].backlog)
        np.testing.assert_array_equal(runs[0].comm_cost, runs[1].comm_cost)


# ---------------------------------------------------------------------------
# drain water-fill invariants (hypothesis)
# ---------------------------------------------------------------------------

class TestDrainProperties:
    def test_property_conserves_mass_and_never_reorders_ages(self):
        pytest.importorskip(
            "hypothesis", reason="hypothesis not installed (pip install -e .[test])"
        )
        import jax.numpy as jnp
        from hypothesis import given, settings, strategies as st

        from repro.core.cohort_fused import drain_ages

        @given(
            buckets=st.lists(st.floats(0.0, 16.0), min_size=1, max_size=12),
            amount=st.floats(0.0, 64.0),
        )
        @settings(max_examples=80, deadline=None)
        def check(buckets, amount):
            b = jnp.asarray(np.asarray(buckets, np.float32))
            d = np.asarray(drain_ages(b, jnp.asarray(np.float32(amount))))
            total = float(np.asarray(b).sum())
            # mass conservation: removes exactly min(amount, total)
            assert float(d.sum()) == pytest.approx(min(amount, total), abs=1e-3)
            # bounds: never removes more than a bucket holds, never negative
            assert (d >= -1e-6).all() and (d <= np.asarray(b) + 1e-6).all()
            # FIFO along ages: removal is an age *prefix* — once a bucket is
            # left partially filled, no younger bucket is touched
            partial = np.nonzero(d < np.asarray(b) - 1e-5)[0]
            if partial.size:
                assert d[partial[0] + 1:].sum() == pytest.approx(0.0, abs=1e-5)

        check()
