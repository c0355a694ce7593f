"""Fields groupings (DESIGN.md §15): keyed edges in the compact slot step.

A component with ``key_shares`` takes a fields grouping on every edge into
it: instance ``j`` takes share ``j`` of the inflow whatever the queues say.
The references here:

* ``core/cohort.py`` on the *pinned expansion*: each keyed instance made a
  component of its own (parallelism 1), fed at the edge's selectivity times
  its share. Shuffle and JSQ ship a per-component amount by Heron's rule, so
  on a pinned instance they ship its share of what the fields grouping ships
  — the same dynamics. On the dyadic system below (pow-2 arrivals, dyadic
  shares and selectivities, integer mu/gamma/U) float32 and float64 are both
  exact, so the engines agree to the bit; the tolerance allows float32
  rounding (rtol 1e-6) only for the non-dyadic shares of the second system.
* under POTUS, which may not steer a keyed edge, no pinned system has the
  same decision; there the landing's split, the mass ledger and the gamma
  budget are checked directly.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Component,
    EngineSpec,
    SweepSpec,
    UnsupportedEngineOption,
    build_topology,
    container_costs,
    fat_tree,
    feasible_rates,
    poisson_arrivals,
    run_sweep,
    simulate,
    spout_rate_matrix,
    t_heron_placement,
)
from repro.core import cohort_fused as cf
from repro.core import compact

T = 96
W = 2
SHARES = (0.5, 0.25, 0.25, 0.0)


def _apps(shares=SHARES, filter_sel=None):
    """src -> split -> {count (keyed), log (shuffle)}, and a shuffle-only
    chain; ``split`` has a keyed and a shuffle successor. ``filter_sel``
    gives split's selectivities (default: flow-conserving halves)."""
    sel = filter_sel or (0.5, 0.5)
    return [
        [
            Component("src", 0, True, 2, successors=(1,)),
            Component("split", 0, False, 2, 4.0, successors=(2, 3), selectivity=sel),
            Component("count", 0, False, len(shares), 1.0, key_shares=shares),
            Component("log", 0, False, 2, 4.0),
        ],
        [
            Component("src", 1, True, 2, successors=(1,)),
            Component("mid", 1, False, 4, 4.0, successors=(2,)),
            Component("sink", 1, False, 2, 8.0),
        ],
    ]


def _pinned(apps):
    """Each instance of a keyed component made a component of its own."""
    out = []
    for comps in apps:
        first = np.cumsum([0] + [len(c.key_shares) or 1 for c in comps])
        pinned = []
        for comp in comps:
            succ, sel = [], []
            for s, f in zip(comp.successors, comp.selectivity or (1.0,) * len(comp.successors)):
                shares = comps[s].key_shares or (1.0,)
                succ += [int(first[s]) + j for j in range(len(shares))]
                sel += [f * x for x in shares]
            for j in range(len(comp.key_shares) or 1):
                pinned.append(Component(
                    f"{comp.name}.{j}" if comp.key_shares else comp.name, comp.app,
                    comp.is_spout, 1 if comp.key_shares else comp.parallelism,
                    comp.proc_capacity, tuple(succ), tuple(sel)))
        out.append(pinned)
    return out


def _system(apps):
    topo = build_topology(apps, gamma=64.0)
    net = container_costs("fat-tree", fat_tree(4)[0])
    placement = t_heron_placement(topo, net, np.ones((topo.n_instances, topo.n_components)),
                                  max_per_container=4)
    return topo, net, placement


def _pow2_arrivals(topo, seed):
    rng = np.random.default_rng(seed)
    unit = spout_rate_matrix(topo, 1.0)
    arr = (2.0 ** rng.integers(-1, 3, size=(T + W + 1, *unit.shape))).astype(np.float32)
    arr *= rng.random(arr.shape) < 0.8
    return (arr * (unit > 0)).astype(np.float32)


def _spec(topo, net, placement, arr, engine="cohort-fused", **kw):
    return EngineSpec(topo=topo, net=net, placement=placement, arrivals=arr, T=T,
                      engine=engine, **{"V": 2.0, "beta": 0.5, "window": W, **kw})


# ---------------------------------------------------------------------------
# the topology
# ---------------------------------------------------------------------------

def test_topology_carries_the_grouping():
    topo, _, _ = _system(_apps())
    assert topo.keyed and topo.comp_keyed.tolist() == [False, False, True, False,
                                                       False, False, False]
    count = topo.instances_of(2)
    np.testing.assert_array_equal(topo.inst_key_share[count], np.float32(SHARES))
    assert topo.inst_key_share[np.setdiff1d(np.arange(topo.n_instances), count)].sum() == 0
    assert not build_topology(_pinned(_apps())).keyed


@pytest.mark.parametrize("bad", ["length", "sum", "spout"])
def test_bad_key_shares_are_refused(bad):
    apps = _apps()
    if bad == "length":
        apps[0][2] = Component("count", 0, False, 3, 4.0, key_shares=SHARES)
    elif bad == "sum":
        apps[0][2] = Component("count", 0, False, 4, 4.0, key_shares=(0.5, 0.5, 0.5, 0.0))
    else:
        apps[0][0] = Component("src", 0, True, 2, successors=(1,), key_shares=(0.5, 0.5))
    with pytest.raises(ValueError, match="share|spout"):
        build_topology(apps)


def test_expected_and_feasible_rates_count_the_hottest_keyed_instance():
    topo = build_topology(_apps(), gamma=64.0)
    unit = spout_rate_matrix(topo, 1.0)
    comp = topo.expected_rates(unit)
    inst = topo.expected_rates(unit, per_instance=True)
    count = topo.instances_of(2)
    np.testing.assert_allclose(inst[count], comp[2] * np.float32(SHARES), rtol=1e-7)
    np.testing.assert_allclose(inst[topo.instances_of(1)], comp[1] / 2)
    rates = feasible_rates(topo, utilization=0.7)
    load = topo.expected_rates(rates, per_instance=True) / np.maximum(topo.inst_mu, 1e-9)
    # the hottest keyed instance (share 0.5 of count's inflow at mu 1) sets
    # the rate; count's mean load would have allowed twice as much
    assert load.max() == pytest.approx(0.7)
    assert load[count].max() == pytest.approx(0.7)
    assert load[count].mean() == pytest.approx(0.7 / 2)


# ---------------------------------------------------------------------------
# the decision
# ---------------------------------------------------------------------------

def _problem(seed):
    """A compact problem of the keyed system with random output queues of
    20-90 tuples per edge (split's two edges together mostly over gamma)."""
    topo, net, placement = _system(_apps())
    cpt = cf._compact(topo)
    rng = np.random.default_rng(seed)
    I, C = topo.n_instances, topo.n_components
    cp = compact.CompactProblem(
        jnp.asarray(topo.inst_comp), jnp.asarray(placement, jnp.int32),
        jnp.asarray(topo.inst_gamma), jnp.asarray(topo.comp_parallelism, jnp.float32),
        jnp.asarray(cpt.adj_rows), jnp.ones((I,), jnp.float32),
        jnp.asarray(topo.comp_keyed, jnp.float32), jnp.asarray(topo.inst_key_share))
    q_out = jnp.asarray(cpt.adj_rows * rng.uniform(20.0, 90.0, (I, C)), jnp.float32)
    spout = topo.comp_is_spout[topo.inst_comp][:, None]
    must = jnp.asarray(np.where(spout, cpt.adj_rows * 3.0, 0.0), jnp.float32)
    q_in = jnp.asarray(rng.uniform(0.0, 5.0, I), jnp.float32)
    return topo, net, cp, q_in, q_out, must


@pytest.mark.parametrize("scheduler", ["potus", "shuffle", "jsq"])
def test_keyed_shipments_are_herons_rule(scheduler):
    topo, net, cp, q_in, q_out, must = _problem(1)
    dec = compact.compact_decide(scheduler, cp, jnp.asarray(net.U), q_in, q_out, must,
                                 jnp.float32(1.0), jnp.float32(1.0))
    keyed = np.asarray(cp.keyed) > 0
    want = np.where(np.asarray(cp.adj_rows) > 0,
                    np.asarray(compact._ship_amounts_compact(cp, q_out, must)), 0.0)
    np.testing.assert_array_equal(np.asarray(dec.shipped)[:, keyed], want[:, keyed])
    # nothing of a keyed edge is aimed at one instance or spread evenly
    assert (np.asarray(dec.point)[:, keyed] == 0).all()
    assert (np.asarray(dec.even_per)[:, keyed] == 0).all()
    assert (np.asarray(dec.j_point)[:, keyed] == topo.n_instances).all()


@pytest.mark.parametrize("scheduler", ["potus", "shuffle", "jsq"])
def test_a_source_with_keyed_and_shuffle_successors_spends_at_most_gamma(scheduler):
    topo, net, cp, q_in, q_out, must = _problem(2)
    dec = compact.compact_decide(scheduler, cp, jnp.asarray(net.U), q_in, q_out, must,
                                 jnp.float32(1.0), jnp.float32(1.0))
    split = topo.instances_of(1)
    spent = np.asarray(dec.shipped)[split].sum(axis=1)
    keyed = np.asarray(dec.shipped)[split][:, 2]
    assert (keyed > 0).all() and (np.asarray(dec.shipped)[split][:, 3] > 0).all()
    assert (spent <= topo.inst_gamma[split] * (1 + 1e-6)).all(), spent


# ---------------------------------------------------------------------------
# whole runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shares,rtol", [(SHARES, 0.0), ((0.375, 0.3, 0.2, 0.125), 1e-6)],
                         ids=["dyadic", "float32"])
@pytest.mark.parametrize("scheduler", ["shuffle", "jsq"])
def test_keyed_fused_equals_cohort_on_the_pinned_expansion(scheduler, shares, rtol):
    topo, net, placement = _system(_apps(shares))
    ptopo = build_topology(_pinned(_apps(shares)), gamma=64.0)
    assert ptopo.n_instances == topo.n_instances  # the same instances, in order
    arr = _pow2_arrivals(topo, 3)
    # the spouts feed split and mid, which are components 1 and 8 pinned
    parr = np.zeros(arr.shape[:2] + (ptopo.n_components,), np.float32)
    parr[..., [1, 8]] = arr[..., [1, 5]]
    fu = simulate(_spec(topo, net, placement, arr, scheduler=scheduler))
    py = simulate(_spec(ptopo, net, placement, parr, engine="cohort", scheduler=scheduler))
    atol = 1e-4 if rtol else 0.0
    np.testing.assert_allclose(fu.backlog, py.backlog, rtol=rtol, atol=atol)
    np.testing.assert_allclose(fu.comm_cost, py.comm_cost, rtol=rtol, atol=atol)
    assert fu.completed_mass == pytest.approx(py.completed_mass, rel=1e-6)


def _slots(topo, net, placement, arr, scheduler):
    """Run the compact slot step one slot at a time; yield each slot's
    landing per instance (the transit it leaves, summed over ages)."""
    cpt = cf._compact(topo)
    prob = cf._compact_prob(topo, placement)
    dev = cf._device_inputs(topo, net, cpt)
    C, I = topo.n_components, topo.n_instances
    consts = cf._step_consts(
        prob, jax.nn.one_hot(prob.inst_comp, C, dtype=jnp.float32), dev["U"], dev["mu"],
        dev["inv_service"], dev["sel_cmp"], dev["stream_cmp"], dev["valid_cmp"],
        dev["succ_map"], dev["term_f"], dev["adj_rows"], jnp.float32(1.0), jnp.float32(1.0),
        dev["keyed"], dev["key_share"])
    age_cap = 16
    A = age_cap + W + 1
    q0 = np.zeros(cpt.valid.shape + (W + 1,), np.float32)
    q0[cpt.lanes[0], cpt.lane_slot] = arr[:W + 1, cpt.lanes[0], cpt.lanes[1]].T
    S = cpt.valid.shape[1]
    state = (jnp.asarray(q0), jnp.zeros((I, S)), jnp.zeros((I, A)), jnp.zeros((I, S, A)),
             jnp.zeros((I, A)), jnp.zeros((C, T + A)), jnp.zeros((C, T + A)))
    step = jax.jit(lambda st, x: compact.compact_slot_step(
        consts, st, x, scheduler=scheduler, age_cap=age_cap))
    for t in range(T):
        x = (jnp.asarray(arr[t]), jnp.asarray(arr[t]), jnp.asarray(arr[t + W + 1]), t)
        state, _ = step(state, x)
        yield np.asarray(state[4]).sum(axis=1)


def test_potus_lands_keyed_mass_by_the_shares_every_slot():
    topo, net, placement = _system(_apps())
    count = topo.instances_of(2)
    landed_any = 0
    for landed in _slots(topo, net, placement, _pow2_arrivals(topo, 5), "potus"):
        total = landed[count].sum()
        np.testing.assert_allclose(landed[count], total * np.float32(SHARES),
                                   rtol=1e-6, atol=1e-6)
        landed_any += total > 0
    assert landed_any > T // 2


@pytest.mark.parametrize("scheduler", ["potus", "shuffle", "jsq"])
@pytest.mark.parametrize("filtered", [False, True], ids=["conserving", "filter"])
def test_the_mass_ledger_closes(scheduler, filtered):
    """Completed mass through slot T-2 plus what the system held at slot
    T-1 equals what arrived in slots 0..T-1+W. With ``filter``, split emits
    one tuple in four and acks the rest, which complete there."""
    apps = _apps(filter_sel=(0.125, 0.125) if filtered else None)
    topo, net, placement = _system(apps)
    rates = feasible_rates(topo, 0.7)
    arr = poisson_arrivals(np.random.default_rng(9), rates, T + W + 1)
    r = simulate(_spec(topo, net, placement, arr, scheduler=scheduler, beta=1.0,
                       metrics=("transit", "held", "saturation")))
    st = r.metrics.streams
    done = r.completed_mass - st["saturation"][-1, 1]
    held = r.backlog[-1] + st["transit"][-2, 0] + st["held"][-2, 0]
    offered = arr[:T + W].sum(dtype=np.float64)
    assert abs(done + held - offered) / offered < 1e-5
    if filtered:
        assert topo.comp_done_share[1] == pytest.approx(0.75)


def test_a_filter_completes_the_same_mass_on_every_oracle():
    """The fused engine, ``core/cohort.py`` and the fluid event simulation
    complete a filter's unemitted half where it is served, alike."""
    from repro.core import SimConfig
    from repro.core.eventsim import run_event_sim

    topo, net, placement = _system(_pinned(_apps(filter_sel=(0.25, 0.25))))
    arr = _pow2_arrivals(topo, 7)
    fu = simulate(_spec(topo, net, placement, arr, scheduler="shuffle"))
    py = simulate(_spec(topo, net, placement, arr, engine="cohort", scheduler="shuffle"))
    ev = run_event_sim(topo, net, placement, arr, T,
                       SimConfig(V=2.0, beta=0.5, window=W, scheduler="shuffle"))
    assert fu.completed_mass == py.completed_mass == ev.completed_mass
    assert fu.completed_mass > 0.99 * arr[:T].sum()  # nearly all left the system
    np.testing.assert_array_equal(fu.backlog, py.backlog)


# ---------------------------------------------------------------------------
# where a fields grouping is refused
# ---------------------------------------------------------------------------

def _refusals():
    yield "engine=jax", lambda s: simulate(_spec(*s, engine="jax"))
    yield "engine=sharded", lambda s: simulate(_spec(*s, engine="sharded"))
    yield "engine=cohort", lambda s: simulate(_spec(*s, engine="cohort"))
    yield "use_pallas", lambda s: simulate(_spec(*s, use_pallas=True))
    yield "sharded", lambda s: simulate(_spec(*s, sharded=True))
    yield "potus-loop", lambda s: simulate(_spec(*s, scheduler="potus-loop"))
    yield "run_sweep jax", lambda s: run_sweep(*s[:3], {"a": s[3]}, T, SweepSpec(V=(1.0,)))
    yield "run_sweep use_pallas", lambda s: run_sweep(
        *s[:3], {"a": s[3]}, T, SweepSpec(V=(1.0,), use_pallas=True), engine="cohort-fused")


def _decide_sharded(s):
    _, net, cp, q_in, q_out, must = _problem(0)
    compact.compact_decide("potus", cp, jnp.asarray(net.U), q_in, q_out, must,
                           1.0, 1.0, axis="i")


@pytest.mark.parametrize("where,call", list(_refusals()) + [
    ("compact_decide sharded", _decide_sharded)],
    ids=lambda x: x if isinstance(x, str) else "")
def test_every_other_engine_and_path_refuses_a_fields_grouping(where, call):
    topo, net, placement = _system(_apps())
    with pytest.raises(UnsupportedEngineOption, match="keyed"):
        call((topo, net, placement, _pow2_arrivals(topo, 1)))


# ---------------------------------------------------------------------------
# a topology without a fields grouping runs the code it ran before
# ---------------------------------------------------------------------------

def _digest(r) -> str:
    h = hashlib.sha256()
    st = r.metrics.streams
    for a in (r.backlog, r.comm_cost, [r.avg_response, r.completed_mass],
              st["transit"], st["held"], st["saturation"]):
        h.update(np.ascontiguousarray(np.asarray(a, np.float64)).tobytes())
    return h.hexdigest()[:16]


#: digests of the unkeyed system's runs before fields groupings existed
#: (dyadic arithmetic is exact, so they hold on any CPU)
UNKEYED_BEFORE = {"shuffle": "6071eed7ba5d188f", "jsq": "adec5f18d095ab24"}


@pytest.mark.parametrize("scheduler", sorted(UNKEYED_BEFORE))
def test_an_unkeyed_topology_gives_what_it_gave_before(scheduler):
    apps = _pinned(_apps())
    topo, net, placement = _system(apps)
    r = simulate(_spec(topo, net, placement, _pow2_arrivals(topo, 11), scheduler=scheduler,
                       metrics=("transit", "held", "saturation")))
    assert _digest(r) == UNKEYED_BEFORE[scheduler]


def test_an_unkeyed_topology_traces_no_keyed_landing():
    topo, net, placement = _system(_pinned(_apps()))
    assert "keyed" not in cf._device_inputs(topo, net, cf._compact(topo))
    kw = dict(scheduler="potus", age_cap=8)
    for t, want in ((topo, False), (_system(_apps())[0], True)):
        cpt = cf._compact(t)
        dev = cf._device_inputs(t, net, cpt)
        prob = cf._compact_prob(t, placement)
        C, I, S = t.n_components, t.n_instances, cpt.valid.shape[1]
        consts = cf._step_consts(prob, jax.nn.one_hot(prob.inst_comp, C), dev["U"], dev["mu"],
                                 dev["inv_service"], dev["sel_cmp"], dev["stream_cmp"],
                                 dev["valid_cmp"], dev["succ_map"], dev["term_f"],
                                 dev["adj_rows"], 1.0, 1.0, dev.get("keyed"),
                                 dev.get("key_share"))
        A = 8 + W + 1
        state = (jnp.zeros((I, S, W + 1)), jnp.zeros((I, S)), jnp.zeros((I, A)),
                 jnp.zeros((I, S, A)), jnp.zeros((I, A)), jnp.zeros((C, A + 4)),
                 jnp.zeros((C, A + 4)))
        x = (jnp.zeros((I, C)), jnp.zeros((I, C)), jnp.zeros((I, C)), 0)
        text = jax.jit(lambda st, x: compact.compact_slot_step(consts, st, x, **kw)).lower(
            state, x).as_text(debug_info=True)
        assert ("drain/keyed" in text) == want
