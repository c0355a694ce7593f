"""The compact decision stage against the index-op spellings it replaced.

On the XLA path the decision spreads (I, S) streams over (I, C) by a
one-hot sum, puts the water-fill back into component order by a second
sort, and prices each row's point target from a (K, C) table — where it
once used a scatter-add, a scatter and an element gather, which the TPU
runs one index at a time (DESIGN.md §12.2). Each is exact, so the
references below are those spellings, kept here, and every comparison is
bitwise, on non-dyadic float32 inputs with ties, ``inf`` prices, zero
budgets, zero gamma, dead instances, a component with no instance and one
whose instances are all dead, and sentinel streams.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Component,
    EngineSpec,
    build_topology,
    container_costs,
    fat_tree,
    feasible_rates,
    poisson_arrivals,
    rolling_restart,
    simulate,
    t_heron_placement,
)
from repro.core import compact
from repro.core.potus import _fill_components


# ---------------------------------------------------------------------------
# the replaced spellings
# ---------------------------------------------------------------------------

def _to_dense_ref(c, x_cmp):
    """(I, S) -> (I, C) by a scatter-add into a sentinel column."""
    I, S = x_cmp.shape
    C = c.comp_onehot.shape[1]
    rows = jnp.arange(I)[:, None]
    return jnp.zeros((I, C + 1), x_cmp.dtype).at[rows, c.succ_map].add(x_cmp)[:, :C]


def _fill_rows_sort_ref(m, j_c, budget, gamma):
    """The water-fill unsorted by a scatter through ``perm``."""
    C = m.shape[1]

    def one(m_r, j_r, b_r, g_r):
        fill, _, perm = _fill_components(m_r, j_r, b_r, g_r)
        return jnp.zeros((C,), fill.dtype).at[perm].set(fill)

    return jax.vmap(one)(m, j_c, budget, gamma)


def _point_price_ref(U, cp, J):
    """U gathered per (i, c) at the container of the row's target ``j_c``."""
    I = cp.inst_comp.shape[0]
    j_c = jnp.where(cp.adj_rows > 0.0, J[cp.inst_cont], I)
    return U[cp.inst_cont[:, None], cp.inst_cont[jnp.minimum(j_c, I - 1)]]


def _use_references(monkeypatch):
    monkeypatch.setattr(compact, "_to_dense", _to_dense_ref)
    monkeypatch.setattr(compact, "_fill_rows_sort", _fill_rows_sort_ref)
    monkeypatch.setattr(compact, "_point_price", _point_price_ref)


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

C, K, S = 8, 6, 3
EMPTY, DEAD, KEYED = 4, 5, 7  # no instance; every instance dead; fields-grouped
# non-dyadic values from small sets, so prices and budgets tie
PRICES = np.float32([0.1, 0.3, 0.7, 1.3, 1.9, 2.9])
QUEUES = np.float32([0.0, 0.2, 0.6, 1.1, 2.3])
BACKLOGS = np.float32([0.0, 1.1, 3.7, 6.1, 9.3])  # outgoing: POTUS's candidates


def _system(seed):
    """A hand-built compact problem: instances per component (none in
    EMPTY), successors (EMPTY, DEAD and KEYED among them) and the (I, S)
    successor map with sentinel streams."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(2, 5, C)
    counts[EMPTY] = 0
    inst_comp = np.repeat(np.arange(C), counts).astype(np.int32)
    I = inst_comp.size
    succ = {c: sorted(rng.choice(np.arange(c + 1, C), min(S, C - 1 - c, int(rng.integers(1, S + 1))),
                                 replace=False)) for c in range(C - 1)}
    succ[C - 1] = []
    succ[1] = sorted({EMPTY, DEAD, KEYED} | set(succ[1]))[:S]
    succ[2] = sorted({KEYED} | set(succ[2]))[:S]
    succ_map = np.full((I, S), C, np.int32)
    adj_rows = np.zeros((I, C), np.float32)
    for i, c in enumerate(inst_comp):
        succ_map[i, :len(succ[c])] = succ[c]
        adj_rows[i, succ[c]] = 1.0
    return rng, inst_comp, succ_map, adj_rows


def _problem(seed, keyed):
    rng, inst_comp, succ_map, adj_rows = _system(seed)
    I = inst_comp.size
    alive = (rng.random(I) < 0.8).astype(np.float32)
    alive[inst_comp == DEAD] = 0.0
    comp_count = jnp.zeros((C,), jnp.float32).at[inst_comp].add(alive)  # the events path's
    key_share = None
    keyed_c = None
    if keyed:
        keyed_c = jnp.asarray(np.arange(C) == KEYED, jnp.float32)
        share = rng.random(I).astype(np.float32) * (inst_comp == KEYED)
        key_share = jnp.asarray(share / share.sum())
    cp = compact.CompactProblem(
        jnp.asarray(inst_comp), jnp.asarray(rng.integers(0, K, I), jnp.int32),
        jnp.asarray(rng.choice(np.float32([0.0, 1.5, 2.7, 24.0]), I)), comp_count,
        jnp.asarray(adj_rows), jnp.asarray(alive), keyed_c, key_share)
    c = types.SimpleNamespace(succ_map=jnp.asarray(succ_map),
                              comp_onehot=jnp.zeros((I, C), jnp.float32))
    U = jnp.asarray(rng.choice(PRICES, (K, K)))
    q_in = jnp.asarray(rng.choice(QUEUES, I))
    # streams, with mass on the sentinel ones too: it must land nowhere
    q_out_cmp = jnp.asarray(rng.choice(BACKLOGS, (I, S)))
    must_cmp = jnp.asarray(rng.choice(np.float32([0.0, 0.3, 0.9]), (I, S)))
    return c, cp, U, q_in, q_out_cmp, must_cmp


# ---------------------------------------------------------------------------
# one function at a time
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("s", [1, 2, 3])
def test_to_dense_is_the_scatter_add(seed, s):
    rng, inst_comp, succ_map, _ = _system(seed)
    I = inst_comp.size
    c = types.SimpleNamespace(succ_map=jnp.asarray(succ_map[:, :s]),
                              comp_onehot=jnp.zeros((I, C), jnp.float32))
    x = jnp.asarray(rng.standard_normal((I, s)).astype(np.float32) / 3)
    got = jax.jit(lambda x: compact._to_dense(c, x))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_to_dense_ref(c, x)))
    assert (succ_map[:, :s] == C).any()  # sentinel streams are exercised


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("shape", [(37, 6), (83, 27), (5, 1)])
def test_fill_is_the_scatter_unsort(seed, shape):
    I, Cf = shape
    rng = np.random.default_rng(seed)
    m = rng.choice(np.float32([-2.3, -1.1, -0.7, -0.2, np.inf]), shape)
    j_c = rng.integers(0, 4, shape).astype(np.int32)  # repeats: ties in (m, j)
    budget = np.where(np.isinf(m), 0.0, rng.choice(QUEUES, shape)).astype(np.float32)
    gamma = rng.choice(np.float32([0.0, 0.9, 2.7, 1e3]), I)
    args = tuple(map(jnp.asarray, (m, j_c, budget, gamma)))
    got = jax.jit(compact._fill_rows_sort)(*args)
    want = jax.jit(_fill_rows_sort_ref)(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got).any()


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("keyed", [False, True], ids=["shuffle-edges", "keyed"])
@pytest.mark.parametrize("scheduler", ["potus", "shuffle", "jsq"])
def test_decision_is_the_replaced_spellings(monkeypatch, scheduler, keyed, seed):
    """From the (I, S) streams through ``_to_dense`` to the whole
    ``CompactDecision``. The cost's products are the same numbers; the order
    in which a jitted program sums them is the compiler's, set by how it
    vectorizes the fusion around the sum, so the cost is compared bitwise op
    by op, where each sum is a reduction of its own, and jitted to within
    the reordering of two sums of I·C non-negative terms."""
    c, cp, U, q_in, q_out_cmp, must_cmp = _problem(seed, keyed)

    def decide():  # a new function each time: jit caches a trace per function
        return jax.jit(lambda q_in, q_out_cmp, must_cmp: compact.compact_decide(
            scheduler, cp, U, q_in, compact._to_dense(c, q_out_cmp),
            compact._to_dense(c, must_cmp) * cp.alive[:, None],
            jnp.float32(1.3), jnp.float32(0.7)))(q_in, q_out_cmp, must_cmp)

    def both():  # jitted, and the cost op by op
        jitted = decide()
        with jax.disable_jit():
            return jitted, decide().cost

    got, got_cost = both()
    _use_references(monkeypatch)
    want, want_cost = both()
    for name, a, b in zip(compact.CompactDecision._fields[:-1], got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert float(got_cost) == float(want_cost) > 0.0
    I = cp.inst_comp.shape[0]
    np.testing.assert_allclose(float(got.cost), float(want.cost),
                               rtol=2 * I * C * np.finfo(np.float32).eps)
    if scheduler == "potus":
        assert np.asarray(got.point).any()  # the point price is read


# ---------------------------------------------------------------------------
# a whole scan
# ---------------------------------------------------------------------------

T, W = 40, 3


def _keyed_run(scheduler):
    apps = [[
        Component("src", 0, True, 3, successors=(1, 2), selectivity=(0.6, 0.4)),
        Component("split", 0, False, 3, 3.0, successors=(3, 4), selectivity=(0.7, 0.3)),
        Component("side", 0, False, 2, 4.0, successors=(4,)),
        Component("count", 0, False, 3, 2.0, key_shares=(0.45, 0.35, 0.2)),
        Component("sink", 0, False, 2, 5.0),
    ]]
    topo = build_topology(apps, gamma=6.0)
    net = container_costs("fat-tree", fat_tree(4)[0])
    placement = t_heron_placement(topo, net, np.ones((topo.n_instances, topo.n_components)),
                                  max_per_container=3)
    arr = poisson_arrivals(np.random.default_rng(7), feasible_rates(topo, 0.8), T + W + 1)
    events = rolling_restart(topo, start=10, down_slots=3,
                             instances=[1, 4, 9]).compile(topo, T, placement)
    jax.clear_caches()  # trace the step anew, with whatever spellings are in place
    return simulate(EngineSpec(
        topo=topo, net=net, placement=placement, arrivals=arr, T=T,
        engine="cohort-fused", scheduler=scheduler, V=1.3, beta=0.7, window=W,
        age_cap=16, events=events, metrics=("transit", "held", "saturation")))


@pytest.mark.parametrize("scheduler", ["potus", "shuffle"])
def test_keyed_scan_with_events_is_the_replaced_spellings(monkeypatch, scheduler):
    got = _keyed_run(scheduler)
    _use_references(monkeypatch)
    want = _keyed_run(scheduler)
    jax.clear_caches()
    for name in ("backlog", "comm_cost", "avg_response"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)), err_msg=name)
    assert float(got.completed_mass) == float(want.completed_mass) > 0.0
    for name, stream in got.metrics.streams.items():
        np.testing.assert_array_equal(stream, want.metrics.streams[name], err_msg=name)
