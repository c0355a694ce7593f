"""The compact one-dispatch slot step against the unfused composition
(DESIGN.md §12).

``core.compact.compact_slot_step`` is the one step every compact scheduler
scans. It is checked

* against the **unfused dense composition** (``cohort_fused._fused_step``:
  separate schedule, drain+split and queue-update stages), scanned whole
  and in 4-slot pieces with the state carried between them;
* in **f32 and f64**: the step is dtype-generic, and f64 runs under the x64
  switch and must agree with the f64 unfused composition to tight relative
  tolerance (catching any accidental f32 truncation inside the step);
* across **piece boundaries**: for every compact scheduler, the scan in
  4-slot pieces is bitwise the whole scan.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Component,
    SimConfig,
    build_topology,
    container_costs,
    fat_tree,
    spout_rate_matrix,
    t_heron_placement,
)
from repro.core import cohort_fused as cf
from repro.core import compact as cm
from repro.core.potus import make_problem
from repro.core.simulator import _get_scheduler, materialize_arrivals

T = 40
AGE_CAP = 16
W = 2


@pytest.fixture(scope="module")
def system():
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
    rng = np.random.default_rng(3)
    unit = spout_rate_matrix(topo, 1.0)
    arr = (2.0 ** rng.integers(-1, 2, size=(T + W + 1, *unit.shape))).astype(np.float32)
    arr *= rng.random((T + W + 1, *unit.shape)) < 0.8
    arr = (arr * (unit > 0)).astype(np.float32)
    return topo, net, placement, arr


def _setup(system, dtype):
    """Scan inputs, initial state, and StepConsts in ``dtype``."""
    topo, net, placement, arr = system
    cfg = SimConfig(V=2.0, beta=0.5, window=W, scheduler="potus")
    actual = materialize_arrivals(arr, topo, T + W + 1)
    prob = make_problem(topo, net, placement)
    cpt = cf._compact(topo)
    pred_p, _, q_rem0 = cf._prep_streams(actual, None, T, W, cpt)
    dev = cf._device_inputs(topo, net, cpt)
    I, C = topo.n_instances, topo.n_components
    Sc, W1 = q_rem0.shape[1:]
    Atot = AGE_CAP + W1
    state0 = (
        jnp.asarray(q_rem0, dtype),
        jnp.zeros((I, Sc), dtype),
        jnp.zeros((I, Atot), dtype),
        jnp.zeros((I, Sc, Atot), dtype),
        jnp.zeros((I, Atot), dtype),
        jnp.zeros((C, T + Atot), dtype),
        jnp.zeros((C, T + Atot), dtype),
    )
    act, pred, nxt = cf._dense_streams(dev["lanes"], jnp.asarray(pred_p, dtype), None,
                                       W + 1, I, C)
    xs = (act, pred, nxt, jnp.arange(T))
    V, beta = jnp.asarray(cfg.V, dtype), jnp.asarray(cfg.beta, dtype)
    comp_onehot = jax.nn.one_hot(prob.inst_comp, C, dtype=dtype)
    dev = {k: (v if v.dtype == jnp.int32 else v.astype(dtype))
           for k, v in dev.items()}
    consts = cm.StepConsts(
        U=dev["U"], mu=dev["mu"], inv_service=dev["inv_service"],
        sel_cmp=dev["sel_cmp"], stream_cmp=dev["stream_cmp"],
        valid_cmp=dev["valid_cmp"], succ_map=dev["succ_map"],
        term_f=dev["term_f"], comp_onehot=comp_onehot,
        inst_comp=prob.inst_comp, inst_cont=prob.inst_container,
        gamma=prob.gamma.astype(dtype),
        comp_count=prob.comp_count.astype(dtype),
        spout_f=prob.is_spout.astype(dtype),
        adj_rows=dev["adj_rows"], V=V, beta=beta,
    )
    return prob, cpt, dev, consts, state0, xs, V, beta, comp_onehot


def _run_dense(system, dtype, scheduler="potus"):
    """The unfused composition: schedule -> drain+split -> update as separate
    stages of ``cohort_fused._fused_step``."""
    prob, cpt, dev, consts, state0, xs, V, beta, comp_onehot = _setup(system, dtype)
    u_pair = dev["U"][prob.inst_container[:, None], prob.inst_container[None, :]]
    step = partial(
        cf._fused_step, prob, _get_scheduler(scheduler, False), cpt.edges,
        dev["U"], u_pair, dev["mu"], dev["inv_service"], dev["sel_cmp"],
        dev["stream_cmp"], dev["valid_cmp"], dev["succ_map"], dev["term_f"],
        comp_onehot, AGE_CAP, False, V, beta,
    )
    return jax.lax.scan(step, state0, xs)


def _run_compact(system, dtype, scheduler="potus", piece=None):
    """The compact scan over all T slots, or ``piece`` slots at a time with
    the state (accumulators included) carried from piece to piece."""
    prob, cpt, dev, consts, state0, xs, V, beta, _ = _setup(system, dtype)
    step = partial(cm.compact_slot_step, consts, scheduler=scheduler,
                   age_cap=AGE_CAP)
    scan = jax.jit(lambda s, x: jax.lax.scan(step, s, x))
    if piece is None:
        fin, out = scan(state0, xs)
        return fin, tuple(np.asarray(o) for o in out)
    state, outs = state0, []
    for t0 in range(0, T, piece):
        state, out = scan(state, tuple(x[t0:t0 + piece] for x in xs))
        outs.append(out)
    return state, tuple(np.concatenate([np.asarray(o[i]) for o in outs])
                        for i in range(4))


def _assert_state_close(a, b, rtol, atol):
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)


class TestCompactStepParity:
    @pytest.mark.parametrize("piece", [None, 4])
    @pytest.mark.parametrize("scheduler", ["potus", "shuffle", "jsq"])
    def test_f32_vs_unfused_composition(self, system, scheduler, piece):
        fin_d, out_d = _run_dense(system, jnp.float32, scheduler)
        fin_c, out_c = _run_compact(system, jnp.float32, scheduler, piece=piece)
        # POTUS' proportional split is the one non-dyadic value (atol 1e-4,
        # same tier as tests/test_cohort_fused.py)
        for a, b in zip(out_d[:2], out_c[:2]):
            np.testing.assert_allclose(np.asarray(a), b, rtol=1e-6, atol=1e-4)
        _assert_state_close(fin_c, fin_d, rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("piece", [None, 4])
    def test_f64_vs_unfused_composition(self, system, piece):
        with jax.enable_x64(True):
            fin_d, out_d = _run_dense(system, jnp.float64)
            fin_c, out_c = _run_compact(system, jnp.float64, piece=piece)
            assert all(x.dtype == jnp.float64 for x in fin_c)  # no f32 truncation
            for a, b in zip(out_d[:2], out_c[:2]):
                np.testing.assert_allclose(np.asarray(a), b, rtol=1e-12, atol=1e-9)
            _assert_state_close(fin_c, fin_d, rtol=1e-10, atol=1e-9)

    @pytest.mark.parametrize("scheduler", ["potus", "shuffle", "jsq"])
    def test_pieces_bitwise_equal_whole_scan(self, system, scheduler):
        """Cutting the scan into 4-slot pieces changes no arithmetic."""
        fin_w, out_w = _run_compact(system, jnp.float32, scheduler)
        fin_p, out_p = _run_compact(system, jnp.float32, scheduler, piece=4)
        for a, b in zip(out_w, out_p):
            np.testing.assert_array_equal(a, b)
        _assert_state_close(fin_p, fin_w, rtol=0, atol=0)
