"""The fused engine's per-call upload (DESIGN.md §11.2).

Each chunk sends only the (instance, successor component) lanes of the
spouts' streams, the only entries of the arrival tensors the slot step reads,
and the scan's program scatters them back into the step's dense inputs. So
the packing is exact by construction, and anything off the lanes is never
read: arrivals with noise there must give bitwise the same run. The
slot-invariant inputs stay on the device, keyed by their content.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Component,
    EngineSpec,
    SweepSpec,
    build_topology,
    container_costs,
    fat_tree,
    run_sweep,
    simulate,
    spout_rate_matrix,
    t_heron_placement,
)
from repro.core import cohort_fused as cf

T = 29
W = 3
METRICS = ("transit", "held", "saturation")


@pytest.fixture(scope="module")
def system():
    """Two apps whose spouts both fan out to two successor components."""
    apps = [
        [
            Component("src", 0, True, 2, successors=(1, 2), selectivity=(0.5, 0.5)),
            Component("left", 0, False, 2, 4.0, successors=(3,)),
            Component("right", 0, False, 4, 4.0, successors=(3,)),
            Component("sink", 0, False, 2, 8.0),
        ],
        [
            Component("src", 1, True, 3, successors=(1, 2)),
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


def _mask(topo):
    """(I, C) 1.0 on the spout streams, from the topology alone."""
    is_spout = topo.comp_is_spout[topo.inst_comp]
    return (topo.adj[topo.inst_comp] & is_spout[:, None]).astype(np.float32)


def _streams(topo, seed):
    """Fractional arrivals on the spout streams, a predictor that misses,
    and the same two with Poisson noise everywhere off the streams."""
    rng = np.random.default_rng(seed)
    unit = spout_rate_matrix(topo, 1.0) > 0
    shape = (T + W + 1,) + unit.shape
    actual = (rng.poisson(1.5, shape) * rng.random(shape) * unit).astype(np.float32)
    predicted = np.maximum(actual + rng.normal(0.0, 0.4, shape) * unit, 0.0).astype(np.float32)
    off = ~(_mask(topo) > 0)

    def noisy(x):
        return np.where(off, x + rng.poisson(3.0, shape), x).astype(np.float32)

    return (actual, predicted), (noisy(actual), noisy(predicted))


def _outputs(r):
    out = {k: np.asarray(getattr(r, k)) for k in (
        "backlog", "comm_cost", "avg_response", "p95_response", "avg_backlog",
        "avg_cost", "n_cohorts", "completed_frac", "saturated_frac", "completed_mass")}
    out.update({f"stream:{k}": np.asarray(v) for k, v in r.metrics.streams.items()})
    return out


def _assert_bitwise(got, want):
    assert got.keys() == want.keys()
    for k in want:
        a, b = np.atleast_1d(got[k]), np.atleast_1d(want[k])
        assert a.dtype == b.dtype and a.shape == b.shape, k
        assert a.tobytes() == b.tobytes(), k


@pytest.mark.parametrize("predicted", [False, True], ids=["perfect", "predictor"])
def test_pack_then_expand_is_the_masked_arrivals(system, predicted):
    """Packing on the host and expanding in the scan's program gives the
    arrivals times the stream mask on every entry, the spouts' initial
    windows and the response weights as the dense arrays gave them."""
    topo, _, _ = system
    I, C = topo.n_instances, topo.n_components
    rng = np.random.default_rng(1)
    x = rng.random((T + W + 1, I, C)).astype(np.float32) + 0.5  # nonzero everywhere
    p = (rng.random(x.shape).astype(np.float32) + 0.5) if predicted else None
    cpt = cf._compact(topo)
    mask = _mask(topo)
    assert cpt.lanes.shape == (2, int(mask.sum()))

    pred, act, q_rem0 = cf._prep_streams(x, p, T, W, cpt)
    assert pred.shape == (T + W + 1, cpt.lanes.shape[1])
    assert (act is None) == (not predicted)
    d_act, d_pred, d_nxt = cf._dense_streams(
        jnp.asarray(cpt.lanes), jnp.asarray(pred),
        None if act is None else jnp.asarray(act), W + 1, I, C)
    src = x if p is None else p
    np.testing.assert_array_equal(np.asarray(d_act), (x * mask)[:T])
    np.testing.assert_array_equal(np.asarray(d_pred), (src * mask)[:T])
    np.testing.assert_array_equal(np.asarray(d_nxt), (src * mask)[W + 1: T + W + 1])

    win = np.moveaxis(src[: W + 1], 0, -1) * mask[:, :, None]  # (I, C, W+1)
    idx = np.minimum(cpt.succ_map, C - 1)[:, :, None]
    np.testing.assert_array_equal(
        q_rem0, np.take_along_axis(win, idx, axis=1) * cpt.valid[:, :, None])
    np.testing.assert_allclose(
        cf._entry_weights(cf._actual_stream((pred, act, q_rem0), T), cpt, C),
        np.einsum("sic,ic->cs", x[:T], mask), rtol=1e-6)


@pytest.mark.parametrize("case", ["perfect", "predictor", "ragged-chunks"])
def test_noise_off_the_streams_changes_nothing(system, case):
    """Arrivals with noise off the stream lanes run bitwise like the clean
    ones through ``simulate(engine="cohort-fused")``."""
    topo, net, placement = system
    clean, noisy = _streams(topo, seed=7)
    kw = dict(topo=topo, net=net, placement=placement, T=T, engine="cohort-fused",
              scheduler="potus", V=2.0, window=W, warmup=4, metrics=METRICS)
    if case == "ragged-chunks":
        kw["chunk"] = 8  # does not divide T
    use_pred = case != "perfect"

    def run(streams):
        actual, predicted = streams
        return _outputs(simulate(EngineSpec(
            arrivals=actual, predicted=predicted if use_pred else None, **kw)))

    _assert_bitwise(run(noisy), run(clean))


def test_noise_off_the_streams_changes_no_stacked_sweep(system):
    """``run_fused_sweep`` with stacked, non-shared arrivals (one scenario
    with a predictor, one without) runs bitwise like the clean arrivals."""
    topo, net, placement = system
    spec = SweepSpec(V=(1.0, 3.0), window=(W,), scheduler=("potus", "shuffle"),
                     arrival=("plain", "predicted"))

    def run(streams):
        actual, predicted = streams
        res = run_sweep(topo, net, placement,
                        {"plain": actual, "predicted": (actual, predicted)}, T, spec,
                        engine="cohort-fused",
                        engine_opts={"warmup": 4, "chunk": 11, "metrics": METRICS})
        return [_outputs(r) for r in res.results]

    clean, noisy = _streams(topo, seed=11)
    got, want = run(noisy), run(clean)
    assert len(got) == len(want) == 8
    for g, w in zip(got, want):
        _assert_bitwise(g, w)


def test_packed_stream_bytes_per_chunk(system):
    """With a predictor each chunk uploads its rows of both packed streams:
    slots t0..t1+W of the prediction, t0..t1-1 of the actuals."""
    from repro.obs import disable_tracing, enable_tracing, take_counters

    topo, net, placement = system
    (actual, predicted), _ = _streams(topo, seed=3)
    L = int(_mask(topo).sum())
    chunk = 8
    take_counters()
    enable_tracing()
    try:
        simulate(EngineSpec(topo=topo, net=net, placement=placement, arrivals=actual,
                            predicted=predicted, T=T, engine="cohort-fused", window=W,
                            warmup=4, chunk=chunk))
    finally:
        disable_tracing()
    got = take_counters()
    rows = sum(2 * min(chunk, T - t0) + W + 1 for t0 in range(0, T, chunk))
    assert got["packed_stream_bytes"] == rows * L * 4


def test_resident_constants_follow_their_content(system):
    """The slot-invariant inputs go to the device once per content: the same
    deployment reuses its device arrays, another service time or placement
    uploads its own, and the cache stays bounded."""
    topo, net, placement = system
    cf._RESIDENT.clear()
    cpt = cf._compact(topo)
    first = cf._device_inputs(topo, net, cpt)
    again = cf._device_inputs(topo, net, cpt)
    assert all(again[k] is first[k] for k in first)
    again["U"] = None  # callers get their own dict
    assert cf._device_inputs(topo, net, cpt)["U"] is first["U"]

    slow = cf._device_inputs(topo, net, cpt, service=2.0)
    assert slow["inv_service"] is not first["inv_service"]
    np.testing.assert_array_equal(np.asarray(slow["inv_service"]), 0.5)
    np.testing.assert_array_equal(np.asarray(first["inv_service"]), 1.0)

    moved = np.roll(placement, 1)
    prob = cf._compact_prob(topo, moved)
    np.testing.assert_array_equal(np.asarray(prob.inst_container), moved)
    assert cf._compact_prob(topo, moved).inst_container is prob.inst_container
    for shift in range(2, 2 + cf._RESIDENT_MAX):
        cf._compact_prob(topo, np.roll(placement, shift))
    assert len(cf._RESIDENT) == cf._RESIDENT_MAX
    assert cf._compact_prob(topo, moved).inst_container is not prob.inst_container
