"""Ahead-of-time compiles of the main path for a described TPU v5e.

Nothing runs: each test lowers and compiles a jitted step or kernel for a
v5e chip that is described, not attached, with shapes only. That is what
the chip's compiler would accept or refuse — VMEM overflows, Mosaic
lowering errors, programs that do not fit HBM — at no chip time. The
topology is described inside a fixture (never while a module is imported),
and the persistent compile cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.systems_bench import _cohort_fleet
from repro.core import container_costs, fat_tree, make_problem
from repro.core import cohort_fused as cf
from repro.kernels.potus_schedule import potus_schedule_call
from repro.kernels.potus_slot import potus_slot_call

V5E_HBM = 16 * 2**30
I_FLEET = 16384  # the fleet cells' size
I_SLOT_KERNEL = 256  # largest _cohort_fleet size the grid-less slot kernel fits
T, W, AGE_CAP = 128, 4, 32


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _fleet_inputs(I):
    """The fused engine's slot-invariant inputs for ``_cohort_fleet(I)`` on
    ``fat_tree(4)`` with 8 containers per server (K=128)."""
    topo = _cohort_fleet(I)
    sd, _ = fat_tree(4)
    net = container_costs(f"cohort-fleet-{I}", sd, containers_per_server=8)
    placement = np.random.default_rng(0).integers(0, net.n_containers, I).astype(np.int32)
    cpt = cf._compact(topo)
    return topo, net, placement, cpt, cf._device_inputs(topo, net, cpt)


def _state(I, S, C, L, n=None):
    lead = () if n is None else (n,)
    Atot = AGE_CAP + W + 1
    f32 = jnp.float32
    return tuple(jax.ShapeDtypeStruct(lead + s, f32) for s in (
        (I, S, W + 1), (I, S), (I, Atot), (I, S, Atot), (I, Atot), (C, L), (C, L)))


def _fits(compiled):
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_fleet_scan_compiles(one_chip):
    """The main path: one chunk of the compact XLA scan at fleet scale."""
    topo, net, placement, cpt, dev = _fleet_inputs(I_FLEET)
    I, C = topo.n_instances, topo.n_components
    prob = cf._compact_prob(topo, placement)
    L = cpt.lanes.shape[1]
    arr = jax.ShapeDtypeStruct((T + W + 1, L), jnp.float32, sharding=one_chip)
    carry = _state(I, cpt.S, C, T + AGE_CAP + W + 1, n=1)[:5]  # the queues
    states = _shapes(carry, one_chip)
    f = partial(cf._scan_cohort_fused, edges=cpt.edges, scheduler="potus",
                age_cap=AGE_CAP, n_components=C, shared_inputs=True)
    compiled = jax.jit(f).lower(
        _shapes(prob, one_chip), states, pred_s=arr, actual_s=None,
        Vs=jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip),
        betas=jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip),
        **_shapes(dev, one_chip)).compile()
    assert _fits(compiled)


@pytest.mark.parametrize("scheduler", ["potus", "shuffle"])
def test_keyed_scan_compiles(one_chip, scheduler):
    """The compact XLA scan with a fields grouping (DESIGN.md §15), at the
    Yahoo Streaming Benchmark's shape: five stages of 22 instances on
    shuffle edges, then 44 keyed tasks; the keyed landing keeps its scope."""
    from repro.core import Component, build_topology

    stages = [Component(f"s{d}", 0, d == 0, 22, 4.0, successors=(d + 1,)) for d in range(5)]
    shares = np.bincount(np.arange(100) * 7 % 44, minlength=44) / 100
    topo = build_topology([stages + [Component("count", 0, False, 44, 4.0,
                                               key_shares=tuple(shares))]], gamma=24.0)
    net = container_costs("ysb", fat_tree(4)[0])
    placement = np.arange(topo.n_instances, dtype=np.int32) % net.n_containers
    cpt = cf._compact(topo)
    dev = cf._device_inputs(topo, net, cpt)
    I, C = topo.n_instances, topo.n_components
    arr = jax.ShapeDtypeStruct((T + W + 1, cpt.lanes.shape[1]), jnp.float32, sharding=one_chip)
    f = partial(cf._scan_cohort_fused, edges=cpt.edges, scheduler=scheduler,
                age_cap=AGE_CAP, n_components=C, shared_inputs=True)
    compiled = jax.jit(f).lower(
        _shapes(cf._compact_prob(topo, placement), one_chip),
        _shapes(_state(I, cpt.S, C, T + AGE_CAP + W + 1, n=1)[:5], one_chip),
        pred_s=arr, actual_s=None,
        Vs=jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip),
        betas=jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip),
        **_shapes(dev, one_chip)).compile()
    assert "keyed" in dev and "/keyed/" in compiled.as_text()
    assert _fits(compiled)


def test_potus_schedule_kernel_compiles(one_chip):
    """The dense Algorithm-1 kernel behind ``use_pallas`` on the scan engines."""
    topo, net, placement, _, _ = _fleet_inputs(I_FLEET)
    I, C, K = topo.n_instances, topo.n_components, net.n_containers
    prob = make_problem(topo, net, placement)
    sds = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    f = partial(potus_schedule_call, interpret=False)
    compiled = jax.jit(f).lower(
        sds((K, K), jnp.float32), sds((I,), jnp.float32), sds((I, C), jnp.float32),
        sds((I,), jnp.int32), sds((I,), jnp.int32), sds((I, I), prob.edge_mask.dtype),
        sds((I,), jnp.float32), sds((), jnp.float32), sds((), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)


def test_slot_kernel_compiles(one_chip):
    """The grid-less one-dispatch slot kernel at the largest fleet whose
    whole slot fits VMEM (I=512 needs 18.7 MB of scoped VMEM, past the
    16 MiB limit)."""
    topo, net, placement, cpt, dev = _fleet_inputs(I_SLOT_KERNEL)
    I, C = topo.n_instances, topo.n_components
    prob = cf._compact_prob(topo, placement)
    comp_onehot = jax.nn.one_hot(prob.inst_comp, C, dtype=jnp.float32)
    consts = cf._step_consts(prob, comp_onehot, dev["U"], dev["mu"], dev["inv_service"],
                             dev["sel_cmp"], dev["stream_cmp"], dev["valid_cmp"],
                             dev["succ_map"], dev["term_f"], dev["adj_rows"],
                             jnp.float32(2.0), jnp.float32(1.0))
    n_slots = 1
    arr = jax.ShapeDtypeStruct((n_slots, I, C), jnp.float32, sharding=one_chip)
    states = _shapes(_state(I, cpt.S, C, n_slots + AGE_CAP + W + 1), one_chip)
    f = partial(potus_slot_call, age_cap=AGE_CAP, n_slots=n_slots, interpret=False)
    compiled = jax.jit(f).lower(
        _shapes(consts, one_chip), states, arr, arr, arr,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
