"""Ahead-of-time compiles of the main path for a described TPU v5e.

Nothing runs: each test lowers and compiles a jitted step or kernel for a
v5e chip that is described, not attached, with shapes only. That is what
the chip's compiler would accept or refuse — VMEM overflows, Mosaic
lowering errors, programs that do not fit HBM — at no chip time. The
topology is described inside a fixture (never while a module is imported),
and the persistent compile cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
import re
from functools import cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.systems_bench import _cohort_fleet
from repro.core import container_costs, fat_tree, make_problem
from repro.core import cohort_fused as cf
from repro.core.topology import random_apps
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


def _paper_deployment():
    """The POTUS paper's shape: the seed-0 layered DAGs (I=83, C=27) on a
    k=4 fat-tree with 2 containers per server (K=32)."""
    from repro.core import build_topology

    topo = build_topology(random_apps(np.random.default_rng(0)), gamma=24.0)
    net = container_costs("k4", fat_tree(4)[0], containers_per_server=2)
    return topo, net, np.arange(topo.n_instances, dtype=np.int32) % net.n_containers


def _keyed_deployment():
    """The Yahoo Streaming Benchmark's shape: five stages of 22 instances on
    shuffle edges, then 44 tasks on a fields grouping."""
    from repro.core import Component, build_topology

    stages = [Component(f"s{d}", 0, d == 0, 22, 4.0, successors=(d + 1,)) for d in range(5)]
    shares = np.bincount(np.arange(100) * 7 % 44, minlength=44) / 100
    topo = build_topology([stages + [Component("count", 0, False, 44, 4.0,
                                               key_shares=tuple(shares))]], gamma=24.0)
    net = container_costs("ysb", fat_tree(4)[0])
    return topo, net, np.arange(topo.n_instances, dtype=np.int32) % net.n_containers


_DEPLOYMENTS = {"paper": _paper_deployment, "keyed": _keyed_deployment}


@cache
def _deployment_scan(sharding, deployment, scheduler):
    """One chunk of the compact XLA scan for ``deployment``, compiled once
    per module: ``(topology, device inputs, compiled)``."""
    topo, net, placement = _DEPLOYMENTS[deployment]()
    cpt = cf._compact(topo)
    dev = cf._device_inputs(topo, net, cpt)
    I, C = topo.n_instances, topo.n_components
    arr = jax.ShapeDtypeStruct((T + W + 1, cpt.lanes.shape[1]), jnp.float32, sharding=sharding)
    f = partial(cf._scan_cohort_fused, edges=cpt.edges, scheduler=scheduler,
                age_cap=AGE_CAP, n_components=C, shared_inputs=True)
    compiled = jax.jit(f).lower(
        _shapes(cf._compact_prob(topo, placement), sharding),
        _shapes(_state(I, cpt.S, C, T + AGE_CAP + W + 1, n=1)[:5], sharding),
        pred_s=arr, actual_s=None,
        Vs=jax.ShapeDtypeStruct((1,), jnp.float32, sharding=sharding),
        betas=jax.ShapeDtypeStruct((1,), jnp.float32, sharding=sharding),
        **_shapes(dev, sharding)).compile()
    return topo, dev, compiled


@pytest.mark.parametrize("scheduler", ["potus", "shuffle"])
def test_keyed_scan_compiles(one_chip, scheduler):
    """The compact XLA scan with a fields grouping (DESIGN.md §15), at the
    Yahoo Streaming Benchmark's shape; the keyed landing keeps its scope."""
    _, dev, compiled = _deployment_scan(one_chip, "keyed", scheduler)
    assert "keyed" in dev and "/keyed/" in compiled.as_text()
    assert _fits(compiled)


_INDEX_OP = re.compile(r"= (\w+)\[([\d,]*)\]\S* (gather|scatter)\((.*)$")


def _per_element_index_ops(hlo_text, scope, sizes):
    """The gathers and scatters under ``scope`` that move one element per
    index into or out of an array of one of ``sizes`` elements (flattened or
    not): a gather whose slice is a single element, a scatter with no update
    window. Row gathers and windowed per-component scatters move a row or a
    column per index and are not listed."""
    found = []
    for line in hlo_text.splitlines():
        m = _INDEX_OP.search(line)
        if m is None or scope not in line:
            continue
        n = int(np.prod([int(d) for d in m.group(2).split(",") if d]))
        if m.group(3) == "gather":
            per_element = re.search(r"slice_sizes=\{[1,]*\}", m.group(4)) is not None
        else:
            per_element = "update_window_dims={}" in m.group(4)
        if per_element and n in sizes:
            found.append(line.strip())
    return found


@pytest.mark.parametrize("deployment", ["paper", "keyed"])
def test_decide_has_no_per_element_index_op(one_chip, deployment):
    """The compact POTUS decision (DESIGN.md §12.2) places streams, unsorts
    the water-fill and prices the point target without a per-element
    scatter or gather on an (I, C)- or (I, C+1)-sized array: the TPU runs
    those one index at a time, and they were most of the decision's time."""
    topo, _, compiled = _deployment_scan(one_chip, deployment, "potus")
    I, C = topo.n_instances, topo.n_components
    text = compiled.as_text()
    assert "/decide/" in text
    assert _per_element_index_ops(text, "/decide/", {I * C, I * (C + 1)}) == []


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
