"""The deployments: placement, fabric and rates, and what a seed may change."""
import numpy as np
import pytest

from chipbench import deploy, placement, traffic


def test_t_heron_copy_equals_the_program_on_the_paper_deployment():
    from repro.core import feasible_rates, t_heron_placement

    dep = deploy.build_deployment("potus-paper-k4")
    topo, net, _ = deploy.program_inputs(dep)
    rates = feasible_rates(topo, utilization=0.7)
    want = t_heron_placement(topo, net, rates, max_per_container=8)
    got = placement.t_heron(dep, dep.rates, 8)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(dep.placement, want)


def test_fabric_and_rates_equal_the_programs():
    from repro.core import container_costs, fat_tree, feasible_rates

    dep = deploy.build_deployment("potus-paper-k4")
    topo, net, _ = deploy.program_inputs(dep)
    np.testing.assert_array_equal(dep.U, container_costs("fat-tree", fat_tree(dep.cfg["fabric_k"])[0]).U)
    np.testing.assert_allclose(dep.rates, feasible_rates(topo, 0.7), rtol=1e-12, atol=0)


@pytest.mark.parametrize("mix_name", ["potus-poisson", "shuffle-poisson"])
def test_seed_changes_traffic_and_never_the_deployment(mix_name):
    mix = dict(traffic.load_mix(mix_name), T=16, draws=1)
    deps = [deploy.build_deployment("potus-paper-k4") for _ in range(2)]
    for field in ("inst_comp", "U", "placement", "rates", "adj", "selectivity"):
        np.testing.assert_array_equal(getattr(deps[0], field), getattr(deps[1], field))
    a, b, a2 = (traffic.draw(mix, deps[0].rates, s)[0]["actual"] for s in (1, 2**31 + 5, 1))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, a2)
    # arrivals only on the spout streams
    assert (a.sum(axis=0)[deps[0].rates == 0] == 0).all()
