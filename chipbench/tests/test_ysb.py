"""The Yahoo Streaming Benchmark configuration (``ysb-storm-10node``, kind
``chipbench/deployments/ysb.py``): its data, the program inputs it hands
over (the fields grouping native, not pinned), its two cells at a short
horizon, a fault on the keyed edge, and the keyed-landing metric's map."""
import dataclasses
import importlib
import json
import os
import warnings

import numpy as np
import pytest

from chipbench import deploy, placement, traffic
from chipbench import run as bench

CONFIG = "ysb-storm-10node"
CELLS = ("ysb-potus-poisson", "ysb-shuffle-poisson")
SEED = 2**31 + 13


def _java_list_hash(items) -> int:
    """Java ``List.hashCode`` of strings (``String.hashCode``), as int32."""
    h = 1
    for s in items:
        sh = 0
        for ch in s:
            sh = (31 * sh + ord(ch)) & 0xFFFFFFFF
        h = (31 * h + sh) & 0xFFFFFFFF
    return h - (1 << 32) if h >= 1 << 31 else h


def test_the_key_shares_are_storms_hash_of_the_campaign_ids():
    cfg = deploy.load_config(CONFIG)
    ids = cfg["assumed"]["campaign_ids"]
    count = cfg["apps"][0][-1]
    tasks = np.array([_java_list_hash([c]) % count["parallelism"] for c in ids])  # floorMod
    assert len(set(ids)) == 100
    want = np.bincount(tasks, minlength=count["parallelism"]) / len(ids)
    np.testing.assert_array_equal(np.asarray(count["key_shares"]), want)


def test_the_program_takes_the_fields_grouping_natively():
    dep = deploy.build_deployment(CONFIG)
    topo, net, pl = deploy.program_inputs(dep)
    assert (topo.n_instances, topo.n_components, net.U.shape[0]) == (154, 6, 20)
    assert topo.comp_keyed.tolist() == [False] * 5 + [True]
    assert topo.comp_parallelism.tolist() == [22] * 5 + [44]
    np.testing.assert_allclose(topo.comp_done_share, [0, 0, 2 / 3, 0, 0, 1], rtol=1e-7)
    np.testing.assert_array_equal(dep.U, net.U)
    assert set(np.unique(dep.U)) == {0.0, 1.0, 2.0}
    # the placement is T-Heron's, at most 8 instances a container
    np.testing.assert_array_equal(pl, placement.t_heron(dep, dep.rates, 8))
    assert np.bincount(pl).max() <= 8
    # the busiest instance (event_deserializer, mu 3) runs at utilization 0.7
    from repro.core import feasible_rates

    np.testing.assert_allclose(dep.rates, feasible_rates(topo, 0.7), rtol=1e-12)
    load = topo.expected_rates(dep.rates, per_instance=True) / np.maximum(topo.inst_mu, 1e-9)
    assert load.max() == pytest.approx(0.7)


@pytest.fixture
def short_mixes(monkeypatch):
    """The cells' mixes at T=64 with no warmup, so a CPU run is short."""
    load = traffic.load_mix
    monkeypatch.setattr(traffic, "load_mix",
                        lambda name: dict(load(name), T=64, warmup=0))


def _run(cell):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # age-cap saturation under POTUS at V=1
        return bench.run(cell, SEED, 0.01, False, require_tpu=False)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_runs_correct(short_mixes, cell):
    line, checks = _run(cell)
    assert line["correct"] is True, checks
    mix = traffic.load_mix(next(c["traffic"] for c in bench.load_benchmark()["workloads"]
                                if c["name"] == cell))
    assert checks.keys() == mix["limits"].keys()


def test_the_keyed_edge_split_evenly_is_not_correct(short_mixes, monkeypatch):
    """The kind hands the program its keyed edge split evenly over the 44
    tasks; the reference keeps the configuration's shares."""
    from chipbench import lookup

    kind = lookup.module(deploy.KINDS, "ysb", "deployment kind")
    real = kind.program_inputs

    def even(dep):
        apps = [[dict(c, key_shares=[1.0 / c["parallelism"]] * c["parallelism"])
                 if c.get("key_shares") else c for c in comps] for comps in dep.cfg["apps"]]
        return real(dataclasses.replace(dep, cfg=dict(dep.cfg, apps=apps)))

    monkeypatch.setattr(kind, "program_inputs", even)
    monkeypatch.setattr(deploy, "kind_module", lambda cfg: kind)
    line, checks = _run("ysb-shuffle-poisson")
    assert line["correct"] is False, checks


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "altered", "half the batch"])
def test_a_broken_run_of_a_cell_is_not_correct(short_mixes, monkeypatch, break_step,
                                               cell, fault):
    """``test_check.py``'s faults on the YSB cells: a step that returns its
    state unchanged, an altered backlog sample, and half of the spouts'
    arrivals dropped with the rest doubled."""
    if fault == "half the batch":
        from repro.core import cohort_fused

        prep = cohort_fused._prep_streams

        def half(actual, predicted, *a, **kw):
            actual = np.array(actual)
            rows = np.nonzero(actual.sum(axis=(0, 2)))[0]
            actual[:, rows[::2]] = 0.0
            actual[:, rows[1::2]] *= 2.0
            return prep(actual, predicted, *a, **kw)

        monkeypatch.setattr(cohort_fused, "_prep_streams", half)
    else:
        break_step(fault)
    line, checks = _run(cell)
    assert line["correct"] is False, checks


def test_the_keyed_metric_maps_the_keyed_scope():
    """On the CPU's compiled scan: operations scoped ``drain/keyed`` map to
    ``keyed`` for the YSB deployment, and none for the paper's."""
    from repro.core import cohort_fused
    from repro.obs import trace as program_trace

    reader = importlib.import_module("chipbench.metrics.keyed_land_device_us_per_slot")
    found = {}
    for config in (CONFIG, "potus-paper-k4"):
        dep = deploy.build_deployment(config)
        topo, net, pl = deploy.program_inputs(dep)
        mix = dict(traffic.load_mix("shuffle-poisson"), T=16, draws=1)
        call, _ = bench.make_call(mix, topo, net, pl)
        program_trace.enable_tracing()
        try:
            call(traffic.draw(mix, dep.rates, 3)[0])
        finally:
            program_trace.disable_tracing()
        ops = reader.keyed_ops(cohort_fused.scan_hlo_text())
        found[config] = [op for op, scope in ops.items() if scope]
    assert found[CONFIG] and not found["potus-paper-k4"]
    assert reader.read({"slots": 0, "log_dir": "/nonexistent"}) is None


def test_the_benchmark_names_the_cells_and_metric():
    bench_file = bench.load_benchmark()
    cells = {c["name"]: c for c in bench_file["workloads"]}
    assert all(cells[c]["config"] == CONFIG and cells[c]["chips"] == 1 for c in CELLS)
    metric = next(m for m in bench_file["per_layer"]
                  if m["name"] == "keyed_land_device_us_per_slot")
    assert metric["workloads"] == list(CELLS)
    cfg = next(c for c in bench_file["configs"] if c["name"] == CONFIG)
    assert os.path.isfile(os.path.join(deploy.ROOT, cfg["file"]))
    assert set(cfg["reduced"]) == set(json.load(open(os.path.join(deploy.ROOT, cfg["file"])))
                                      ["reduced"])
