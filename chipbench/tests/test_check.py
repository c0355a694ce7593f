"""The output check: it passes the program, fails the control, and fails a
run whose timed path is broken underneath.

Everything runs on the CPU at a short horizon; the mixes are used with
``T`` cut so a test run holds them."""
import numpy as np
import pytest

from chipbench import deploy, traffic
from chipbench import run as bench

SHORT = dict(T=48, warmup=0)
MIXES = {  # (configuration, mix): test-sized cuts
    ("potus-paper-k4", "potus-poisson"): SHORT,
    ("potus-paper-k4", "shuffle-poisson"): SHORT,
}
CELLS = [c["name"] for c in bench.load_benchmark()["workloads"]]
SEEDS = (3, 2**31 + 11)


def _short(monkeypatch, cell):
    """Cut a cell's mix to a test-sized horizon, for every caller."""
    c = bench.find_cell(bench.load_benchmark(), cell)
    mix = dict(traffic.load_mix(c["traffic"]), **MIXES[(c["config"], c["traffic"])])
    monkeypatch.setattr(traffic, "load_mix", lambda name: dict(mix))


def _call_once(mix, config, seed):
    dep = deploy.build_deployment(config)
    topo, net, pl = deploy.program_inputs(dep)
    call, _ = bench.make_call(mix, topo, net, pl)
    inputs = traffic.draw(mix, dep.rates, seed)[0]
    return dep, inputs, call(inputs)


@pytest.mark.parametrize("config,mix_name", list(MIXES))
@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_and_control_fails(config, mix_name, seed):
    mix = dict(traffic.load_mix(mix_name), **MIXES[(config, mix_name)])
    dep, inputs, got = _call_once(mix, config, seed)
    numbers = bench.compare(mix, dep, inputs, got)
    assert numbers.keys() == mix["limits"].keys()
    ok, checks = bench.judge(numbers, mix["limits"])
    assert ok, checks
    ok, checks = bench.judge(bench.compare(mix, dep, inputs), mix["limits"])
    assert not ok, checks


def _run_broken(monkeypatch, cell):
    _short(monkeypatch, cell)
    line, _ = bench.run(cell, SEEDS[0], 0.01, False, require_tpu=False)
    return line


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_a_broken_step_is_not_correct(monkeypatch, break_step, cell, fault):
    break_step(fault)
    line = _run_broken(monkeypatch, cell)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out_is_not_correct(monkeypatch, fresh_jit, cell):
    """Half of the batch is dropped and the rest stands for it: a run drops
    half of its spout instances' arrivals and doubles the others'."""
    from repro.core import cohort_fused

    prep = cohort_fused._prep_streams

    def half(actual, predicted, *a, **kw):
        actual = np.array(actual)
        rows = np.nonzero(actual.sum(axis=(0, 2)))[0]
        actual[:, rows[::2]] = 0.0
        actual[:, rows[1::2]] *= 2.0
        return prep(actual, predicted, *a, **kw)

    monkeypatch.setattr(cohort_fused, "_prep_streams", half)
    line = _run_broken(monkeypatch, cell)
    assert line["correct"] is False, line["checks"]


def test_no_chip_no_result(capsys):
    assert bench.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""
