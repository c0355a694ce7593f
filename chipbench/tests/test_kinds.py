"""A configuration's own deployment kind, reference and arrival process.

A kind and a process enter a run as new files only: the test copies those
of ``chipbench/tests/kinds/`` (a keyed DAG whose reference routes the keyed
edge by its fixed shares, and on-off bursts) to where the harness looks.
The paper's configuration takes the path it took before, to the bit, and a
missing file fails the run and is named."""
import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from chipbench import deploy, traffic
from chipbench import run as bench

FILES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kinds")
MIXES = ("keyed-shuffle", "keyed-potus")
SEED = 2**31 + 7


@pytest.fixture
def new_files(tmp_path, monkeypatch):
    """The harness's lookup directories moved to a copy of ``kinds/``, and
    ``add_cell(config, mix)``, which adds a cell to the benchmark."""
    root = tmp_path / "chipbench"
    shutil.copytree(FILES, root)
    monkeypatch.setattr(deploy, "CONFIGS", str(root / "configs"))
    monkeypatch.setattr(deploy, "KINDS", str(root / "deployments"))
    monkeypatch.setattr(traffic, "MIXES", str(root / "traffic"))
    monkeypatch.setattr(traffic, "PROCESS_FILES", str(root / "processes"))
    benchmark = bench.load_benchmark()

    def add_cell(config, mix):
        name = f"{config}.{mix}"
        benchmark["workloads"].append({"name": name, "config": config, "traffic": mix,
                                       "chips": 1, "why": "a test cell"})
        return name

    monkeypatch.setattr(bench, "load_benchmark", lambda: benchmark)
    return root, add_cell


@pytest.mark.parametrize("mix", MIXES)
def test_a_new_kind_and_process_run_correct(new_files, mix):
    _, add_cell = new_files
    line, checks = bench.run(add_cell("keyed-tiny", mix), SEED, 0.01, False,
                             require_tpu=False)
    assert line["correct"] is True, checks
    assert checks.keys() == traffic.load_mix(mix)["limits"].keys()
    dep = deploy.build_deployment("keyed-tiny")
    assert dep.n_components == 5  # the keyed component's 3 instances are pinned
    assert deploy.reference_of(dep).__module__ == "chipbench.deployments.keyed"
    assert traffic.process("onoff").__module__ == "chipbench.processes.onoff"


def _split_evenly(monkeypatch):
    """The kind hands the program its keyed edge split evenly, not by the
    shares the configuration states."""
    from chipbench import lookup

    kind = lookup.module(deploy.KINDS, "keyed", "deployment kind")

    def even(apps):
        return [[dict(c, key_shares=[1.0 / len(c["key_shares"])] * len(c["key_shares"]))
                 if "key_shares" in c else c for c in comps] for comps in apps]

    kind.program_inputs = lambda dep: deploy.layered_fat_tree_inputs(
        dep, kind.pin(even(dep.cfg["apps"])))
    monkeypatch.setattr(deploy, "kind_module", lambda cfg: kind)


@pytest.mark.parametrize("fault", ["unchanged", "altered", "keyed edge split evenly"])
def test_a_broken_run_of_the_new_kind_is_not_correct(new_files, monkeypatch, break_step,
                                                     fault):
    _, add_cell = new_files
    if fault == "keyed edge split evenly":
        _split_evenly(monkeypatch)
    else:
        break_step(fault)
    line, _ = bench.run(add_cell("keyed-tiny", "keyed-shuffle"), SEED, 0.01, False,
                        require_tpu=False)
    assert line["correct"] is False, line["checks"]


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


#: digests of what the paper's configuration gave before configurations
#: could name a kind (CPU; draws of a mix at seeds 3 and 2**31 + 11)
K4_BEFORE = {
    "deployment": "868b949ae44a57de",
    "program inputs": "0ae59b4ddadf3597",
    ("potus-poisson", 3): "aba2f7357a3ac7c4",
    ("potus-poisson", 2**31 + 11): "d5ef9c8f5af7289a",
    ("shuffle-poisson", 3): "aba2f7357a3ac7c4",
    ("shuffle-poisson", 2**31 + 11): "d5ef9c8f5af7289a",
}


@pytest.mark.parametrize("what", list(K4_BEFORE), ids=str)
def test_the_paper_configuration_gives_what_it_gave_before(what):
    dep = deploy.build_deployment("potus-paper-k4")
    if what == "deployment":
        got = _digest(dep.comp_is_spout, dep.comp_parallelism, dep.comp_mu, dep.adj,
                      dep.selectivity, dep.inst_comp, np.float64(dep.gamma), dep.U,
                      dep.placement, dep.rates)
    elif what == "program inputs":
        topo, net, pl = deploy.program_inputs(dep)
        got = _digest(topo.comp_app, topo.comp_is_spout, topo.comp_parallelism, topo.adj,
                      topo.selectivity, topo.inst_comp, topo.inst_mu, topo.inst_gamma,
                      net.server_dist, net.container_server, net.U, pl)
    else:
        mix, seed = what
        assert traffic.load_mix(mix)["arrivals"]["process"] in traffic.PROCESSES
        got = _digest(*(d["actual"] for d in traffic.draw(traffic.load_mix(mix),
                                                            dep.rates, seed)))
    assert got == K4_BEFORE[what]
    assert deploy.reference_of(dep).__file__ == os.path.join(deploy.HERE, "reference.py")


@pytest.mark.parametrize("missing", ["deployments", "processes"])
def test_a_missing_file_fails_the_run_and_is_named(new_files, missing):
    root, add_cell = new_files
    config, mix = "keyed-tiny", "keyed-shuffle"
    if missing == "deployments":
        cfg = json.loads((root / "configs" / f"{config}.json").read_text())
        config = "keyed-nosuch"
        (root / "configs" / f"{config}.json").write_text(json.dumps(dict(cfg, kind="nosuch")))
    else:
        m = json.loads((root / "traffic" / f"{mix}.json").read_text())
        mix = "keyed-nosuch"
        (root / "traffic" / f"{mix}.json").write_text(
            json.dumps(dict(m, arrivals={"process": "nosuch"})))
    with pytest.raises(FileNotFoundError, match=str(root / missing / "nosuch.py")):
        bench.run(add_cell(config, mix), SEED, 0.01, False, require_tpu=False)
