"""A deployment, built from its configuration file.

A configuration (``chipbench/configs/<name>.json``) states the whole
deployment as data. Nothing a deployment holds depends on the traffic seed,
so a seed can change what arrives but never what is deployed.

A configuration that names no ``kind`` is the POTUS paper's: layered
application DAGs (components, parallelism, per-instance capacity ``mu``,
successors and selectivities) on a fat-tree, the transmission budget
``gamma``, the placement (a data file beside the configuration) and the
utilization the spout rates are set at. ``Deployment`` holds the plain
arrays the traffic generator and the reference read; ``program_inputs``
converts it into the objects the system under test takes. The fabric and
the rate arithmetic are the benchmark's own copies, so the deployment a cell
measures cannot move with the program.

A configuration that names ``"kind": "<kind>"`` brings its own code in
``chipbench/deployments/<kind>.py``, a new file that needs no edit here:

* ``build(cfg, read_placement)`` returns the deployment. ``cfg`` is the
  configuration file's object, its ``name`` set to the configuration's name.
  What it returns carries what the harness reads: ``cfg`` (whose ``kind``
  leads :func:`program_inputs` and :func:`reference_of` back to the module),
  ``rates`` ((I, C) mean arrivals per spout stream, which ``traffic.draw``
  offers) and, for ``chipbench/reference.py`` and ``chipbench/placement.py``,
  the other fields and properties of :class:`Deployment`. A ``Deployment``
  carries all of them.
* ``program_inputs(dep)`` returns ``(topology, network costs, placement)``
  for the system under test.
* ``reference``, where the module has one, is the plain reference that
  ``run.compare`` judges the kind by: an object with ``Model`` and ``run`` of
  the signatures of ``chipbench/reference.py``, which it takes otherwise. It
  imports nothing of the program.

A kind whose file is missing fails the run and names the file.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from chipbench import lookup
from chipbench import reference as plain_reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIGS = os.path.join(HERE, "configs")
KINDS = os.path.join(HERE, "deployments")


@dataclasses.dataclass(frozen=True)
class Deployment:
    name: str
    cfg: dict
    comp_is_spout: np.ndarray  # (C,) bool
    comp_parallelism: np.ndarray  # (C,) int
    comp_mu: np.ndarray  # (C,) tuples/slot per bolt instance
    adj: np.ndarray  # (C, C) bool
    selectivity: np.ndarray  # (C, C) tuples to c' per tuple processed at c
    inst_comp: np.ndarray  # (I,) component-major instance order
    gamma: float  # per-instance transmission budget
    U: np.ndarray  # (K, K) per-tuple cost between containers
    placement: np.ndarray  # (I,) container of each instance
    rates: np.ndarray  # (I, C) mean arrivals per (spout instance, successor)

    @property
    def n_instances(self) -> int:
        return int(self.inst_comp.shape[0])

    @property
    def n_components(self) -> int:
        return int(self.comp_is_spout.shape[0])

    @property
    def n_containers(self) -> int:
        return int(self.U.shape[0])


def load_config(name: str) -> dict:
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def kind_module(cfg: dict):
    """The module of the configuration's deployment kind, ``None`` where it
    names none."""
    kind = cfg.get("kind")
    return None if kind is None else lookup.module(KINDS, kind, "deployment kind")


def flatten_apps(apps: list) -> dict:
    """Per-app component lists (successors indexed within the app) ->
    global component arrays, in the order the apps list them."""
    is_spout, par, mu = [], [], []
    edges = []
    base = 0
    for comps in apps:
        for ci, comp in enumerate(comps):
            is_spout.append(bool(comp["is_spout"]))
            par.append(int(comp["parallelism"]))
            mu.append(float(comp["mu"]))
            for s, f in zip(comp["successors"], comp["selectivity"]):
                edges.append((base + ci, base + int(s), float(f)))
        base += len(comps)
    C = base
    adj = np.zeros((C, C), bool)
    sel = np.zeros((C, C), np.float32)
    for c, c2, f in edges:
        adj[c, c2] = True
        sel[c, c2] = f
    return dict(comp_is_spout=np.array(is_spout), comp_parallelism=np.array(par),
                comp_mu=np.array(mu), adj=adj, selectivity=sel)


def fat_tree_server_hops(k: int) -> np.ndarray:
    """(S, S) link hops between the S = k³/4 servers of a k-ary fat-tree
    (Al-Fares et al., SIGCOMM 2008): k/2 servers per edge switch, k/2 edge
    switches per pod. Same edge switch: 2 hops; same pod: 4; other pod: 6."""
    half = k // 2
    s = np.arange(k * half * half)
    edge = s // half
    pod = edge // half
    d = np.where(pod[:, None] == pod[None, :], 4.0, 6.0)
    d = np.where(edge[:, None] == edge[None, :], 2.0, d)
    np.fill_diagonal(d, 0.0)
    return d.astype(np.float32)


def container_cost_matrix(server_hops: np.ndarray, per_server: int,
                          intra_server_cost: float) -> np.ndarray:
    """(K, K) cost between containers: 0 within one, ``intra_server_cost``
    between two on one server, else the servers' hop count."""
    server = np.repeat(np.arange(server_hops.shape[0]), per_server)
    U = server_hops[np.ix_(server, server)]
    U = np.where(server[:, None] == server[None, :], intra_server_cost, U)
    np.fill_diagonal(U, 0.0)
    return U.astype(np.float32)


def topo_order(adj: np.ndarray) -> list[int]:
    indeg = adj.sum(axis=0).astype(int)
    stack = [c for c in range(adj.shape[0]) if indeg[c] == 0]
    order = []
    while stack:
        c = stack.pop()
        order.append(c)
        for c2 in np.nonzero(adj[c])[0]:
            indeg[c2] -= 1
            if indeg[c2] == 0:
                stack.append(int(c2))
    return order


def spout_rate_matrix(comps: dict, inst_comp: np.ndarray, per_stream: float) -> np.ndarray:
    """(I, C): ``per_stream`` on every (spout instance, successor) stream."""
    spout_rows = comps["comp_is_spout"][inst_comp]
    return (comps["adj"][inst_comp] & spout_rows[:, None]).astype(np.float64) * per_stream


def processed_rates(spout: np.ndarray, adj: np.ndarray, sel: np.ndarray,
                    direct: np.ndarray) -> np.ndarray:
    """(C,) tuples each bolt component processes per slot, given ``direct``
    (C,) spout tuples sent to it: inflow from spouts plus what upstream bolts
    process times the edge's selectivity."""
    through = np.zeros(len(spout))
    for c in topo_order(adj):
        if spout[c]:
            continue
        inflow = direct[c]
        for p in np.nonzero(adj[:, c])[0]:
            if not spout[p]:
                inflow += through[p] * sel[p, c]
        through[c] = inflow
    return through


def utilization_rates(comps: dict, inst_comp: np.ndarray, gamma: float,
                      utilization: float) -> np.ndarray:
    """Per-stream spout rates at which the busiest resource (a component's
    processing, parallelism × mu, or an instance's transmission, gamma) runs
    at ``utilization`` — the paper's §5.1 operating point."""
    sel = comps["selectivity"]
    spout, par, mu = comps["comp_is_spout"], comps["comp_parallelism"], comps["comp_mu"]
    unit = spout_rate_matrix(comps, inst_comp, 1.0)
    through = processed_rates(spout, comps["adj"], sel, unit.sum(axis=0))
    worst = 0.0
    for c in range(len(spout)):
        if spout[c]:
            rows = inst_comp == c
            worst = max(worst, float(np.max(unit[rows].sum(axis=1) / gamma)))
        else:
            worst = max(worst, through[c] / (par[c] * mu[c]))
            worst = max(worst, through[c] * sel[c].sum() / par[c] / gamma)
    return unit * (utilization / worst)


def build_deployment(name: str, read_placement: bool = True) -> Deployment:
    """The deployment of configuration ``name``, its placement read from the
    data file the configuration names (left empty for the placement tool,
    which computes it); a kind's ``build`` where the configuration names one."""
    cfg = load_config(name)
    kind = kind_module(cfg)
    if kind is not None:
        return kind.build(dict(cfg, name=name), read_placement)
    return layered_fat_tree(name, cfg, read_placement)


def layered_fat_tree(name: str, cfg: dict, read_placement: bool) -> Deployment:
    """The paper's kind: the layered DAGs of ``cfg["apps"]`` on the
    configuration's fat-tree, at its utilization."""
    comps = flatten_apps(cfg["apps"])
    inst_comp = np.repeat(np.arange(len(comps["comp_parallelism"])),
                          comps["comp_parallelism"]).astype(np.int32)
    fabric = cfg["fabric"]
    if fabric["kind"] != "fat-tree":
        raise ValueError(f"unknown fabric {fabric['kind']!r}")
    U = container_cost_matrix(fat_tree_server_hops(int(cfg["fabric_k"])),
                              int(fabric["containers_per_server"]),
                              float(fabric["intra_server_cost"]))
    gamma = float(cfg["gamma"])
    rates = utilization_rates(comps, inst_comp, gamma, float(cfg["utilization"]))
    placement = np.zeros(0, np.int32)
    if read_placement:
        placement = np.load(os.path.join(ROOT, cfg["placement"]["file"])).astype(np.int32)
        if placement.shape != inst_comp.shape or placement.max() >= U.shape[0]:
            raise ValueError(f"placement of {name} does not fit its deployment")
    return Deployment(name=name, cfg=cfg, inst_comp=inst_comp, gamma=gamma, U=U,
                      placement=placement, rates=rates, **comps)


def program_inputs(dep: Deployment):
    """(Topology, NetworkCosts, placement) of the system under test; a
    kind's ``program_inputs`` where the deployment's configuration names one."""
    kind = kind_module(dep.cfg)
    if kind is not None:
        return kind.program_inputs(dep)
    return layered_fat_tree_inputs(dep, dep.cfg["apps"])


def layered_fat_tree_inputs(dep: Deployment, app_list: list):
    """The program's inputs for the layered DAGs ``app_list`` (per-app
    component lists, as a configuration states them) on ``dep``'s fat-tree,
    at ``dep``'s placement."""
    from repro.core import Component, NetworkCosts, build_topology

    apps = [[Component(name=comp["name"], app=a, is_spout=bool(comp["is_spout"]),
                       parallelism=int(comp["parallelism"]),
                       proc_capacity=float(comp["mu"]),
                       successors=tuple(int(s) for s in comp["successors"]),
                       selectivity=tuple(float(f) for f in comp["selectivity"]))
             for comp in comps]
            for a, comps in enumerate(app_list)]
    topo = build_topology(apps, gamma=dep.gamma)
    if not np.array_equal(topo.inst_comp, dep.inst_comp):
        raise ValueError("the program orders instances differently from the deployment")
    fabric = dep.cfg["fabric"]
    per = int(fabric["containers_per_server"])
    K = dep.n_containers
    net = NetworkCosts(
        name=fabric["kind"], n_servers=K // per, n_containers=K,
        server_dist=fat_tree_server_hops(int(dep.cfg["fabric_k"])),
        container_server=np.repeat(np.arange(K // per), per).astype(np.int32),
        U=dep.U,
    )
    return topo, net, dep.placement


def reference_of(dep):
    """The plain reference the deployment is judged by: its kind's
    ``reference`` where it has one, else ``chipbench/reference.py``."""
    return getattr(kind_module(dep.cfg), "reference", None) or plain_reference
