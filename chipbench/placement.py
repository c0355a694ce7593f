"""T-Heron instance placement (POTUS paper §5.1, after T-Storm), vectorized.

The greedy is the one the paper describes: instances in descending order of
expected (in + out) tuple traffic, each put into the container with the least
added cross-container traffic, at most ``max_per_container`` per container.
The added traffic of instance ``i`` (component ``c``) in container ``k`` is
``sum_j r(c, c_j) U[k, k_j]`` over the instances placed so far; grouping them
by component gives ``sum_c' r(c, c') A[c', k]`` with running sums
``A[c', k] = sum_{j in c'} U[k, k_j]``, so one instance costs O(C·K) and the
whole placement O(I·C·K) instead of the O(I²·K) of the instance-pair loop.

The placement of a configuration is data beside it. Regenerate one with::

    PYTHONPATH=src python chipbench/placement.py potus-paper-k4
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.deploy import ROOT, build_deployment, processed_rates  # noqa: E402


def component_flows(dep, rates: np.ndarray) -> np.ndarray:
    """(C, C) expected tuple rate on each component edge."""
    C = dep.n_components
    spout, sel = dep.comp_is_spout, dep.selectivity
    through = processed_rates(spout, dep.adj, sel, rates.sum(axis=0))
    flow = np.zeros((C, C))
    for c in range(C):
        if spout[c]:
            flow[c] = rates[dep.inst_comp == c].sum(axis=0)
        else:
            flow[c] = through[c] * sel[c]
    return flow


def t_heron(dep, rates: np.ndarray, max_per_container: int) -> np.ndarray:
    """(I,) container of each instance."""
    I, K = dep.n_instances, dep.n_containers
    par = dep.comp_parallelism.astype(np.float64)
    flow = component_flows(dep, rates)
    per_inst = ((flow.sum(axis=0) + flow.sum(axis=1)) / par)[dep.inst_comp]
    order = np.argsort(-per_inst.astype(np.float32), kind="stable")
    pair = flow / (par[:, None] * par[None, :])
    r = pair + pair.T  # (C, C) symmetric instance-pair rate
    U = dep.U.astype(np.float64)
    A = np.zeros((dep.n_components, K))  # running sums of U[:, k_j] per component
    load = np.zeros(K, np.int64)
    assign = np.full(I, -1, np.int32)
    for i in order:
        c = dep.inst_comp[i]
        inc = np.where(load < max_per_container, r[c] @ A, np.inf)
        best = inc.min()
        if not np.isfinite(best):
            raise ValueError("no container has remaining capacity")
        k = int(np.flatnonzero(inc <= best + 1e-12)[0])
        assign[i] = k
        load[k] += 1
        A[c] += U[:, k]
    return assign


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config", help="configuration name, e.g. potus-paper-k4")
    args = ap.parse_args(argv)
    dep = build_deployment(args.config, read_placement=False)
    pl = dep.cfg["placement"]
    assign = t_heron(dep, dep.rates, int(pl["max_per_container"]))
    path = os.path.join(ROOT, pl["file"])
    np.save(path, assign.astype(np.int16 if dep.n_containers < 2**15 else np.int32))
    print(f"wrote {path}: {dep.n_instances} instances on "
          f"{len(np.unique(assign))} of {dep.n_containers} containers")


if __name__ == "__main__":
    main()
