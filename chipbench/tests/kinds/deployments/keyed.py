"""A deployment kind for the tests: layered DAGs on a fat-tree in which the
edges into a component may be keyed.

A component that states ``key_shares`` (one share per instance) takes a
fields grouping on every edge into it: the key of a tuple sends it to
instance ``j`` with share ``j``, whatever the queues, so no scheduler may
steer it. The program has no groupings, so the kind hands it each instance
of such a component as a component of its own (parallelism 1), fed at the
edge's selectivity times the instance's share. The placement is T-Heron's,
made when the deployment is built.

Its reference pins the keyed instances on its own, on the arrays of the
configuration, and judges the run with the plain cohort dynamics.
"""
import dataclasses

import numpy as np

from chipbench import deploy, placement
from chipbench import reference as plain


def pin(apps: list) -> list:
    """Per-app component lists with each instance of a keyed component made
    a component of its own."""
    out = []
    for comps in apps:
        first = np.cumsum([0] + [len(c.get("key_shares", [1])) for c in comps])
        pinned = []
        for comp in comps:
            succ, sel = [], []
            for s, f in zip(comp["successors"], comp["selectivity"]):
                shares = comps[s].get("key_shares", [1.0])
                succ += [int(first[s]) + j for j in range(len(shares))]
                sel += [f * share for share in shares]
            plain_comp = {k: v for k, v in comp.items() if k != "key_shares"}
            shares = comp.get("key_shares")
            if shares and len(shares) != comp["parallelism"]:
                raise ValueError(f"{comp['name']}: one key share per instance")
            for j in range(len(shares) if shares else 1):
                pinned.append(dict(plain_comp, successors=succ, selectivity=sel,
                                   **({"name": f"{comp['name']}.{j}", "parallelism": 1}
                                      if shares else {})))
        out.append(pinned)
    return out


def build(cfg: dict, read_placement: bool):
    dep = deploy.layered_fat_tree(cfg["name"], dict(cfg, apps=pin(cfg["apps"])),
                                  read_placement=False)
    if read_placement:
        dep = dataclasses.replace(dep, placement=placement.t_heron(
            dep, dep.rates, int(cfg["placement"]["max_per_container"])))
    return dataclasses.replace(dep, cfg=cfg)


def program_inputs(dep):
    return deploy.layered_fat_tree_inputs(dep, pin(dep.cfg["apps"]))


class reference:
    """The plain reference on the configuration's DAG with the keyed edges
    routed by their shares: component ``c`` of the configuration becomes one
    component per share, each fed at the edge's selectivity times it."""

    @staticmethod
    def Model(dep):
        cfg_comps = [c for comps in dep.cfg["apps"] for c in comps]
        logical = deploy.flatten_apps(dep.cfg["apps"])
        share = [np.asarray(c.get("key_shares", [1.0])) for c in cfg_comps]
        own = np.repeat(np.arange(len(cfg_comps)), [len(s) for s in share])
        weight = np.concatenate(share)
        keyed = np.array([len(c.get("key_shares", [])) > 0 for c in cfg_comps])
        par = np.where(keyed[own], 1, logical["comp_parallelism"][own])
        sel = logical["selectivity"].astype(np.float64)[np.ix_(own, own)] * weight
        routed = dataclasses.replace(
            dep, comp_is_spout=logical["comp_is_spout"][own], comp_parallelism=par,
            comp_mu=logical["comp_mu"][own], adj=logical["adj"][np.ix_(own, own)],
            selectivity=sel.astype(np.float32),
            inst_comp=np.repeat(np.arange(len(own)), par).astype(np.int32))
        return plain.Model(routed)

    run = staticmethod(plain.run)
