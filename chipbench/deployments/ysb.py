"""The Yahoo Streaming Benchmark's Storm topology as a deployment kind.

``AdvertisingTopology.java`` (``github.com/yahoo/streaming-benchmarks``,
``storm-benchmarks``): a Kafka spout, four bolts joined by shuffle
groupings (deserialize, filter the views, project, join ``ad_id`` ->
``campaign_id``), then ``campaign_processor`` on a fields grouping on
``campaign_id``. The configuration states the DAG in the layered form of
``potus-paper-k4``; the keyed component states ``key_shares``, one share
per task: the campaigns Storm's hash sends to the task over all campaigns.

The program takes the fields grouping natively (``Component.key_shares``):
``program_inputs`` hands it the configuration's six components, not one
component per keyed task. The fabric is one switch joining the servers.

Its reference (:class:`reference`) is ``chipbench/reference.py``'s plain
cohort dynamics on the configuration's own components and arrays, with the
grouping routed there on its own: a keyed edge ships by Heron's default rule
(the gamma-throttled share of the output queue, at least the mandatory
arrivals) and lands on task ``j`` at ``key_shares[j]``; POTUS prices and
water-fills the shuffle edges with what of gamma the keyed edges left. The
filter emits one tuple in three; the two it acks without emitting complete
there, as a tuple tree completes where its last tuple is acked, so the mass
ledger closes. The reference imports nothing of the program.
"""
import os

import numpy as np

from chipbench import deploy
from chipbench import reference as plain


def _shares(cfg: dict) -> list:
    """Per configuration component: its key shares, or ``None`` (shuffle)."""
    return [np.asarray(c["key_shares"], np.float64) if c.get("key_shares") else None
            for comps in cfg["apps"] for c in comps]


def _fabric(cfg: dict):
    """(server hops, container cost matrix) of the one-switch fabric."""
    fab = cfg["fabric"]
    if fab["kind"] != "one-switch":
        raise ValueError(f"unknown fabric {fab['kind']!r}")
    n = int(fab["servers"])
    hops = np.full((n, n), float(fab["server_hops"]), np.float32)
    np.fill_diagonal(hops, 0.0)
    U = deploy.container_cost_matrix(hops, int(fab["containers_per_server"]),
                                     float(fab["intra_server_cost"]))
    return hops, U


def _rates(comps: dict, inst_comp, shares: list, gamma: float,
           utilization: float) -> np.ndarray:
    """Per-stream spout rates at which the busiest instance runs at
    ``utilization``: a shuffle-grouped bolt's load is its component's mean,
    a keyed bolt's is its hottest task's share of the inflow."""
    spout, par, mu = comps["comp_is_spout"], comps["comp_parallelism"], comps["comp_mu"]
    sel = comps["selectivity"]
    unit = deploy.spout_rate_matrix(comps, inst_comp, 1.0)
    through = deploy.processed_rates(spout, comps["adj"], sel, unit.sum(axis=0))
    worst = 0.0
    for c in range(len(spout)):
        if spout[c]:
            worst = max(worst, float(np.max(unit[inst_comp == c].sum(axis=1) / gamma)))
            continue
        load = through[c] * (shares[c].max() if shares[c] is not None else 1.0 / par[c])
        worst = max(worst, load / mu[c], load * sel[c].sum() / gamma)
    return unit * (utilization / worst)


def build(cfg: dict, read_placement: bool):
    comps = deploy.flatten_apps(cfg["apps"])
    shares = _shares(cfg)
    for c, sh in enumerate(shares):
        if sh is not None and (len(sh) != comps["comp_parallelism"][c]
                               or abs(sh.sum() - 1.0) > 1e-9):
            raise ValueError(f"component {c}: one key share per task, summing to 1")
    inst_comp = np.repeat(np.arange(len(shares)),
                          comps["comp_parallelism"]).astype(np.int32)
    _, U = _fabric(cfg)
    gamma = float(cfg["gamma"])
    rates = _rates(comps, inst_comp, shares, gamma, float(cfg["utilization"]))
    placement = np.zeros(0, np.int32)
    if read_placement:
        placement = np.load(os.path.join(deploy.ROOT, cfg["placement"]["file"])).astype(np.int32)
        if placement.shape != inst_comp.shape or placement.max() >= U.shape[0]:
            raise ValueError(f"placement of {cfg['name']} does not fit its deployment")
    return deploy.Deployment(name=cfg["name"], cfg=cfg, inst_comp=inst_comp, gamma=gamma,
                             U=U, placement=placement, rates=rates, **comps)


def program_inputs(dep):
    """The program's topology with its native fields grouping, the
    one-switch network and the placement."""
    from repro.core import Component, NetworkCosts, build_topology

    apps = [[Component(name=c["name"], app=a, is_spout=bool(c["is_spout"]),
                       parallelism=int(c["parallelism"]), proc_capacity=float(c["mu"]),
                       successors=tuple(int(s) for s in c["successors"]),
                       selectivity=tuple(float(f) for f in c["selectivity"]),
                       key_shares=tuple(float(x) for x in c.get("key_shares", ())))
             for c in comps]
            for a, comps in enumerate(dep.cfg["apps"])]
    topo = build_topology(apps, gamma=dep.gamma)
    if not np.array_equal(topo.inst_comp, dep.inst_comp):
        raise ValueError("the program orders instances differently from the deployment")
    hops, _ = _fabric(dep.cfg)
    per = int(dep.cfg["fabric"]["containers_per_server"])
    K = dep.n_containers
    net = NetworkCosts(name="one-switch", n_servers=K // per, n_containers=K,
                       server_dist=hops,
                       container_server=np.repeat(np.arange(K // per), per).astype(np.int32),
                       U=dep.U)
    return topo, net, dep.placement


# ---------------------------------------------------------------------------
# the reference: the plain cohort dynamics with the fields grouping routed
# ---------------------------------------------------------------------------

def _heron(R, q_out, must, gamma):
    """Heron's default shipment per successor slot: the gamma-throttled
    share of the output queue, at least the mandatory arrivals."""
    total = R(q_out.sum(axis=1))[:, None]
    ratio = R(R(np.full(total.shape, gamma)) / R(np.maximum(total, R(1e-9))))
    scale = R(np.where(total > 0, R(np.minimum(R(1.0), ratio)), R(0.0)))
    return R(np.maximum(R(q_out * scale), must))


def _potus(R, m, q_in, q_out, must, V, beta, gamma):
    """POTUS on the shuffle slots; the keyed slots ship by Heron's rule
    first and the water-fill spends what of gamma they left."""
    I, S = q_out.shape
    zero = R(0.0)
    key_ship = R(np.where(m.keyed, _heron(R, q_out, must, gamma), zero))
    g = R(np.maximum(R(R(np.full((I, 1), gamma)) - R(key_ship.sum(axis=1))[:, None]), zero))
    steer = m.valid & ~m.keyed
    k = m.cont[:, None]
    M, J = plain._cheapest(m, V, q_in)
    m_raw = R(np.where(steer, M[k, m.sm], np.inf) - R(R(beta) * q_out))
    cand = steer & (m_raw < 0.0)
    key = np.where(cand, m_raw, np.inf)
    j_pt = np.where(steer, J[k, m.sm], I)
    budget = R(np.where(cand, R(np.maximum(q_out, zero)), zero))
    rows = np.arange(I)[:, None]
    order = np.lexsort((j_pt, key), axis=-1)
    b_sorted = budget[rows, order]
    after = R(np.cumsum(b_sorted, axis=-1))
    f_sorted = R(R(np.minimum(after, g)) - R(np.minimum(R(after - b_sorted), g)))
    fill = f_sorted[rows, np.argsort(order, axis=-1)]
    short = R(np.where(steer, R(np.maximum(R(must - fill), zero)), zero))
    even = R(short / R(m.count[m.sm]))
    u_pt = m.U[k, m.cont[np.minimum(j_pt, I - 1)]]
    cost = R(R(R(R(fill * R(u_pt)).sum()) + R(R(even * R(m.u_sum[k, m.sm])).sum()))
             + R(R(key_ship * R(m.u_key[k, m.sm])).sum()))
    return R(R(fill + short) + key_ship), fill, j_pt, even, key_ship, cost


def _shuffle(R, m, q_out, must, gamma):
    """Shuffle on the shuffle slots, Heron's rule on every slot."""
    I = q_out.shape[0]
    ship = _heron(R, q_out, must, gamma)
    zero = R(0.0)
    per = R(np.where(m.valid & ~m.keyed, R(ship / R(m.count[m.sm])), zero))
    key_ship = R(np.where(m.keyed, ship, zero))
    k = m.cont[:, None]
    cost = R(R(R(per * R(m.u_sum[k, m.sm])).sum()) + R(R(key_ship * R(m.u_key[k, m.sm])).sum()))
    return (R(R(per * R(m.count[m.sm])) + key_ship), R(np.zeros(per.shape)),
            np.full(per.shape, I), per, key_ship, cost)


def _model(dep):
    """``chipbench/reference.py``'s model of the deployment's own
    components, plus the keyed successor slots, each instance's key share,
    the per-(container, component) keyed cost and each instance's
    completion share."""
    m = plain.Model(dep)
    shares = _shares(dep.cfg)
    keyed = np.array([sh is not None for sh in shares])
    m.keyed = m.valid & keyed[m.sm]  # (I, S)
    m.share = np.concatenate([sh if sh is not None else np.zeros(int(p))
                              for sh, p in zip(shares, dep.comp_parallelism)])
    m.u_key = m.U[:, m.cont] @ (m.onehot * m.share[:, None])  # (K, C)
    # a tuple tree completes where its last tuple is acked: all of it at a
    # terminal bolt, the share a filter does not emit at the filter
    short = 1.0 - dep.selectivity.astype(np.float64).sum(axis=1)
    done = np.where(short > 1e-6, short, 0.0)
    done[~dep.adj.any(axis=1)] = 1.0
    m.done = np.where(m.spout, 0.0, done[dep.inst_comp])
    return m


def _run(m, actual: np.ndarray, T: int, scheduler: str, V: float, W: int,
         age_cap: int, warmup: int, beta: float = 1.0, drain_margin: int | None = None,
         precision: str = "float64") -> dict:
    """``chipbench/reference.py``'s ``run`` with the keyed slots shipped by
    Heron's rule and landed by key share, and a filter's unemitted share
    completed where it is served."""
    R = plain.Arith(precision)
    I, S = m.sm.shape
    C = m.count.shape[0]
    W1 = W + 1
    A = age_cap + W1
    act = m.to_slots(actual[:T].astype(np.float64))
    pred = m.to_slots(actual[:T + W1].astype(np.float64))
    q_rem = R(np.moveaxis(pred[:W1], 0, -1))  # (I, S, W1)
    admit = R(np.zeros((I, S)))
    q_in = R(np.zeros((I, A)))
    q_out = R(np.zeros((I, S, A)))
    transit = R(np.zeros((I, A)))
    resp_mass, resp_time = np.zeros((C, T + W1)), np.zeros((C, T + W1))
    backlog, cost = np.zeros(T), np.zeros(T)
    ledger = {k: np.zeros(T) for k in ("transit", "held", "served")}
    capped = served = 0.0
    spout, bolt = m.spout[:, None], ~m.spout[:, None]
    spout3, bolt3 = spout[:, :, None], bolt[:, :, None]
    zero, beta_n = R(0.0), R(beta)
    resp_of_bucket = np.maximum(age_cap - np.arange(A), 0.0)
    gamma = m.dep.gamma
    inst_comp = m.dep.inst_comp
    for t in range(T):
        # 1. reconcile the window's first slot with the actual arrivals
        p_t, a_t = R(pred[t]), R(act[t])
        tp = R(np.minimum(p_t, a_t))
        p_safe = R(np.where(pred[t] > 0, pred[t], 1.0))
        r = R(np.where(pred[t] > 0, R(q_rem[:, :, 0] / p_safe), zero))
        first = R(R(r * tp) + R(a_t - tp))
        q_rem = R(np.concatenate([first[:, :, None], q_rem[:, :, 1:]], axis=2))
        # 2. observe and decide
        q_in_tot = R(q_in.sum(axis=1))
        q_out_tot = R(np.where(spout, R(q_rem.sum(axis=2)), R(q_out.sum(axis=2))))
        must = R(np.where(spout, R(q_rem[:, :, 0] + admit), zero))
        backlog[t] = float(R(R(q_in_tot.sum()) + R(beta_n * R(q_out_tot.sum()))))
        if scheduler == "potus":
            shipped, point, j_pt, even, key_ship, c_t = _potus(R, m, q_in_tot, q_out_tot,
                                                               must, V, beta, gamma)
        else:
            shipped, point, j_pt, even, key_ship, c_t = _shuffle(R, m, q_out_tot, must, gamma)
        cost[t] = float(c_t)
        # 3. drain oldest-first and land
        from_bolt = R(np.concatenate([np.where(spout3, zero, q_out),
                                      R(np.zeros((I, S, 1)))], axis=2))
        from_spout = R(np.concatenate([np.zeros((I, S, age_cap)), q_rem, admit[:, :, None]],
                                      axis=2))
        drained = plain._drain(R, R(np.where(spout3, from_spout, from_bolt)), shipped)
        q_rem = R(q_rem - R(np.where(spout3, drained[:, :, age_cap:A], zero)))
        admit = R(admit - R(np.where(spout, drained[:, :, A], zero)))
        q_out = R(q_out - R(np.where(bolt3, drained[:, :, :A], zero)))
        d_land = R(np.concatenate([drained[:, :, :age_cap],
                                   R(drained[:, :, age_cap:age_cap + 1] + drained[:, :, A:]),
                                   drained[:, :, age_cap + 1:A]], axis=2))
        live = shipped > plain._EPS
        safe = R(np.where(live, shipped, R(1.0)))
        w_pt = R(np.where(live, R(point / safe), zero))
        w_ev = R(np.where(live, R(even / safe), zero))
        w_key = np.where(live & m.keyed, 1.0, 0.0)  # a keyed slot ships only keyed mass
        land = plain._scatter_rows(R, j_pt.reshape(-1),
                                   R(w_pt[:, :, None] * d_land).reshape(I * S, A), I)
        ev_cb = R(np.zeros((C, A)))
        key_cb = R(np.zeros((C, A)))
        for s in range(S):
            w_s = R(R(m.slot_onehot[s]) * w_ev[:, s:s + 1])
            ev_cb = R(ev_cb + R(np.einsum("ic,ib->cb", w_s, d_land[:, s, :])))
            k_s = m.slot_onehot[s] * w_key[:, s:s + 1]
            key_cb = R(key_cb + R(np.einsum("ic,ib->cb", k_s, d_land[:, s, :])))
        land = R(land + ev_cb[inst_comp])
        land = R(land + R(R(m.share)[:, None] * key_cb[inst_comp]))
        # 4. land last slot's transit, serve bolts
        avail = R(q_in + transit)
        amount = R(np.where(m.spout, zero, R(np.minimum(R(avail.sum(axis=1)), R(m.mu)))))
        served_b = plain._drain(R, avail, amount)
        q_in = R(np.where(bolt, R(avail - served_b), zero))
        done = R(served_b * R(m.done)[:, None])
        cmass = R(np.einsum("ic,ib->cb", R(m.onehot), done))
        cols = t + np.arange(A) - age_cap
        ok = (cols >= 0) & (cols < T + W1)
        resp_mass[:, cols[ok]] = R(resp_mass[:, cols[ok]] + cmass[:, ok])
        resp_time[:, cols[ok]] = R(resp_time[:, cols[ok]]
                                   + R(cmass * resp_of_bucket)[:, ok])
        capped += float(cmass[:, 0].sum())
        served += float(cmass.sum())
        ledger["served"][t] = float(cmass.sum())
        ledger["transit"][t] = float(R(land.sum()))
        fwd = R(served_b[:, None, :] * R(m.sel[:, :, None]))
        q_out = R(q_out + R(np.where(bolt3, fwd, zero)))
        # 5. admit, shift the window in, age
        admit = R(admit + R(np.where(spout, q_rem[:, :, 0], zero)))
        ledger["held"][t] = float(R(admit.sum()))
        q_rem = R(np.concatenate([q_rem[:, :, 1:], R(pred[t + W1][:, :, None])], axis=2))
        q_in, q_out, transit = plain._age(R, q_in), plain._age(R, q_out), plain._age(R, land)
    weights = np.einsum("tic,ic->ct", actual[:T].astype(np.float64),
                        (m.dep.adj[inst_comp] & m.spout[:, None]).astype(np.float64))
    out = plain.aggregate(resp_mass, resp_time, weights, m.reach, backlog, cost,
                          T, W, warmup, drain_margin)
    out.update(backlog=backlog, cost=cost, completed_mass=served,
               saturated_frac=capped / max(served, 1e-9), **ledger)
    return out


class reference:
    """The plain reference of the YSB deployment: ``chipbench/reference.py``'s
    cohort dynamics on the configuration's own components and arrays, the
    keyed slots routed by the configuration's key shares."""

    Model = staticmethod(_model)
    run = staticmethod(_run)
