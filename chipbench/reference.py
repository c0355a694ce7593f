"""Plain reference of the simulated cohort dynamics, in NumPy.

It restates, slot by slot, what the fused cohort engine computes (POTUS paper
§3-§4 with the response-time bookkeeping of its §5.1) under perfect
prediction, from the deployment's arrays alone: it imports nothing of the
program and takes nothing the program made. Per slot ``t``:

1. reconcile the spout window's first slot with the actual arrivals: the
   part not yet shipped ahead of time keeps its share;
2. observe the queues and decide. POTUS: per (container k, component c) the
   cheapest candidate ``min_j V U[k, k_j] + q_in[j]`` (lowest index on ties);
   each source fills its budget ``gamma`` over its successors in ascending
   price among those with a negative price ``... - beta q_out``, then ships
   the shortfall of its mandatory arrivals evenly over the successor's
   instances. Shuffle: the gamma-throttled output queue, at least the
   mandatory arrivals, evenly over the successor's instances;
3. drain each source oldest-first and land the drained age buckets on the
   targets (point part and even part in proportion);
4. serve bolts oldest-first up to ``mu`` and add terminal completions to the
   response tally of their source slot; completed mass leaves, the rest
   forwards with each edge's selectivity;
5. admit unshipped actuals, shift the window in, age every bucket by one
   (the oldest saturates at ``age_cap``).

The response statistics are the paper's: per (entry component, source slot)
the largest mean response over the terminals reachable from the entry,
weighted by the actual arrivals, averaged (and its 95th percentile) over the
slots after ``warmup`` and before the horizon's drain margin.

Arithmetic: float64 for the reference; ``precision="bfloat16"`` is the
control, with every result (accumulators included) rounded to bfloat16.
"""
from __future__ import annotations

import numpy as np

_EPS = 1e-12  # negligible shipped mass: no landing weights


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest, ties to even), kept in float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return b.view(np.float32)


class Arith:
    """``R(x)``: ``x`` as this precision stores a result."""

    def __init__(self, precision: str):
        if precision == "float64":
            self.dt, self.rnd = np.float64, (lambda a: a)
        elif precision == "bfloat16":
            self.dt, self.rnd = np.float32, _bf16
        else:
            raise ValueError(f"unknown precision {precision!r}")

    def __call__(self, x) -> np.ndarray:
        return self.rnd(np.asarray(x, self.dt))


# ---------------------------------------------------------------------------
# the deployment in successor-slot layout
# ---------------------------------------------------------------------------

class Model:
    """Static arrays of one deployment, in successor-slot layout: row ``i``,
    slot ``s`` is the stream from instance ``i`` to its ``s``-th successor."""

    def __init__(self, dep):
        self.dep = dep
        C, I = dep.n_components, dep.n_instances
        succ = [np.nonzero(dep.adj[c])[0] for c in range(C)]
        self.S = max(1, max(len(s) for s in succ))
        succ_map = np.full((I, self.S), -1, np.int64)
        for i, c in enumerate(dep.inst_comp):
            succ_map[i, :len(succ[c])] = succ[c]
        self.valid = succ_map >= 0
        self.sm = np.where(self.valid, succ_map, 0)
        self.spout = dep.comp_is_spout[dep.inst_comp]
        self.sel = np.where(self.valid, dep.selectivity[dep.inst_comp[:, None], self.sm], 0.0)
        self.stream = self.valid & self.spout[:, None]
        terminal = ~dep.adj.any(axis=1) & ~dep.comp_is_spout
        self.term = terminal[dep.inst_comp] & ~self.spout
        self.mu = np.where(self.spout, 0.0, dep.comp_mu[dep.inst_comp])
        self.count = dep.comp_parallelism.astype(np.float64)
        self.cont = dep.placement.astype(np.int64)
        self.U = dep.U.astype(np.float64)
        self.starts = np.concatenate([[0], np.cumsum(dep.comp_parallelism)])
        self.onehot = np.eye(C)[dep.inst_comp]  # (I, C)
        # per (container, component) sum of U over the component's instances
        self.u_sum = self.U[:, self.cont] @ self.onehot  # (K, C)
        # one-hot of each successor slot's component, (S, I, C)
        self.slot_onehot = np.stack([np.eye(C)[self.sm[:, s]] * self.valid[:, s, None]
                                     for s in range(self.S)])
        # the containers hosting each component, and each instance's host
        self.hosts = [np.unique(self.cont[self.starts[c]:self.starts[c + 1]],
                                return_inverse=True) for c in range(C)]
        reach = dep.adj | np.eye(C, dtype=bool)
        for _ in range(C):
            reach = reach | ((reach.astype(np.int64) @ reach.astype(np.int64)) > 0)
        self.reach = reach

    def to_slots(self, x: np.ndarray) -> np.ndarray:
        """(..., I, C) per-stream values -> (..., I, S) on the spout streams."""
        idx = np.broadcast_to(self.sm, x.shape[:-2] + self.sm.shape)
        return np.take_along_axis(x, idx, axis=-1) * self.stream


# ---------------------------------------------------------------------------
# decisions
# ---------------------------------------------------------------------------

def _cheapest(m: Model, V: float, q_in: np.ndarray):
    """Per (container, component): the cheapest candidate's price ``M`` and
    its instance ``J`` (lowest index on ties)."""
    K, C = m.U.shape[0], m.count.shape[0]
    I = q_in.shape[0]
    M = np.full((K, C), np.inf)
    J = np.full((K, C), I, np.int64)
    rows = np.arange(K)
    for c in range(C):
        if m.dep.comp_is_spout[c]:
            continue
        lo, hi = m.starts[c], m.starts[c + 1]
        ks, host = m.hosts[c]
        q = q_in[lo:hi]
        # each hosting container's best instance: least queue, then least
        # index (one container's instances share U)
        order = np.lexsort((np.arange(hi - lo), q, host))
        first = np.flatnonzero(np.r_[True, host[order][1:] != host[order][:-1]])
        best = order[first]  # one per hosting container, in ks order
        price = V * m.U[:, ks] + q[best][None, :]  # (K, hosts)
        h = np.lexsort((np.broadcast_to(best, price.shape), price), axis=1)[:, 0]
        M[:, c], J[:, c] = price[rows, h], lo + best[h]
    return M, J


def _potus(R, m: Model, q_in, q_out, must, V, beta, gamma):
    I, S = q_out.shape
    k = m.cont[:, None]
    M, J = _cheapest(m, V, q_in)
    M_row = np.where(m.valid, M[k, m.sm], np.inf)
    m_raw = R(M_row - R(R(beta) * q_out))
    cand = m.valid & (m_raw < 0.0)
    key = np.where(cand, m_raw, np.inf)
    j_pt = np.where(m.valid, J[k, m.sm], I)
    zero = R(0.0)
    budget = R(np.where(cand, R(np.maximum(q_out, zero)), zero))
    rows = np.arange(I)[:, None]
    order = np.lexsort((j_pt, key), axis=-1)
    b_sorted = budget[rows, order]
    after = R(np.cumsum(b_sorted, axis=-1))
    g = R(np.full((I, 1), gamma))
    f_sorted = R(R(np.minimum(after, g)) - R(np.minimum(R(after - b_sorted), g)))
    fill = f_sorted[rows, np.argsort(order, axis=-1)]
    short = R(np.where(m.valid, R(np.maximum(R(must - fill), zero)), zero))
    even = R(short / R(m.count[m.sm]))
    u_pt = m.U[k, m.cont[np.minimum(j_pt, I - 1)]]
    cost = R(R(R(fill * R(u_pt)).sum()) + R(R(even * R(m.u_sum[k, m.sm])).sum()))
    return R(fill + short), fill, j_pt, even, cost


def _shuffle(R, m: Model, q_out, must, gamma):
    I = q_out.shape[0]
    total = R(q_out.sum(axis=1))[:, None]
    ratio = R(R(np.full((I, 1), gamma)) / R(np.maximum(total, R(1e-9))))
    scale = R(np.where(total > 0, R(np.minimum(R(1.0), ratio)), R(0.0)))
    ship = R(np.maximum(R(q_out * scale), must))
    per = R(np.where(m.valid, R(ship / R(m.count[m.sm])), R(0.0)))
    cost = R(R(per * R(m.u_sum[m.cont[:, None], m.sm])).sum())
    return (R(per * R(m.count[m.sm])), R(np.zeros(per.shape)),
            np.full(per.shape, I), per, cost)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _drain(R, buckets, amount):
    """Mass taken from each bucket when ``amount`` is drained oldest-first."""
    before = R(R(np.cumsum(buckets, axis=-1)) - buckets)
    return R(np.minimum(R(np.maximum(R(amount[..., None] - before), R(0.0))), buckets))


def _age(R, x):
    """One slot older: bucket b+1 -> b, the oldest saturating."""
    head = R(x[..., 0:1] + x[..., 1:2])
    return R(np.concatenate([head, x[..., 2:], R(np.zeros(x.shape[:-1] + (1,)))], axis=-1))


def _scatter_rows(R, idx, rows, n):
    """out[idx[r]] += rows[r] into an (n, ...) array; idx == n drops."""
    v = np.zeros((n + 1,) + rows.shape[1:], R.dt)
    np.add.at(v, idx, rows)
    return R(v[:n])


def run(m: Model, actual: np.ndarray, T: int, scheduler: str, V: float, W: int,
        age_cap: int, warmup: int, beta: float = 1.0, drain_margin: int | None = None,
        precision: str = "float64") -> dict:
    """Simulate ``T`` slots under perfect prediction; ``actual`` is
    (>= T+W+1, I, C). ``precision`` is ``"float64"`` (the reference) or
    ``"bfloat16"`` (the control). Besides the program's summary values, the
    run returns the per-slot ``transit`` (mass landed, arriving next slot),
    ``held`` (admission backlog) and ``served`` (terminal completions) that
    close the mass ledger."""
    R = Arith(precision)
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
            shipped, point, j_pt, even, c_t = _potus(R, m, q_in_tot, q_out_tot, must,
                                                     V, beta, gamma)
        else:
            shipped, point, j_pt, even, c_t = _shuffle(R, m, q_out_tot, must, gamma)
        cost[t] = float(c_t)
        # 3. drain oldest-first and land
        from_bolt = R(np.concatenate([np.where(spout3, zero, q_out),
                                      R(np.zeros((I, S, 1)))], axis=2))
        from_spout = R(np.concatenate([np.zeros((I, S, age_cap)), q_rem, admit[:, :, None]],
                                      axis=2))
        drained = _drain(R, R(np.where(spout3, from_spout, from_bolt)), shipped)
        q_rem = R(q_rem - R(np.where(spout3, drained[:, :, age_cap:A], zero)))
        admit = R(admit - R(np.where(spout, drained[:, :, A], zero)))
        q_out = R(q_out - R(np.where(bolt3, drained[:, :, :A], zero)))
        d_land = R(np.concatenate([drained[:, :, :age_cap],
                                   R(drained[:, :, age_cap:age_cap + 1] + drained[:, :, A:]),
                                   drained[:, :, age_cap + 1:A]], axis=2))
        live = shipped > _EPS
        safe = R(np.where(live, shipped, R(1.0)))
        w_pt = R(np.where(live, R(point / safe), zero))
        w_ev = R(np.where(live, R(even / safe), zero))
        land = _scatter_rows(R, j_pt.reshape(-1),
                             R(w_pt[:, :, None] * d_land).reshape(I * S, A), I)
        ev_cb = R(np.zeros((C, A)))
        for s in range(S):
            w_s = R(R(m.slot_onehot[s]) * w_ev[:, s:s + 1])
            ev_cb = R(ev_cb + R(np.einsum("ic,ib->cb", w_s, d_land[:, s, :])))
        land = R(land + ev_cb[m.dep.inst_comp])
        # 4. land last slot's transit, serve bolts
        avail = R(q_in + transit)
        amount = R(np.where(m.spout, zero, R(np.minimum(R(avail.sum(axis=1)), R(m.mu)))))
        served_b = _drain(R, avail, amount)
        q_in = R(np.where(bolt, R(avail - served_b), zero))
        term = R(np.where(m.term[:, None], served_b, zero))
        cmass = R(np.einsum("ic,ib->cb", R(m.onehot), term))
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
        q_in, q_out, transit = _age(R, q_in), _age(R, q_out), _age(R, land)
    weights = np.einsum("tic,ic->ct", actual[:T].astype(np.float64),
                        (m.dep.adj[m.dep.inst_comp] & m.spout[:, None]).astype(np.float64))
    out = aggregate(resp_mass, resp_time, weights, m.reach, backlog, cost,
                    T, W, warmup, drain_margin)
    out.update(backlog=backlog, cost=cost, completed_mass=served,
               saturated_frac=capped / max(served, 1e-9), **ledger)
    return out


def aggregate(resp_mass, resp_time, weights, reach, backlog, cost, T, W, warmup,
              drain_margin=None) -> dict:
    """Response statistics of one run (see the module docstring)."""
    margin = drain_margin if drain_margin is not None else max(2 * W + 20, 40)
    lo, hi = max(warmup, 0), min(T - margin, T)
    out = dict(avg_backlog=float(backlog[warmup:].mean()), avg_cost=float(cost[warmup:].mean()))
    entries = np.nonzero(weights[:, lo:hi].sum(axis=1) > 0)[0] if hi > lo else []
    if len(entries) == 0:
        nan = float("nan")
        out.update(avg_response=nan, p95_response=nan, completed_frac=0.0)
        return out
    mass = resp_mass[:, lo:hi]
    mean = np.where(mass > 1e-9, resp_time[:, lo:hi] / np.maximum(mass, 1e-30), -np.inf)
    resp = np.stack([mean[reach[e]].max(axis=0, initial=-np.inf) for e in entries])
    w = weights[entries, lo:hi]
    ok = (w > 0) & np.isfinite(resp)
    r, wt = resp[ok], w[ok]
    order = np.argsort(r)
    cum = np.cumsum(wt[order]) / wt.sum()
    out.update(avg_response=float(np.average(r, weights=wt)),
               p95_response=float(r[order][np.searchsorted(cum, 0.95)]),
               completed_frac=int(ok.sum()) / max(int((weights[:, lo:hi] > 0).sum()), 1))
    return out
