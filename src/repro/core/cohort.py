"""Cohort (discrete-event) engine — exact response-time semantics.

The JAX engine (``core.simulator``) is exact for backlogs and communication
costs but fluid cohorts are merged, so it cannot attribute completions to
arrival slots. This engine tracks *cohorts* keyed by ``(entry_component,
source_slot)`` through every FIFO queue of the system and reproduces the
paper's response-time metric (§5.1): time from a tuple's **actual arrival**
to the completion of its last descendant at a terminal bolt, with tuples
pre-served before arrival counting as ~0.

Mis-prediction semantics (§5.2.2):
  * window entries are *predicted* tuples; when a window slot becomes current
    its untreated remainder is reconciled against actual arrivals:
    true-positive remainder stays, false-positive (phantom) remainder is
    dropped, unpredicted true-negative tuples join untreated;
  * phantom tuples already pre-served keep consuming downstream resources
    (they are indistinguishable in flight) — exactly the paper's
    "processing such tuples consumes extra system resources".

Approximation (documented in DESIGN.md §2): response is aggregated per
cohort as ``max over terminal components of the mass-weighted mean of
clip(completion - arrival, 0)``; within a component the per-tuple max is
replaced by the mean, across components the max is kept.

Scheduling decisions come from the same jitted schedulers as the JAX engine
(`potus_schedule`, `shuffle_schedule`, ...), so both engines exercise one
implementation of Algorithm 1.

Disruption traces (``core.events``, DESIGN.md §9) are consumed per slot: the
scheduler is called with the slot's :class:`~repro.core.potus.SlotCaps`
(dead instances priced out), bolts serve at the slot's effective ``mu``, and
tuples stranded at a failed bolt keep their cohort keys — their response
honestly includes the downtime. Mass held *at the spout* (admission backlog)
is re-tagged to its dispatch slot, the engine's pre-existing attribution.

This event loop is the *semantic oracle*: ``core.cohort_fused`` re-expresses
the same dynamics as age-tagged arrays under ``lax.scan`` (DESIGN.md §8) and
is differentially tested against it; use the fused engine for grids and
scale, this one to pin down semantics.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict, deque

import numpy as np

from repro.obs.metrics import MetricsFrame, build_frame, compute_host_streams, scan_stream_names
from repro.obs.trace import span as obs_span

from .network import NetworkCosts
from .potus import make_problem
from .simulator import SimConfig, _get_scheduler
from .topology import Topology

__all__ = ["CohortResult"]


@dataclasses.dataclass
class CohortResult:
    avg_response: float  # slots, weighted by actual arrivals
    p95_response: float
    avg_backlog: float
    avg_cost: float
    backlog: np.ndarray  # (T,)
    comm_cost: np.ndarray  # (T,)
    n_cohorts: int
    completed_frac: float
    # fraction of terminal completions reporting the age-capped response —
    # always 0.0 here (the event loop tracks ages exactly); the fused engine
    # (DESIGN.md §8) sets it so callers can tell when age_cap is too shallow
    saturated_frac: float = 0.0
    # total tuple mass completed over the whole run (warmup included,
    # phantoms included): served at terminal bolts, plus what a filter acks
    # without emitting (Topology.comp_done_share) — the conservation ledger
    # the disruption property tests check against injected mass (DESIGN.md §9)
    completed_mass: float = 0.0
    # selected per-slot obs streams, or None when metrics were off (DESIGN.md §14)
    metrics: MetricsFrame | None = None


class _Fifo:
    """FIFO of cohort groups; proportional service within a group."""

    __slots__ = ("groups", "total")

    def __init__(self):
        self.groups: deque = deque()  # each: dict key -> mass
        self.total: float = 0.0

    def push(self, items: dict):
        mass = sum(items.values())
        if mass <= 0:
            return
        self.groups.append(dict(items))
        self.total += mass

    def drain(self, amount: float) -> dict:
        """Remove up to ``amount`` oldest-first; returns key -> mass removed."""
        out: dict = defaultdict(float)
        amount = min(amount, self.total)
        while amount > 1e-12 and self.groups:
            head = self.groups[0]
            head_total = sum(head.values())
            if head_total <= 1e-12:
                self.groups.popleft()
                continue
            take = min(amount, head_total)
            frac = take / head_total
            for k in list(head.keys()):
                moved = head[k] * frac
                out[k] += moved
                head[k] -= moved
            self.total -= take
            amount -= take
            if head_total - take <= 1e-12:
                self.groups.popleft()
        return dict(out)


def _run_cohort_sim_impl(
    topo: Topology,
    net: NetworkCosts,
    inst_container: np.ndarray,
    actual,  # (T, I, C) actual arrivals, or ArrivalSpec
    predicted: np.ndarray | None,  # (T, I, C) predicted arrivals (None => perfect)
    T: int,
    cfg: SimConfig,
    warmup: int = 50,
    drain_margin: int | None = None,
    events=None,  # EventTrace | None — disruption trace (core.events, DESIGN.md §9)
    metrics=None,  # MetricsSpec | None — selected obs streams (DESIGN.md §14)
) -> CohortResult:
    import jax.numpy as jnp

    from .potus import SlotCaps
    from .simulator import materialize_arrivals

    W = cfg.window
    actual = materialize_arrivals(actual, topo, T + W + 1)
    if predicted is None:
        predicted = actual
    prob = make_problem(topo, net, inst_container)
    sched = _get_scheduler(cfg.scheduler, cfg.use_pallas)
    trace = None if events is None else events.prepared(T)

    I, C = topo.n_instances, topo.n_components
    inst_comp = topo.inst_comp
    is_spout = topo.comp_is_spout[inst_comp]
    terminal = set(int(c) for c in topo.terminal_components)
    succ_of = {c: topo.successors_of_comp(c) for c in range(C)}
    sel = topo.selectivity
    done_share = topo.comp_done_share  # a filter completes what it acks unemitted
    mu = topo.inst_mu
    U = net.U
    u_pair = U[np.ix_(inst_container, inst_container)]
    spout_streams = [
        (i, int(c2)) for i in range(I) if is_spout[i] for c2 in succ_of[int(inst_comp[i])]
    ]

    # --- state ---------------------------------------------------------------
    window_unt = {s: np.zeros(W + 1) for s in spout_streams}  # untreated per lookahead pos
    admit_backlog = {s: 0.0 for s in spout_streams}
    q_in = {i: _Fifo() for i in range(I) if not is_spout[i]}
    q_out = {
        (i, int(c2)): _Fifo()
        for i in range(I)
        if not is_spout[i]
        for c2 in succ_of[int(inst_comp[i])]
    }
    transit: list[tuple[int, tuple, float]] = []  # (target, key, mass) landing next slot
    # response accumulators: key -> {terminal_comp: [mass, mass*clip(resp)]}
    resp_acc: dict = defaultdict(lambda: defaultdict(lambda: [0.0, 0.0]))
    weights: dict = defaultdict(float)  # key -> actual arrivals

    # pre-load window with predictions for slots 0..W
    for (i, c2) in spout_streams:
        for w in range(W + 1):
            if w < predicted.shape[0]:
                window_unt[(i, c2)][w] = predicted[w, i, c2]

    backlog_ts = np.zeros(T)
    cost_ts = np.zeros(T)
    completed_mass = 0.0
    U_dev = jnp.asarray(U)  # hoisted: one host->device transfer, not one per slot
    met_names = () if metrics is None else scan_stream_names(metrics)
    met_rows: list[tuple] = []
    u_colmean = U.mean(axis=0)[inst_container]  # (I,) mean transfer cost per column

    target_split_cache: dict[int, np.ndarray] = {
        c: topo.instances_of(c) for c in range(C)
    }

    for t in range(T):
        # -- 1. reconcile window pos-0 with actual arrivals of slot t ---------
        tp_t = fp_t = tn_t = drop_t = 0.0
        for (i, c2) in spout_streams:
            pred_total = predicted[t, i, c2] if t < predicted.shape[0] else 0.0
            act = actual[t, i, c2] if t < actual.shape[0] else 0.0
            unt = window_unt[(i, c2)][0]
            tp = min(pred_total, act)
            fp = pred_total - tp
            tn = act - tp
            r = unt / pred_total if pred_total > 0 else 0.0
            window_unt[(i, c2)][0] = r * tp + tn  # drop unserved phantoms
            weights[(c2, t)] += act
            tp_t += tp
            fp_t += fp
            tn_t += tn
            drop_t += r * fp  # phantom remainder retired by reconciliation

        # -- 2. gather queue state, schedule ----------------------------------
        q_in_arr = np.zeros(I, np.float32)
        for i, f in q_in.items():
            q_in_arr[i] = f.total
        q_out_arr = np.zeros((I, C), np.float32)
        must_send = np.zeros((I, C), np.float32)
        for (i, c2), w_arr in window_unt.items():
            q_out_arr[i, c2] = w_arr.sum()
            must_send[i, c2] = w_arr[0] + admit_backlog[(i, c2)]
        for (i, c2), f in q_out.items():
            q_out_arr[i, c2] = f.total

        caps = None
        if trace is not None:
            alive_row = jnp.asarray(trace.alive_t[t])
            caps = SlotCaps(alive=alive_row, row_alive=alive_row,
                            mu=jnp.asarray(trace.mu_t[t]),
                            gamma=jnp.asarray(trace.gamma_t[t]))
        with obs_span("potus/cohort/scheduler-call", t=t):
            X = np.asarray(
                sched(prob, U_dev, jnp.asarray(q_in_arr), jnp.asarray(q_out_arr),
                      jnp.asarray(must_send), float(cfg.V), float(cfg.beta), caps=caps)
            )
        backlog_ts[t] = q_in_arr.sum() + cfg.beta * q_out_arr.sum()
        cost_ts[t] = float((X * u_pair).sum())

        # -- 3. drain sources, enqueue transit ---------------------------------
        new_transit: list[tuple[int, tuple, float]] = []
        for i in range(I):
            ci = int(inst_comp[i])
            for c2 in succ_of[ci]:
                c2 = int(c2)
                targets = target_split_cache[c2]
                amounts = X[i, targets]
                total_amt = float(amounts.sum())
                if total_amt <= 1e-12:
                    continue
                if is_spout[i]:
                    # drain window ascending w; cohort src_slot = t + w
                    w_arr = window_unt[(i, c2)]
                    remaining = total_amt
                    drained: dict = {}
                    for w in range(W + 1):
                        take = min(remaining, w_arr[w])
                        if take > 1e-12:
                            drained[(c2, t + w)] = drained.get((c2, t + w), 0.0) + take
                            w_arr[w] -= take
                            remaining -= take
                        if remaining <= 1e-12:
                            break
                    # shortfall of mandatory dispatch is tracked as admit backlog
                    ab_take = min(remaining, admit_backlog[(i, c2)])
                    if ab_take > 0:
                        drained[(c2, t)] = drained.get((c2, t), 0.0) + ab_take
                        admit_backlog[(i, c2)] -= ab_take
                else:
                    drained = q_out[(i, c2)].drain(total_amt)
                drained_total = sum(drained.values())
                if drained_total <= 1e-12:
                    continue
                for j, amt in zip(targets, amounts):
                    if amt <= 1e-12:
                        continue
                    frac = float(amt) / total_amt
                    for key, mass in drained.items():
                        new_transit.append((int(j), key, mass * frac))
        # any unshipped pos-0 actuals become admission backlog for next slot
        for (i, c2) in spout_streams:
            leftover = window_unt[(i, c2)][0]
            if leftover > 1e-12:
                admit_backlog[(i, c2)] += leftover
                window_unt[(i, c2)][0] = 0.0

        # -- 4. land last slot's transit, serve bolts --------------------------
        land: dict[int, dict] = defaultdict(dict)
        for j, key, mass in transit:
            land[j][key] = land[j].get(key, 0.0) + mass
        for j, items in land.items():
            q_in[j].push(items)
        transit = new_transit

        mu_slot = mu if trace is None else trace.mu_t[t]
        for i, fifo in q_in.items():
            served = fifo.drain(float(mu_slot[i]))
            if not served:
                continue
            ci = int(inst_comp[i])
            succs = succ_of[ci]
            if len(succs) == 0:  # terminal bolt: completions
                for key, mass in served.items():
                    completed_mass += mass
                    acc = resp_acc[key][ci]
                    acc[0] += mass
                    acc[1] += mass * max(t - key[1], 0.0)
            else:
                for c2 in succs:
                    c2 = int(c2)
                    f = sel[ci, c2]
                    q_out[(i, c2)].push({k: m * f for k, m in served.items()})
                if done_share[ci] > 0.0:  # acked without emitting: completes here
                    for key, mass in served.items():
                        part = mass * float(done_share[ci])
                        completed_mass += part
                        acc = resp_acc[key][ci]
                        acc[0] += part
                        acc[1] += part * max(t - key[1], 0.0)

        # -- 5. shift spout windows, load prediction for slot t + W + 1 --------
        # every lookahead position moves one slot closer to current; the
        # vacated tail admits the prediction for slot t + W + 1 (eqs. 5-7).
        # With W == 0 the "shift" is the whole story: the single position is
        # overwritten by predicted[t + 1], which next slot's reconciliation
        # immediately replaces with the actual arrivals (r == 1 there, since
        # an untouched fresh prediction is fully untreated).
        for (i, c2) in spout_streams:
            w_arr = window_unt[(i, c2)]
            w_arr[:-1] = w_arr[1:]
            nxt = t + W + 1
            w_arr[-1] = predicted[nxt, i, c2] if nxt < predicted.shape[0] else 0.0

        # -- 6. per-slot metric rows (DESIGN.md §14) ---------------------------
        if metrics is not None:
            landed = np.zeros(I, np.float32)
            for j, _key, mass in transit:
                landed[j] += mass
            comp_backlog = np.zeros(C)
            np.add.at(comp_backlog, inst_comp, q_in_arr)
            ctx = {
                "h": backlog_ts[t],
                "q_in": q_in_arr,
                "price": cfg.V * u_colmean + q_in_arr,
                "landed": landed,
                "transit_total": landed.sum(),
                "comp_backlog": comp_backlog,
                "held": sum(admit_backlog.values()),
                "dropped": drop_t,
                "tp": tp_t,
                "fp": fp_t,
                "tn": tn_t,
            }
            met_rows.append(compute_host_streams(met_names, ctx))

    # --- aggregate response times ---------------------------------------------
    horizon = T - (drain_margin if drain_margin is not None else max(2 * W + 20, 40))
    resp_list, wts = [], []
    n_keys, n_done = 0, 0
    for key, per_term in resp_acc.items():
        c2, s = key
        if s < warmup or s >= horizon or weights.get(key, 0.0) <= 0:
            continue
        n_keys += 1
        resp = max(acc[1] / acc[0] for acc in per_term.values() if acc[0] > 1e-9)
        resp_list.append(resp)
        wts.append(weights[key])
        n_done += 1
    if resp_list:
        resp_arr, wt_arr = np.array(resp_list), np.array(wts)
        avg = float(np.average(resp_arr, weights=wt_arr))
        order = np.argsort(resp_arr)
        cum = np.cumsum(wt_arr[order]) / wt_arr.sum()
        p95 = float(resp_arr[order][np.searchsorted(cum, 0.95)])
    else:
        avg, p95 = float("nan"), float("nan")
    measured = [k for k in weights if warmup <= k[1] < horizon and weights[k] > 0]
    frame = None
    if metrics is not None:
        cols = [np.stack([row[k] for row in met_rows]) for k in range(len(met_names))]
        frame = build_frame(metrics, cols, n_slots=T, payload_floats=0.0)
    return CohortResult(
        avg_response=avg,
        p95_response=p95,
        avg_backlog=float(backlog_ts[warmup:].mean()) if T > warmup else float(backlog_ts.mean()),
        avg_cost=float(cost_ts[warmup:].mean()) if T > warmup else float(cost_ts.mean()),
        backlog=backlog_ts,
        comm_cost=cost_ts,
        n_cohorts=len(measured),
        completed_frac=(n_done / max(len(measured), 1)),
        completed_mass=completed_mass,
        metrics=frame,
    )
