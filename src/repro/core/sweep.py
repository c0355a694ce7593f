"""Batched scenario-sweep engine (DESIGN.md §6).

The paper's headline results are parameter sweeps — response time vs the
lookahead window W (Fig. 4), backlog/cost vs the Lyapunov weight V (Fig. 5),
robustness vs mis-prediction level (Fig. 6). Running each grid point as a
separate ``simulate(EngineSpec(engine="jax"))`` call pays Python dispatch and
scan overhead N times. Here a sweep is a first-class object:

* :class:`SweepSpec` declares the axes — V, beta, window W, scheduler, and a
  named *arrival scenario* (seed / predictor / mis-prediction level);
* :func:`run_sweep` partitions the grid by the axes that change compiled
  structure (scheduler, window, Pallas path), stacks the per-scenario inputs
  of each partition, and ``jax.vmap``-s the per-slot :func:`sim_step` inside
  one ``lax.scan`` — an entire partition runs as a single compiled
  computation;
* :class:`SweepResult` returns one :class:`SimResult` per scenario, in grid
  order, numerically matching a per-scenario ``simulate`` loop.

Response-time grids have two engines behind the same API: the Python cohort
(discrete-event) engine cannot be ``vmap``-ed — ``engine="cohort"`` runs the
grid through the Python cohort engine sequentially — while
``engine="cohort-fused"`` (DESIGN.md §8) re-expresses the same semantics as
age-tagged arrays under ``lax.scan`` and batches each (scheduler, window,
Pallas) partition exactly like the JAX engine, mis-predicted arrival
scenarios included. Adding a new scenario is one more axis value, not
another Python loop.
"""
from __future__ import annotations

import dataclasses
import itertools
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import build_frame, scan_stream_names

from .engine import (
    OPTION_SUPPORT,
    UnsupportedEngineOption,
    check_engine_option,
    check_keyed,
    check_metrics_spec,
)
from .events import EventTrace, FleetScenario
from .network import NetworkCosts
from .potus import caps_for_slot, make_problem
from .queues import init_state_batch
from .simulator import (
    SimConfig,
    SimResult,
    _check_mu_override,
    _get_scheduler,
    materialize_arrivals,
    _run_sim_impl,
    pad_arrivals,
    sim_step,
    stacked_host_traces,
)
from .topology import Topology

__all__ = ["Scenario", "SweepSpec", "SweepResult", "run_sweep"]


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One point of a sweep grid."""

    index: int
    V: float
    beta: float
    window: int
    scheduler: str
    arrival: str
    use_pallas: bool = False
    sharded: bool = False
    events: str = "none"  # named disruption trace (core.events, DESIGN.md §9)

    def config(self) -> SimConfig:
        return SimConfig(
            V=self.V,
            beta=self.beta,
            window=self.window,
            scheduler=self.scheduler,
            use_pallas=self.use_pallas,
            sharded=self.sharded,
        )

    def matches(self, **axes: Any) -> bool:
        return all(getattr(self, k) == v for k, v in axes.items())


def _as_tuple(v) -> tuple:
    if isinstance(v, tuple):
        return v
    if isinstance(v, (list, np.ndarray)):
        return tuple(v)
    return (v,)


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """Declarative grid of simulator configurations (full cross product).

    ``window``, ``scheduler`` and ``use_pallas`` change the *compiled
    structure* (state shapes / traced scheduler), so they partition the grid;
    V, beta, the arrival scenario and the named disruption trace (``events``,
    core.events) vary inside one compiled batch — the undisturbed ``"none"``
    trace keeps the legacy no-events fast path.
    """

    V: tuple = (3.0,)
    beta: tuple = (1.0,)
    window: tuple = (0,)
    scheduler: tuple = ("potus",)
    arrival: tuple = ("default",)
    events: tuple = ("none",)
    use_pallas: bool = False
    sharded: bool = False

    def __post_init__(self):
        for axis in ("V", "beta", "window", "scheduler", "arrival", "events"):
            object.__setattr__(self, axis, _as_tuple(getattr(self, axis)))
        for flag in ("use_pallas", "sharded"):
            if not isinstance(getattr(self, flag), bool):
                # not an axis: a truthy tuple would silently re-route everything
                raise TypeError(
                    f"{flag} is a single flag, not a sweep axis; run separate "
                    f"sweeps per backend (got {getattr(self, flag)!r})"
                )

    @property
    def n_scenarios(self) -> int:
        return (
            len(self.V) * len(self.beta) * len(self.window)
            * len(self.scheduler) * len(self.arrival) * len(self.events)
        )

    def scenarios(self) -> list[Scenario]:
        """Grid order: events, arrival, scheduler, window, beta outermost;
        V innermost."""
        return [
            Scenario(idx, float(V), float(beta), int(W), sched, arr,
                     self.use_pallas, self.sharded, events=ev)
            for idx, (ev, arr, sched, W, beta, V) in enumerate(
                itertools.product(self.events, self.arrival, self.scheduler,
                                  self.window, self.beta, self.V)
            )
        ]


@dataclasses.dataclass
class SweepResult:
    spec: SweepSpec
    scenarios: list[Scenario]
    results: list  # SimResult | CohortResult, aligned with ``scenarios``
    n_batches: int  # number of separately-compiled scenario partitions

    def __len__(self) -> int:
        return len(self.scenarios)

    def __iter__(self):
        return iter(zip(self.scenarios, self.results))

    def select(self, **axes: Any) -> list[tuple[Scenario, Any]]:
        """All (scenario, result) pairs whose axes match, in grid order."""
        return [(s, r) for s, r in self if s.matches(**axes)]

    def result(self, **axes: Any):
        """The single result matching ``axes``; raises if not exactly one."""
        hits = self.select(**axes)
        if len(hits) != 1:
            raise KeyError(f"{axes} matches {len(hits)} scenarios, expected 1")
        return hits[0][1]


@partial(jax.jit, static_argnames=("scheduler", "use_pallas", "shared_inputs",
                                   "events_shared", "metrics_spec"),
         donate_argnames=("states0",))
def _scan_sweep(
    prob,
    states0,  # SimState pytree, leading scenario axis S (always batched)
    streams: jax.Array,  # (S, T, I, C) window-entry streams ((T, I, C) if shared)
    U: jax.Array,  # (K, K)
    mu: jax.Array,  # (I,)
    selectivity_rows: jax.Array,  # (I, C)
    Vs: jax.Array,  # (S,)
    betas: jax.Array,  # (S,)
    events_s=None,  # (S?, T, I) (mu_t, gamma_t, alive_t) triple, or None
    scheduler: str = "potus",
    use_pallas: bool = False,
    shared_inputs: bool = False,
    events_shared: bool = False,
    metrics_spec=None,  # static MetricsSpec | None (DESIGN.md §14)
):
    sched = _get_scheduler(scheduler, use_pallas)
    u_pair = U[prob.inst_container[:, None], prob.inst_container[None, :]]

    def one(state0, stream, V, beta, ev):
        def step(state, xs):
            if ev is None:
                new_arr, caps = xs, None
            else:
                new_arr, (mu_row, gamma_row, alive_row) = xs
                caps = caps_for_slot(mu_row, gamma_row, alive_row)
            return sim_step(prob, sched, U, u_pair, mu, selectivity_rows, V, beta,
                            state, new_arr, caps=caps, metrics_spec=metrics_spec)

        xs = stream if ev is None else (stream, ev)
        return jax.lax.scan(step, state0, xs)

    # when every scenario in the batch shares one arrival tensor (a pure
    # V/beta sweep), scan a single stream instead of S stacked copies; the
    # state is always batched so a chunked run can feed each chunk's final
    # states straight back in as the next chunk's initial states
    ev_ax = None if (events_s is None or events_shared) else 0
    in_axes = (0,) + ((None, 0, 0) if shared_inputs else (0, 0, 0)) + (ev_ax,)
    return jax.vmap(one, in_axes=in_axes)(states0, streams, Vs, betas, events_s)


def _normalize_arrivals(
    arrivals, spec: SweepSpec, topo: Topology, n_slots: int
) -> dict[str, tuple[np.ndarray, np.ndarray | None]]:
    """name -> (actual, predicted|None). A bare array (or ``ArrivalSpec``) is
    the scenario ``"default"`` with perfect prediction; ``ArrivalSpec``
    values are materialized here against the sweep's topology and horizon."""
    from .workload import ArrivalSpec

    if isinstance(arrivals, (np.ndarray, ArrivalSpec)):
        arrivals = {"default": arrivals}
    out: dict[str, tuple[np.ndarray, np.ndarray | None]] = {}
    for name, val in arrivals.items():
        if isinstance(val, tuple):
            actual, predicted = val
        else:
            actual, predicted = val, None
        actual = materialize_arrivals(actual, topo, n_slots)
        if predicted is not None:
            predicted = materialize_arrivals(predicted, topo, n_slots)
        out[name] = (actual, predicted)
    missing = [a for a in spec.arrival if a not in out]
    if missing:
        raise KeyError(f"spec names arrival scenarios {missing} not present in arrivals")
    return out


def _normalize_events(
    events, spec: SweepSpec, topo: Topology, T: int, inst_container: np.ndarray
) -> dict[str, EventTrace | None]:
    """name -> EventTrace|None. ``"none"`` is always the undisturbed fleet;
    :class:`FleetScenario` values are compiled here (with the placement
    vector, so container-level outages resolve)."""
    out: dict[str, EventTrace | None] = {"none": None}
    for name, val in (events or {}).items():
        if val is None:
            out[name] = None
        elif isinstance(val, FleetScenario):
            out[name] = val.compile(topo, T, placement=inst_container)
        elif isinstance(val, EventTrace):
            out[name] = val
        else:
            raise TypeError(f"events[{name!r}] must be FleetScenario | EventTrace | None")
    missing = [e for e in spec.events if e not in out]
    if missing:
        raise KeyError(f"spec names event scenarios {missing} not present in events")
    return out


def run_sweep(
    topo: Topology,
    net: NetworkCosts,
    inst_container: np.ndarray,
    arrivals,  # np.ndarray | dict[str, np.ndarray | (actual, predicted)]
    T: int,
    spec: SweepSpec,
    mu: np.ndarray | None = None,
    engine: str = "jax",  # jax (batched) | cohort-fused (batched responses) | cohort
    engine_opts: dict | None = None,  # warmup/drain_margin/age_cap/service (cohort
    #   engines) and "chunk" (streaming scan, jax + cohort-fused; DESIGN.md §11.2)
    events=None,  # dict[str, FleetScenario | EventTrace | None] for spec.events
) -> SweepResult:
    """Run every scenario of ``spec`` and return per-scenario results.

    The JAX engine batches all scenarios that share (scheduler, window,
    use_pallas, events-or-not) into one vmapped ``lax.scan``; results agree
    elementwise with a per-scenario ``simulate`` loop. Response-time
    grids use ``engine="cohort-fused"`` (batched the same way, DESIGN.md §8)
    or the sequential Python event loop ``engine="cohort"`` (the semantic
    oracle). Named disruption traces (``spec.events`` / the ``events`` map,
    core.events) form one more scenario axis on every engine.

    ``engine_opts={"chunk": n}`` streams each scan ``n`` slots at a time
    (carry checkpointing, host-resident streams) on the ``jax`` and
    ``cohort-fused`` engines, so deep horizons run at fixed device memory.
    """
    scenarios = spec.scenarios()
    for scn in scenarios:  # a fields grouping lands on one path only (DESIGN.md §15)
        check_keyed("sharded" if engine == "jax" and scn.sharded else engine, topo,
                    scn.scheduler, scn.use_pallas, scn.sharded)
    arr_map = _normalize_arrivals(arrivals, spec, topo, T + max(spec.window) + 1)
    ev_map = _normalize_events(events, spec, topo, T, inst_container)
    chunk = (engine_opts or {}).get("chunk")
    if chunk is not None and (not isinstance(chunk, (int, np.integer)) or chunk <= 0):
        raise ValueError(f"engine_opts['chunk'] must be a positive slot count, got {chunk!r}")
    # engine_opts["metrics"] selects in-scan metric streams for every
    # scenario (DESIGN.md §14); stream availability is engine-checked with
    # the same normalized error as a whole unsupported option
    metrics_spec = check_metrics_spec(
        engine if engine != "jax" or not spec.sharded else "sharded",
        (engine_opts or {}).get("metrics"),
    )

    if engine in ("cohort", "cohort-fused"):
        if mu is not None:
            raise UnsupportedEngineOption(engine, "mu")
        if spec.sharded and engine == "cohort":
            # cohort-fused passes spec.sharded through to run_fused_sweep,
            # which shards every partition's vmapped scan (DESIGN.md §13)
            raise UnsupportedEngineOption(engine, "sharded")
        opts = dict(engine_opts or {})
        opts.pop("metrics", None)  # already coerced to metrics_spec above
        if engine == "cohort-fused":
            from .cohort_fused import run_fused_sweep

            results, n_batches = run_fused_sweep(
                topo, net, inst_container, arr_map, T, spec, events_map=ev_map,
                metrics=metrics_spec, **opts
            )
            return SweepResult(spec, scenarios, results, n_batches=n_batches)
        from .cohort import _run_cohort_sim_impl

        if opts.get("service") is not None:
            check_engine_option("cohort", "service")
        if opts.get("chunk") is not None:
            check_engine_option("cohort", "chunk")
        if opts.get("slots_per_launch", 1) != 1:
            check_engine_option("cohort", "slots_per_launch")
        opts.pop("service", None)
        opts.pop("chunk", None)
        opts.pop("age_cap", None)  # the event loop tracks ages exactly
        opts.pop("slots_per_launch", None)  # fused-engine launch knob
        results = []
        for scn in scenarios:
            actual, predicted = arr_map[scn.arrival]
            results.append(
                _run_cohort_sim_impl(topo, net, inst_container, actual, predicted,
                                     T, scn.config(), events=ev_map[scn.events],
                                     metrics=metrics_spec, **opts)
            )
        return SweepResult(spec, scenarios, results, n_batches=len(scenarios))
    if engine != "jax":
        raise ValueError(f"unknown engine {engine!r}")
    for opt in sorted(set(engine_opts or {}) - {"chunk"}):
        if opt not in OPTION_SUPPORT:
            raise ValueError(f"unknown engine_opts key {opt!r}")
        check_engine_option("jax", opt)
    active_traces = [t for t in (ev_map[scn.events] for scn in scenarios) if t is not None]
    if active_traces:
        _check_mu_override(mu, active_traces[0])
    mispredicted = [a for a in spec.arrival if arr_map[a][1] is not None]
    if mispredicted:
        # arrival scenarios carrying distinct 'predicted' streams only make
        # sense on the cohort engines (the JAX engine treats its single
        # stream as the predicted/actual arrivals combined)
        check_engine_option("jax", "predicted")
    if spec.sharded:
        if chunk is not None:
            check_engine_option("sharded", "chunk")
        # shard_map partitions the instance axis across devices; scenarios are
        # not additionally vmapped (the sharded path targets single big-I
        # scenarios, not wide grids) — run the grid sequentially (DESIGN.md §7)
        results = [
            _run_sim_impl(topo, net, inst_container, arr_map[scn.arrival][0], T,
                          scn.config(), mu=mu, events=ev_map[scn.events],
                          metrics=metrics_spec)
            for scn in scenarios
        ]
        return SweepResult(spec, scenarios, results, n_batches=len(scenarios))

    prob = make_problem(topo, net, inst_container)
    mu_arr = jnp.asarray(mu if mu is not None else topo.inst_mu, jnp.float32)
    sel_rows = jnp.asarray(topo.selectivity[topo.inst_comp], jnp.float32)
    U = jnp.asarray(net.U)

    # partition by the axes that change compiled structure; scenarios with a
    # disruption trace scan extra per-slot inputs, so they batch separately
    # from the undisturbed fast path
    groups: dict[tuple, list[Scenario]] = {}
    for scn in scenarios:
        key = (scn.scheduler, scn.window, scn.use_pallas, ev_map[scn.events] is not None)
        groups.setdefault(key, []).append(scn)

    results: list[SimResult | None] = [None] * len(scenarios)
    for (scheduler, W, use_pallas, has_events), group in groups.items():
        S = len(group)
        shared = len({scn.arrival for scn in group}) == 1
        # streams stay host-resident; the chunk loop below transfers one
        # slice at a time (the monolithic run is the single-chunk case)
        if shared:
            p = pad_arrivals(arr_map[group[0].arrival][0].astype(np.float32, copy=False), T + W + 1)
            streams = p[W + 1 : T + W + 1]
            prefixes = np.broadcast_to(p[: W + 1], (S,) + p[: W + 1].shape)
        else:
            # one stacked stream per scenario, even when some scenarios share
            # an arrival tensor — duplicates cost memory, never correctness;
            # grids mixing many (V, arrival) pairs could dedup here if needed
            padded = [
                pad_arrivals(arr_map[scn.arrival][0].astype(np.float32, copy=False), T + W + 1)
                for scn in group
            ]
            prefixes = np.stack([p[: W + 1] for p in padded])  # (S, W+1, I, C)
            streams = np.stack([p[W + 1 : T + W + 1] for p in padded])
        states = init_state_batch(topo, W, prefixes)
        Vs = jnp.asarray([scn.V for scn in group], jnp.float32)
        betas = jnp.asarray([scn.beta for scn in group], jnp.float32)
        ev_host, ev_shared = None, True
        if has_events:
            ev_host, ev_shared = stacked_host_traces(
                [scn.events for scn in group], [ev_map[scn.events] for scn in group], T
            )

        tc = T if chunk is None else int(chunk)
        n_streams = (0 if metrics_spec is None
                     else len(scan_stream_names(metrics_spec)))
        outs: list[list[np.ndarray]] = [[] for _ in range(5 + n_streams)]
        for t0 in range(0, T, tc) or [0]:
            t1 = min(t0 + tc, T)
            stream_c = jnp.asarray(streams[t0:t1] if shared else streams[:, t0:t1])
            ev_c = None
            if ev_host is not None:
                ev_c = tuple(
                    jnp.asarray(e[t0:t1] if ev_shared else e[:, t0:t1]) for e in ev_host
                )
            states, per_slot = _scan_sweep(
                prob, states, stream_c, U, mu_arr, sel_rows, Vs, betas,
                events_s=ev_c, events_shared=ev_shared,
                scheduler=scheduler, use_pallas=use_pallas, shared_inputs=shared,
                metrics_spec=metrics_spec,
            )
            for acc, piece in zip(outs, per_slot):
                acc.append(np.asarray(piece))
        h, cost, qi, qo, served = (np.concatenate(a, axis=1) for a in outs[:5])
        met_arrays = [np.concatenate(a, axis=1) for a in outs[5:]]  # (S, T, w)
        final = jax.device_get(states)
        for s, scn in enumerate(group):
            frame = None
            if metrics_spec is not None:
                frame = build_frame(metrics_spec, [a[s] for a in met_arrays],
                                    n_slots=T, payload_floats=0.0)
            results[scn.index] = SimResult(
                backlog=h[s],
                comm_cost=cost[s],
                q_in_total=qi[s],
                q_out_total=qo[s],
                served_total=served[s],
                final_state=jax.tree_util.tree_map(lambda x: x[s], final),
                metrics=frame,
            )
    return SweepResult(spec, scenarios, results, n_batches=len(groups))
