"""Fused cohort engine — response-time semantics as one JAX ``lax.scan``
(DESIGN.md §8).

The Python cohort engine (``core.cohort``) reproduces the paper's per-tuple
response-time metric (§5.1, Figs. 4/6) but is interpreter-bound: a per-slot
event loop over dict/deque FIFOs with a host round-trip into the jitted
scheduler every slot, which ``core.sweep`` cannot ``vmap``. This module
re-expresses the same semantics on dense arrays so the whole T-slot
simulation compiles to a single ``lax.scan`` (schedulers traced in-graph)
and entire scenario grids batch with ``jax.vmap``
(``run_sweep(engine="cohort-fused")``).

Representation (DESIGN.md §8): every FIFO becomes an **age-by-source-slot
mass matrix**. At slot ``t``, bucket ``b`` of an age axis of depth
``Atot = age_cap + W + 1`` holds the tuple mass whose *source slot* (the
actual-arrival slot its response is measured from) is ``s = t - age_cap + b``
— bucket ``age_cap`` is mass arriving this slot, buckets above it are
pre-served future mass (negative age), bucket 0 saturates at age ``age_cap``
(the A-cap truncation rule). Queues are stored **successor-compact**: output
state carries an axis of size ``S = max successors per component`` instead
of all C components, and the per-slot hot ops — the oldest-first drain and
the proportional split of drained mass over successor instances — run as
per-DAG-edge blocks over the (statically contiguous) instance ranges of each
component, so their cost scales with the edges that exist rather than I x C.
State per scenario:

* ``q_rem``   (I, S, W+1)  — spout lookahead windows (untreated mass);
* ``admit``   (I, S)       — admission backlog of unshipped actuals;
* ``q_in``    (I, Atot)    — bolt input queues, mass per age bucket;
* ``q_out``   (I, S, Atot) — bolt output queues, mass per age bucket;
* ``transit`` (I, Atot)    — mass landing in input queues next slot.

FIFO ``drain(amount)`` becomes a masked prefix-sum along the age axis
("water-fill over ages": ``clip(amount - cum_before, 0, bucket)``), window
reconciliation (TP/FP/TN mis-prediction splitting, phantom pre-serves,
admission backlog) becomes pure array ops, and the drain + split is
optionally fused into one VMEM pass by the Pallas kernel
``kernels/cohort_drain.py`` (behind ``use_pallas``).

Deliberate deltas vs the Python engine, documented in DESIGN.md §8: queues
serve oldest-*source-slot*-first instead of oldest-*push*-first (identical
drain totals, so scheduler inputs — and therefore backlog and cost — match;
only the response attribution of partially-drained mixed queues shifts), and
cohorts of one source slot are merged across entry components that reach a
common terminal (the per-key max of §2 runs over the terminals *reachable*
from each entry component). Both engines share the within-cohort mean
approximation. Parity is differentially tested in
``tests/test_cohort_fused.py`` — bit-level on exact-arithmetic systems,
statistically on the paper-profile grids, where f32-vs-f64 near-tie flips
make queue-feedback schedulers (POTUS, JSQ) chaotically sensitive (the same
phenomenon ``tests/test_core_dynamics.py`` documents between the JAX and
cohort engines).
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import warnings
from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from typing import NamedTuple

from jax.sharding import PartitionSpec as P

from repro.distributed.context import shard_map_compat
from repro.distributed.sharding import named
from repro.obs.metrics import build_frame, compute_scan_streams, scan_stream_names
from repro.obs.trace import count as obs_count
from repro.obs.trace import span as obs_span
from repro.obs.trace import tracing_enabled

from .cohort import CohortResult
from .compact import COMPACT_SCHEDULERS, StepConsts, compact_slot_step
from .network import NetworkCosts
from .potus import caps_for_slot, make_problem
from .sharded import (
    COHORT_AXIS,
    cohort_slot_payload_floats,
    cohort_state_specs,
    instance_mesh,
)
from .simulator import (
    SimConfig,
    _get_scheduler,
    host_trace,
    materialize_arrivals,
    pad_arrivals,
    stacked_host_traces,
)
from .topology import Topology

__all__ = ["run_fused_sweep", "drain_ages", "AgeCapSaturationWarning", "scan_hlo_text"]

_EPS = 1e-12  # same negligible-mass threshold as the Python engine's FIFOs

#: ``saturated_frac`` above this emits :class:`AgeCapSaturationWarning` —
#: past ~1% capped completions the response mean is visibly biased low.
SATURATION_WARN_FRAC = 0.01


class AgeCapSaturationWarning(UserWarning):
    """A cohort-fused run truncated a non-negligible completed-mass fraction
    at the ``age_cap`` saturation bucket, so reported response times are
    biased low (DESIGN.md §8). Re-run with the suggested deeper cap."""


def _maybe_warn_saturation(saturated_frac: float, age_cap: int,
                           label: str | None = None) -> None:
    """``label`` names the run (scenario / sweep partition) in the warning —
    without it a sweep emitting several of these gave no way to tell *which*
    grid point saturated."""
    if saturated_frac > SATURATION_WARN_FRAC:
        where = f" [{label}]" if label else ""
        warnings.warn(
            f"{saturated_frac:.1%} of terminal completions{where} hit the "
            f"age_cap={age_cap} saturation bucket: response times are "
            f"silently truncated (biased low). Re-run with a deeper cap, "
            f"e.g. age_cap={2 * age_cap}.",
            AgeCapSaturationWarning,
            stacklevel=3,
        )


def drain_ages(buckets: jax.Array, amount: jax.Array) -> jax.Array:
    """Mass removed from each age bucket when ``amount`` is drained
    oldest-first: a masked prefix-sum water-fill along the last axis.

    Returns an array like ``buckets``; total removed is
    ``min(amount, buckets.sum(-1))`` and removal is always an age *prefix*
    (a bucket is touched only once every older bucket is empty) — the two
    invariants the hypothesis property in ``tests/test_cohort_fused.py``
    pins down.
    """
    cum = jnp.cumsum(buckets, axis=-1)
    return jnp.clip(amount[..., None] - (cum - buckets), 0.0, buckets)


class _CompactProb(NamedTuple):
    """The O(I) slice of :class:`~repro.core.potus.SchedProblem` the compact
    one-dispatch path consumes — everything but the (I, I) ``edge_mask``, so
    fleet-scale (and instance-sharded, DESIGN.md §13) runs never materialize
    O(I²) anywhere. Field dtypes mirror :func:`~repro.core.potus.make_problem`
    exactly; only ``potus-loop`` (the dense reference scheduler) still needs
    the full problem."""

    inst_comp: jax.Array  # (I,) int32
    inst_container: jax.Array  # (I,) int32
    gamma: jax.Array  # (I,)
    comp_count: jax.Array  # (C,) f32
    is_spout: jax.Array  # (C,)[inst_comp] bool


def _compact_prob(topo: Topology, inst_container) -> _CompactProb:
    return _CompactProb(**_resident(dict(
        inst_comp=topo.inst_comp,
        inst_container=np.asarray(inst_container, np.int32),
        gamma=topo.inst_gamma,
        comp_count=np.asarray(topo.comp_parallelism, np.float32),
        is_spout=topo.comp_is_spout[topo.inst_comp],
    )))


def _host(x, dtype=None) -> np.ndarray:
    """``x`` as the host array JAX would place: canonical dtype."""
    a = np.asarray(x, dtype)
    return a.astype(jax.dtypes.canonicalize_dtype(a.dtype), copy=False)


def _to_device(x, dtype=None) -> jax.Array:
    """``jnp.asarray`` of a host array; its bytes count as ``h2d_bytes``."""
    a = _host(x, dtype)
    obs_count("h2d_bytes", a.nbytes)
    return jnp.asarray(a)


#: device copies of slot-invariant inputs, by content (see :func:`_resident`)
_RESIDENT: OrderedDict = OrderedDict()
_RESIDENT_MAX = 4
_RESIDENT_LOCK = threading.Lock()


def _resident(arrays: dict) -> dict:
    """Device copies of ``arrays``, uploaded once per content: calls on one
    deployment reuse them. On a TPU v5e host every transfer costs ~0.25 ms
    of host time whatever its size, so re-sending a topology's constants
    each call cost more than the arrival streams did (PERF.md §6). The key
    hashes every array's name, dtype, shape and bytes; the cache keeps the
    last ``_RESIDENT_MAX`` contents."""
    host = {k: _host(v) for k, v in arrays.items()}
    h = hashlib.blake2b(digest_size=20)
    for k, a in host.items():
        h.update(f"{k}:{a.dtype.str}:{a.shape}".encode())
        h.update(np.ascontiguousarray(a).data)
    key = h.digest()
    with _RESIDENT_LOCK:
        dev = _RESIDENT.get(key)
        if dev is None:
            dev = _RESIDENT[key] = {k: _to_device(a) for k, a in host.items()}
            if len(_RESIDENT) > _RESIDENT_MAX:
                _RESIDENT.popitem(last=False)
        else:
            _RESIDENT.move_to_end(key)
        return dict(dev)


def _to_device_stream(x) -> jax.Array:
    """``_to_device`` of packed arrival lanes; their bytes also count as
    ``packed_stream_bytes``."""
    a = _to_device(x, np.float32)
    obs_count("packed_stream_bytes", a.nbytes)
    return a


def _fetch(x) -> np.ndarray:
    """``np.asarray`` of a device array (blocks on it); its bytes count as
    ``d2h_bytes``."""
    a = np.asarray(x)
    obs_count("d2h_bytes", a.nbytes)
    return a


# ---------------------------------------------------------------------------
# successor-compact topology view
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Compact:
    """Static successor-compact structure of one topology.

    ``edges`` drives the per-edge blocked drain-split: one entry per DAG edge
    (source component -> successor component), carrying the source instance
    range, the successor's slot in the source's successor list, and the
    target instance range. Instance ranges are contiguous by construction
    (``build_topology`` appends instances in component order).
    """

    S: int  # max successors of any component (>= 1)
    edges: tuple  # ((row_start, row_end, slot, col_start, col_end), ...)
    succ_map: np.ndarray  # (I, S) int32 successor comp per slot; C = no edge
    valid: np.ndarray  # (I, S) f32 — 1 where the slot is a real successor
    sel_cmp: np.ndarray  # (I, S) f32 — selectivity toward each successor
    stream_cmp: np.ndarray  # (I, S) f32 — valid & spout row (window streams)
    adj_rows: np.ndarray  # (I, C) f32 — 1 where comp(i) -> c is a DAG edge
    lanes: np.ndarray  # (2, L) int32 — (instance, successor comp) of each stream lane
    lane_slot: np.ndarray  # (L,) — each stream lane's successor slot


def _compact(topo: Topology) -> _Compact:
    I, C = topo.n_instances, topo.n_components
    is_spout = topo.comp_is_spout[topo.inst_comp]
    S = max(1, max((len(topo.successors_of_comp(c)) for c in range(C)), default=1))
    succ_map = np.full((I, S), C, np.int32)
    valid = np.zeros((I, S), np.float32)
    sel_cmp = np.zeros((I, S), np.float32)
    adj_rows = np.zeros((I, C), np.float32)
    edges = []
    for c in range(C):
        rows = topo.instances_of(c)
        if len(rows) == 0:
            continue
        if rows[-1] - rows[0] + 1 != len(rows):
            raise ValueError(
                f"instances of component {c} are not contiguous; the fused "
                "cohort engine requires build_topology-style instance order"
            )
        rs, re = int(rows[0]), int(rows[-1]) + 1
        for s, c2 in enumerate(topo.successors_of_comp(c)):
            cols = topo.instances_of(int(c2))
            cs, ce = int(cols[0]), int(cols[-1]) + 1
            edges.append((rs, re, s, cs, ce))
            succ_map[rs:re, s] = c2
            valid[rs:re, s] = 1.0
            sel_cmp[rs:re, s] = topo.selectivity[c, c2]
            adj_rows[rs:re, c2] = 1.0
    stream_cmp = valid * is_spout[:, None].astype(np.float32)
    # the stream lanes, in row-major (instance, successor component) order:
    # successors ascend, so row-major (instance, slot) order is the same
    lane_i, lane_slot = np.nonzero(stream_cmp)
    lanes = np.stack([lane_i, succ_map[lane_i, lane_slot]]).astype(np.int32)
    return _Compact(S, tuple(edges), succ_map, valid, sel_cmp, stream_cmp,
                    adj_rows, lanes, lane_slot)


def _fused_step(
    prob,
    sched,
    edges: tuple,
    U: jax.Array,  # (K, K)
    u_pair: jax.Array,  # (I, I)
    mu: jax.Array,  # (I,)
    inv_service: jax.Array,  # (I,) 1/service-time; converts mu to tuples/slot
    sel_cmp: jax.Array,  # (I, S)
    stream_cmp: jax.Array,  # (I, S)
    valid_cmp: jax.Array,  # (I, S)
    succ_map: jax.Array,  # (I, S) int32
    term_f: jax.Array,  # (I,) share of served mass completing (1.0 on terminal bolts)
    comp_onehot: jax.Array,  # (I, C)
    age_cap: int,
    use_pallas: bool,
    V: jax.Array,
    beta: jax.Array,
    state=None,
    xs=None,
    metrics_spec=None,
):
    """One slot of the cohort dynamics (mirrors ``core.cohort`` step order).

    ``xs`` optionally carries a fifth element — one slot of a disruption
    trace ``(mu_row, gamma_row, alive_row)`` (DESIGN.md §9). The scheduler
    then prices dead instances out, bolts serve at the slot's effective
    ``mu``, and a dead spout's mandatory arrivals flow into the admission
    backlog (step 5 already retains every unshipped pos-0 remainder, so
    disruption adds no new mass-loss path: stranded mass holds its age tags
    — which keep aging through the outage — and re-drains on recovery).

    ``inv_service`` is the token-length service-time axis (DESIGN.md §10):
    ``mu`` stays in raw capacity units (e.g. tokens/slot) while queues count
    tuples, and each slot a bolt completes ``mu[i] / service[i]`` tuples.
    All-ones is bit-transparent; event-trace ``mu_t`` rows stay in the same
    raw units and get the same conversion.
    """
    act_t, pred_t, new_pred, t, *ev = xs
    caps = caps_for_slot(*ev[0]) if ev else None
    mu = (mu if caps is None else caps.mu) * inv_service
    q_rem, admit, q_in_tag, q_out_tag, transit, resp_mass, resp_time = state
    I, S, W1 = q_rem.shape
    C = comp_onehot.shape[1]
    Atot = q_in_tag.shape[-1]  # = age_cap + (W1 - 1) + 1
    is_spout = prob.is_spout
    spout_f = is_spout.astype(q_rem.dtype)
    bolt_f = 1.0 - spout_f
    rows = jnp.arange(I)[:, None]
    gather_idx = jnp.minimum(succ_map, C - 1)

    def to_dense(x_cmp):  # (I, S) -> (I, C); the C sentinel column is dropped
        return jnp.zeros((I, C + 1), x_cmp.dtype).at[rows, succ_map].add(x_cmp)[:, :C]

    def to_cmp(x):  # (I, C) -> (I, S)
        return jnp.take_along_axis(x, gather_idx, axis=1) * valid_cmp

    # -- 1. reconcile window pos-0 with actual arrivals of slot t ------------
    with jax.named_scope("reconcile"):
        pred_m = to_cmp(pred_t) * stream_cmp
        act_m = to_cmp(act_t) * stream_cmp
        tp = jnp.minimum(pred_m, act_m)
        tn = act_m - tp
        r = jnp.where(pred_m > 0, q_rem[:, :, 0] / jnp.where(pred_m > 0, pred_m, 1.0), 0.0)
        q_rem = q_rem.at[:, :, 0].set(r * tp + tn)  # drop unserved phantoms

    # -- 2. observe queue state, schedule ------------------------------------
    with jax.named_scope("decide"):
        q_in_arr = q_in_tag.sum(-1)
        q_out_cmp = jnp.where(is_spout[:, None], q_rem.sum(-1), q_out_tag.sum(-1))
        q_out_arr = to_dense(q_out_cmp)
        must_send = to_dense((q_rem[:, :, 0] + admit) * spout_f[:, None])
        X = sched(prob, U, q_in_arr, q_out_arr, must_send, V, beta, caps=caps)
        backlog = q_in_arr.sum() + beta * q_out_arr.sum()
        cost = (X * u_pair).sum()

    # -- 3. drain sources oldest-first, split over targets -------------------
    with jax.named_scope("drain"):
        # requested mass per successor slot: blocked column sums over DAG edges
        shipped = jnp.zeros((I, S), q_rem.dtype)
        for (rs, re, s, cs, ce) in edges:
            shipped = shipped.at[rs:re, s].set(X[rs:re, cs:ce].sum(axis=1))
        # unified drain buffer: bolts ship from q_out buckets; spouts ship the
        # window in ascending lookahead (buckets age_cap..age_cap+W), then the
        # admission backlog (a trailing slot, re-tagged to age 0 when it lands)
        src_spout = jnp.concatenate(
            [jnp.zeros((I, S, age_cap), q_rem.dtype), q_rem, admit[:, :, None]], axis=-1
        )
        src_bolt = jnp.concatenate([q_out_tag, jnp.zeros((I, S, 1), q_rem.dtype)], axis=-1)
        src_ext = jnp.where(is_spout[:, None, None], src_spout, src_bolt)  # (I, S, Atot+1)
        drained = drain_ages(src_ext, shipped)
        q_rem = q_rem - drained[:, :, age_cap:Atot] * spout_f[:, None, None]
        admit = admit - drained[:, :, -1] * spout_f[:, None]
        q_out_tag = q_out_tag - drained[:, :, :Atot] * bolt_f[:, None, None]

        if use_pallas:
            from repro.kernels import ops as kops

            # the kernel's split is component-dense: expand the compact buffers
            src_dense = jnp.zeros((I, C + 1, Atot + 1), q_rem.dtype)
            src_dense = src_dense.at[rows, succ_map, :].add(src_ext)[:, :C]
            ship_dense = to_dense(shipped)
            ship_cols = ship_dense[:, prob.inst_comp]  # (I, I)
            ratio = jnp.where(ship_cols > _EPS, X / jnp.where(ship_cols > 0, ship_cols, 1.0), 0.0)
            land = kops.cohort_drain_split(src_dense, ship_dense, ratio, prob.inst_comp, age_cap)
        else:
            # proportional split, one skinny matmul per DAG edge
            land = jnp.zeros((I, Atot), q_rem.dtype)
            for (rs, re, s, cs, ce) in edges:
                d_land = drained[rs:re, s, :Atot].at[:, age_cap].add(drained[rs:re, s, -1])
                sh = shipped[rs:re, s]
                ratio_b = jnp.where(
                    (sh > _EPS)[:, None], X[rs:re, cs:ce] / jnp.where(sh > 0, sh, 1.0)[:, None], 0.0
                )
                land = land.at[cs:ce].add(jax.lax.dot_general(
                    ratio_b, d_land, (((0,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST, preferred_element_type=q_rem.dtype,
                ))

    # -- 4. land last slot's transit, serve bolts ----------------------------
    with jax.named_scope("land"):
        avail = q_in_tag + transit
        served_amt = jnp.minimum(avail.sum(-1), mu) * bolt_f
        served_b = drain_ages(avail, served_amt)
        q_in_tag = (avail - served_b) * bolt_f[:, None]
        # terminal completions -> response accumulators, indexed by *chunk-local*
        # source slot: ``t`` counts slots within this scan segment, and bucket
        # ``b`` of slot ``t`` holds source slot ``t0 + t - age_cap + b``, which
        # is accumulator column ``t + b`` (the accumulator spans the chunk's
        # global source-slot range [t0 - age_cap, t0 + Tc + W]; the host loop
        # adds each chunk's slab at offset t0 - age_cap, DESIGN.md §11.2)
        # full f32 (mass-carrying): the TPU's default precision rounds through bf16
        cmass = jnp.dot(comp_onehot.T, served_b * term_f[:, None],
                        precision=jax.lax.Precision.HIGHEST)  # (C, Atot)
        resp_per_b = jnp.maximum(
            age_cap - jnp.arange(Atot, dtype=q_rem.dtype), 0.0
        )  # clip(t - s, 0); saturated mass reports age_cap
        idx = t + jnp.arange(Atot)  # always in range: accumulator length Tc + Atot
        resp_mass = resp_mass.at[:, idx].add(cmass, mode="drop")
        resp_time = resp_time.at[:, idx].add(cmass * resp_per_b[None, :], mode="drop")
        # completions reporting the capped response — nonzero means age_cap is
        # (or is close to) too shallow and the response metric is biased low
        capped_served = cmass[:, 0].sum()
        term_served = cmass.sum()
        # emissions: served * selectivity into own output queues (same buckets)
        q_out_tag = q_out_tag + served_b[:, None, :] * sel_cmp[:, :, None] * bolt_f[:, None, None]

    # -- 5. admit leftover actuals, shift windows and age axes ---------------
    with jax.named_scope("admit"):
        admit = admit + q_rem[:, :, 0] * spout_f[:, None]
        q_rem = jnp.concatenate(
            [q_rem[:, :, 1:], (to_cmp(new_pred) * stream_cmp)[:, :, None]], axis=-1
        )

        def shift(x):  # age b+1 -> b; the oldest bucket saturates (A-cap rule)
            head = x[..., 0:1] + x[..., 1:2]
            return jnp.concatenate([head, x[..., 2:], jnp.zeros_like(x[..., 0:1])], axis=-1)

        state = (q_rem, admit, shift(q_in_tag), shift(q_out_tag), shift(land), resp_mass, resp_time)
    out = (backlog, cost, capped_served, term_served)
    if metrics_spec is not None:
        # §14 metric streams as extra scan outputs (dense reference path)
        with jax.named_scope("metrics"):
            landed = land.sum(-1)
            ctx = {
                "h": backlog,
                "q_in": q_in_arr,
                "price": V * U.mean(axis=0)[prob.inst_container] + q_in_arr,
                "landed": landed,
                "transit_total": landed.sum(),
                "comp_backlog": comp_onehot.T @ q_in_arr,
                "held": admit.sum(),
                "dropped": (r * (pred_m - tp)).sum(),
                "tp": tp.sum(), "fp": (pred_m - tp).sum(), "tn": tn.sum(),
                "capped": capped_served, "served": term_served,
            }
            out = out + compute_scan_streams(scan_stream_names(metrics_spec), ctx)
    return state, out


def _kernel_launches(consts, state, actual, pred, nxt, scheduler, age_cap,
                     slots_per_launch):
    """Drive one scenario's chunk through the Pallas slot kernel: a
    ``lax.scan`` of K-slot megakernel launches plus one ragged-tail launch
    (DESIGN.md §12). Shared by the dense scan and the single-shard sharded
    scan — the kernel body contains no collectives, so under ``shard_map``
    it only runs when the mesh has one shard (DESIGN.md §13)."""
    from repro.kernels import ops as kops

    T = actual.shape[0]
    K = max(1, slots_per_launch)
    nb, tail = T // K, T % K

    def launch(state, xs_b, n_slots):
        act_b, pred_b, nxt_b, t0 = xs_b
        return kops.potus_slot_step(
            consts, state, act_b, pred_b, nxt_b, t0,
            scheduler=scheduler, age_cap=age_cap, n_slots=n_slots,
        )

    mets = []
    if nb:
        blk = (actual[: nb * K].reshape(nb, K, *actual.shape[1:]),
               pred[: nb * K].reshape(nb, K, *pred.shape[1:]),
               nxt[: nb * K].reshape(nb, K, *nxt.shape[1:]),
               jnp.arange(nb, dtype=jnp.int32) * K)
        state, m = jax.lax.scan(partial(launch, n_slots=K), state, blk)
        mets.append(jax.tree.map(lambda y: y.reshape(nb * K), m))
    if tail:
        state, m = launch(
            state,
            (actual[nb * K:], pred[nb * K:], nxt[nb * K:], jnp.int32(nb * K)),
            n_slots=tail,
        )
        mets.append(m)
    backlog, cost, capped, served = (
        jax.tree.map(lambda *ys: jnp.concatenate(ys), *mets)
        if len(mets) > 1 else mets[0]
    )
    return state, (backlog, cost, capped.sum(), served.sum())


def _step_consts(prob, comp_onehot, U, mu, inv_service, sel_cmp, stream_cmp,
                 valid_cmp, succ_map, term_f, adj_rows, V, beta,
                 keyed=None, key_share=None) -> StepConsts:
    return StepConsts(
        U=U, mu=mu, inv_service=inv_service, sel_cmp=sel_cmp,
        stream_cmp=stream_cmp, valid_cmp=valid_cmp, succ_map=succ_map,
        term_f=term_f, comp_onehot=comp_onehot,
        inst_comp=prob.inst_comp, inst_cont=prob.inst_container,
        gamma=prob.gamma,
        comp_count=prob.comp_count.astype(mu.dtype),
        spout_f=prob.is_spout.astype(mu.dtype),
        adj_rows=adj_rows, V=V, beta=beta, keyed=keyed, key_share=key_share,
    )


def _dense_streams(lanes, pred_s, actual_s, W1: int, I: int, C: int):
    """The slot step's dense ``(…, Tc, I, C)`` actual, predicted and entering
    (slot t+W+1) arrivals, scattered from the packed stream lanes into zeros:
    every other entry is masked away by the step's ``stream_cmp``. ``pred``
    and ``nxt`` are windows of the one prediction stream of Tc + W + 1 slots."""

    def dense(x):
        return jnp.zeros(x.shape[:-1] + (I, C), x.dtype).at[..., lanes[0], lanes[1]].set(x)

    full = dense(pred_s)
    n = pred_s.shape[-2] - W1
    pred, nxt = full[..., :n, :, :], full[..., W1:, :, :]
    return (pred if actual_s is None else dense(actual_s)), pred, nxt


def _with_accumulators(carry, n_slots: int, age_cap: int, C: int):
    """The scan's full state: the carried queues plus this chunk's response
    accumulators, zeros of (S, C, n_slots + age_cap + W + 1)."""
    Sn, W1 = carry[0].shape[0], carry[0].shape[-1]
    acc = jnp.zeros((Sn, C, n_slots + age_cap + W1), carry[0].dtype)
    return tuple(carry) + (acc, acc)


@partial(jax.jit, static_argnames=("age_cap",))
def _initial_carry(q_rem0: jax.Array, age_cap: int):
    """The queue state at slot 0, made on the device: the spouts' initial
    windows (S, I, Sc, W+1), every other queue empty."""
    Sn, I, Sc, W1 = q_rem0.shape
    Atot = age_cap + W1

    def zeros(*shape):
        return jnp.zeros(shape, q_rem0.dtype)

    return (q_rem0, zeros(Sn, I, Sc), zeros(Sn, I, Atot), zeros(Sn, I, Sc, Atot),
            zeros(Sn, I, Atot))


@partial(jax.jit, static_argnames=("edges", "scheduler", "use_pallas", "age_cap",
                                   "n_components", "shared_inputs", "events_shared",
                                   "slots_per_launch", "metrics_spec"),
         donate_argnames=("states",))
def _scan_cohort_fused(
    prob,
    states,  # 5-tuple queue state (the carry), leading scenario axis (always batched)
    U: jax.Array,  # (K, K)
    mu: jax.Array,  # (I,)
    inv_service: jax.Array,  # (I,)
    sel_cmp: jax.Array,  # (I, S)
    stream_cmp: jax.Array,  # (I, S)
    valid_cmp: jax.Array,  # (I, S)
    succ_map: jax.Array,  # (I, S) int32
    term_f: jax.Array,  # (I,)
    adj_rows: jax.Array,  # (I, C)
    lanes: jax.Array,  # (2, L) int32 stream lanes (``_Compact.lanes``)
    pred_s: jax.Array,  # (S?, Tc+W+1, L) packed predictions (unbatched if shared)
    actual_s: jax.Array | None,  # (S?, Tc, L) packed actuals; None: pred_s[:Tc]
    Vs: jax.Array,  # (S,)
    betas: jax.Array,  # (S,)
    events_s=None,  # (S?, Tc, I) (mu_t, gamma_t, alive_t) triple, or None
    keyed=None,  # (C,) fields-grouped components (DESIGN.md §15), or None
    key_share=None,  # (I,) each keyed instance's share, or None
    edges: tuple = (),
    scheduler: str = "potus",
    use_pallas: bool = False,
    age_cap: int = 64,
    n_components: int = 1,
    shared_inputs: bool = False,
    events_shared: bool = False,
    slots_per_launch: int = 1,
    metrics_spec=None,  # static MetricsSpec | None (DESIGN.md §14)
):
    """Scan one chunk of slots for every scenario in the batch.

    The queue state is an explicit input/output so a chunked run can thread
    it through repeated calls at fixed device memory — the input buffers are
    donated to the next chunk; the output adds this chunk's response
    accumulators, which start as zeros made here. The monolithic run is the
    single-chunk case of the same function. The chunk's arrivals come as
    packed stream lanes and are expanded into the step's dense inputs once,
    before the scan (DESIGN.md §11.2).

    Scheduler routing (DESIGN.md §12): every scheduler in
    :data:`~repro.core.compact.COMPACT_SCHEDULERS` runs the one-dispatch
    :func:`~repro.core.compact.compact_slot_step` — no (I, I) tensor in the
    slot loop, and price computation batches across the vmapped sweep axis.
    Under ``use_pallas`` the POTUS step additionally fuses into the
    ``kernels/potus_slot.py`` slot kernel (``slots_per_launch`` slots per
    launch — the megakernel); the kernel falls back to the compact XLA step
    when a disruption trace is present (per-slot caps re-fold the problem).
    ``potus-loop`` keeps the dense reference path (and, under ``use_pallas``,
    the ``cohort_drain`` kernel).
    """
    actual_s, pred_s, nxt_s = _dense_streams(lanes, pred_s, actual_s, states[0].shape[-1],
                                             mu.shape[0], n_components)
    states = _with_accumulators(states, nxt_s.shape[-3], age_cap, n_components)
    comp_onehot = jax.nn.one_hot(prob.inst_comp, n_components, dtype=mu.dtype)
    compact = scheduler in COMPACT_SCHEDULERS
    # metrics never ride the kernel path: stream reductions (sorts) cannot
    # lower into the Pallas slot kernel, so metrics-on falls back to the
    # compact XLA step (metrics=None keeps the kernel — zero-cost-when-off)
    kernel_path = (compact and use_pallas and scheduler == "potus"
                   and events_s is None and metrics_spec is None)
    if not compact:
        sched = _get_scheduler(scheduler, use_pallas)
        u_pair = U[prob.inst_container[:, None], prob.inst_container[None, :]]

    def one(state, actual, pred, nxt, V, beta, ev):
        T = actual.shape[0]
        if compact:
            consts = _step_consts(prob, comp_onehot, U, mu, inv_service, sel_cmp,
                                  stream_cmp, valid_cmp, succ_map, term_f,
                                  adj_rows, V, beta, keyed, key_share)
        if kernel_path and ev is None:
            return _kernel_launches(consts, state, actual, pred, nxt,
                                    scheduler, age_cap, slots_per_launch)
        if compact:
            def step(st, x):
                return compact_slot_step(consts, st, x, scheduler=scheduler,
                                         age_cap=age_cap,
                                         metrics_spec=metrics_spec)
        else:
            step = partial(
                _fused_step, prob, sched, edges, U, u_pair, mu, inv_service,
                sel_cmp, stream_cmp, valid_cmp, succ_map, term_f, comp_onehot,
                age_cap, use_pallas, V, beta, metrics_spec=metrics_spec,
            )
        xs = (actual, pred, nxt, jnp.arange(T))
        if ev is not None:
            xs = xs + (ev,)
        final, ys = jax.lax.scan(step, state, xs)
        return final, (ys[0], ys[1], ys[2].sum(), ys[3].sum()) + tuple(ys[4:])

    ev_ax = None if (events_s is None or events_shared) else 0
    in_axes = (0,) + ((None, None, None) if shared_inputs else (0, 0, 0)) + (0, 0, ev_ax)
    return jax.vmap(one, in_axes=in_axes)(
        states, actual_s, pred_s, nxt_s, Vs, betas, events_s
    )


@partial(jax.jit, static_argnames=("mesh", "scheduler", "use_pallas", "age_cap",
                                   "n_components", "shared_inputs", "events_shared",
                                   "slots_per_launch", "metrics_spec"),
         donate_argnames=("states",))
def _scan_cohort_sharded(
    mesh,
    prob: _CompactProb,
    states,  # 5-tuple queue state (the carry), leading scenario axis (always batched)
    U: jax.Array,  # (K, K)
    mu: jax.Array,  # (I,)
    inv_service: jax.Array,  # (I,)
    sel_cmp: jax.Array,  # (I, S)
    stream_cmp: jax.Array,  # (I, S)
    valid_cmp: jax.Array,  # (I, S)
    succ_map: jax.Array,  # (I, S) int32
    term_f: jax.Array,  # (I,)
    adj_rows: jax.Array,  # (I, C)
    lanes: jax.Array,  # (2, L) int32 stream lanes (``_Compact.lanes``)
    pred_s: jax.Array,  # (S?, Tc+W+1, L) packed predictions (unbatched if shared)
    actual_s: jax.Array | None,  # (S?, Tc, L) packed actuals; None: pred_s[:Tc]
    Vs: jax.Array,  # (S,)
    betas: jax.Array,  # (S,)
    events_s=None,  # (S?, Tc, I) (mu_t, gamma_t, alive_t) triple, or None
    scheduler: str = "potus",
    use_pallas: bool = False,
    age_cap: int = 64,
    n_components: int = 1,
    shared_inputs: bool = False,
    events_shared: bool = False,
    slots_per_launch: int = 1,
    metrics_spec=None,  # static MetricsSpec | None (DESIGN.md §14)
):
    """:func:`_scan_cohort_fused` over an instance mesh (DESIGN.md §13).

    One ``shard_map`` wraps the whole chunk scan: every (I, …)-shaped array
    — queue state, arrival streams, event-trace rows, per-instance consts —
    is row-sharded along :data:`~repro.core.sharded.COHORT_AXIS` for the
    *entire* scan, while ``U``, ``comp_count``, and the response
    accumulators stay replicated. The scenario ``vmap`` runs *inside* the
    shard_map (its axis is replicated), so a sweep partition's scans fold
    their collectives together. Per slot, the only cross-device traffic is
    the compact decision fold plus the (I, Atot) landing ``psum``
    (:func:`~repro.core.sharded.cohort_slot_payload_floats`).

    Requires ``scheduler in COMPACT_SCHEDULERS`` (the dense ``potus-loop``
    reference path materializes (I, I) and is rejected upstream with
    ``UnsupportedEngineOption``). Under ``use_pallas`` the slot kernel runs
    per-shard **only on a 1-shard mesh** — Pallas bodies cannot contain
    collectives — and silently falls back to the compact XLA step on
    multi-shard meshes (the documented megakernel fallback, DESIGN.md §13).
    On a 1-shard mesh every collective is the identity, so this path is
    bitwise-equal to the dense scan there.
    """
    if scheduler not in COMPACT_SCHEDULERS:
        raise ValueError(
            f"sharded cohort scan requires a compact scheduler "
            f"{COMPACT_SCHEDULERS}, got {scheduler!r}"
        )
    n_shards = mesh.shape[COHORT_AXIS]
    # expanded before the shard_map, which row-shards the dense streams
    actual_s, pred_s, nxt_s = _dense_streams(lanes, pred_s, actual_s, states[0].shape[-1],
                                             mu.shape[0], n_components)
    states = _with_accumulators(states, nxt_s.shape[-3], age_cap, n_components)
    kernel_path = (use_pallas and scheduler == "potus" and events_s is None
                   and n_shards == 1 and metrics_spec is None)

    def local(prob_l, states_l, U, mu, inv_service, sel_cmp, stream_cmp,
              valid_cmp, succ_map, term_f, adj_rows, actual_l, pred_l, nxt_l,
              Vs, betas, *ev_l):
        ev = ev_l[0] if ev_l else None
        comp_onehot = jax.nn.one_hot(prob_l.inst_comp, n_components, dtype=mu.dtype)

        def one(state, actual, pred, nxt, V, beta, ev_one):
            T = actual.shape[0]
            consts = _step_consts(prob_l, comp_onehot, U, mu, inv_service,
                                  sel_cmp, stream_cmp, valid_cmp, succ_map,
                                  term_f, adj_rows, V, beta)
            if kernel_path and ev_one is None:
                return _kernel_launches(consts, state, actual, pred, nxt,
                                        scheduler, age_cap, slots_per_launch)

            def step(st, x):
                return compact_slot_step(consts, st, x, scheduler=scheduler,
                                         age_cap=age_cap, axis=COHORT_AXIS,
                                         n_shards=n_shards,
                                         metrics_spec=metrics_spec)

            xs = (actual, pred, nxt, jnp.arange(T))
            if ev_one is not None:
                xs = xs + (ev_one,)
            final, ys = jax.lax.scan(step, state, xs)
            return final, (ys[0], ys[1], ys[2].sum(), ys[3].sum()) + tuple(ys[4:])

        ev_ax = None if (ev is None or events_shared) else 0
        in_axes = ((0,) + ((None, None, None) if shared_inputs else (0, 0, 0))
                   + (0, 0, ev_ax))
        return jax.vmap(one, in_axes=in_axes)(
            states_l, actual_l, pred_l, nxt_l, Vs, betas, ev
        )

    A = COHORT_AXIS
    prob_specs = _CompactProb(
        inst_comp=P(A), inst_container=P(A), gamma=P(A),
        comp_count=P(None), is_spout=P(A),
    )
    arr_spec = P(None, A, None) if shared_inputs else P(None, None, A, None)
    ev_specs = () if events_s is None else (
        ((P(None, A),) * 3 if events_shared else (P(None, None, A),) * 3),
    )
    ev_args = () if events_s is None else (events_s,)
    # replicated metrics out (values are psummed inside the step, so every
    # shard holds the global series; check_rep=False skips the proof)
    n_streams = 0 if metrics_spec is None else len(scan_stream_names(metrics_spec))
    met_specs = (P(None, None), P(None, None), P(None), P(None)) + (
        (P(None, None, None),) * n_streams)  # (S, T, w) stream slabs, replicated
    return shard_map_compat(
        local,
        mesh=mesh,
        in_specs=(
            prob_specs, cohort_state_specs(), P(None, None), P(A), P(A),
            P(A, None), P(A, None), P(A, None), P(A, None), P(A), P(A, None),
            arr_spec, arr_spec, arr_spec, P(None), P(None),
        ) + ev_specs,
        out_specs=(cohort_state_specs(), met_specs),
    )(prob, states, U, mu, inv_service, sel_cmp, stream_cmp, valid_cmp,
      succ_map, term_f, adj_rows, actual_s, pred_s, nxt_s, Vs, betas, *ev_args)


# ---------------------------------------------------------------------------
# host-side preparation and aggregation
# ---------------------------------------------------------------------------

def _terminal_mask(topo: Topology) -> np.ndarray:
    """(I,) share of a bolt's served mass that completes there: 1.0 on
    terminal bolts, the share a filter acks without emitting
    (``Topology.comp_done_share``), 0 elsewhere."""
    return topo.comp_done_share[topo.inst_comp].astype(np.float32)


def _reachability(topo: Topology) -> np.ndarray:
    """(C, C) bool — transitive closure of the component DAG (incl. self)."""
    C = topo.n_components
    reach = topo.adj | np.eye(C, dtype=bool)
    for _ in range(C):  # C squarings overshoot any DAG diameter
        nxt = reach | (reach @ reach)
        if (nxt == reach).all():
            break
        reach = nxt
    return reach


def _prep_streams(actual, predicted, T: int, W: int, cpt: _Compact):
    """Pack one scenario's arrivals into the stream lanes the scan reads
    (``cpt.lanes``; the slot step masks every other entry away).

    Returns ``(pred, act, q_rem0)``: the prediction stream of slots
    0..T+W, (T+W+1, L); the actual stream of slots 0..T-1, (T, L), or None
    under perfect prediction, where it is ``pred[:T]``; and the spouts'
    initial lookahead windows, (I, S, W+1)."""
    def pack(x, n):
        lanes = np.asarray(x)[:n, cpt.lanes[0], cpt.lanes[1]]
        return pad_arrivals(lanes.astype(np.float32, copy=False), n)

    pred = pack(actual if predicted is None else predicted, T + W + 1)
    act = None if predicted is None else pack(actual, T)
    q_rem0 = np.zeros(cpt.valid.shape + (W + 1,), np.float32)
    q_rem0[cpt.lanes[0], cpt.lane_slot] = pred[: W + 1].T
    return pred, act, q_rem0


def _actual_stream(prepped, T: int) -> np.ndarray:
    """The (T, L) actual stream of a :func:`_prep_streams` result."""
    pred, act, _ = prepped
    return pred[:T] if act is None else act


def _entry_weights(act: np.ndarray, cpt: _Compact, C: int) -> np.ndarray:
    """(C, T) actual arrivals per (entry component, slot): the (T, L) stream
    lanes summed onto their successor components."""
    onehot = np.zeros((act.shape[1], C), np.float32)
    onehot[np.arange(act.shape[1]), cpt.lanes[1]] = 1.0
    return (act @ onehot).T


def _aggregate(
    resp_mass: np.ndarray,  # (C, S_acc)
    resp_time: np.ndarray,  # (C, S_acc)
    weights: np.ndarray,  # (C, T) actual arrivals per (entry component, slot)
    reach: np.ndarray,  # (C, C) bool component reachability
    backlog: np.ndarray,  # (T,)
    cost: np.ndarray,  # (T,)
    saturated_frac: float,  # capped / total terminal completions (whole run)
    completed_mass: float,  # total terminal-served mass (conservation ledger)
    T: int,
    W: int,
    warmup: int,
    drain_margin: int | None,
) -> CohortResult:
    """Weighted response aggregation, mirroring ``core.cohort`` (§2): per key
    (entry component, source slot), the max over *reachable* terminal
    components of the mass-weighted mean response, weighted by actual
    arrivals. The per-terminal means merge entry components that share a
    terminal (DESIGN.md §8) — the reachability restriction keeps each app's
    (and each entry's) max over its own terminals only."""
    horizon = T - (drain_margin if drain_margin is not None else max(2 * W + 20, 40))
    lo, hi = max(warmup, 0), min(horizon, T)
    avg_backlog = float(backlog[warmup:].mean()) if T > warmup else float(backlog.mean())
    avg_cost = float(cost[warmup:].mean()) if T > warmup else float(cost.mean())
    if hi <= lo:
        nan = float("nan")
        return CohortResult(
            avg_response=nan, p95_response=nan, avg_backlog=avg_backlog,
            avg_cost=avg_cost, backlog=backlog, comm_cost=cost,
            n_cohorts=0, completed_frac=0.0, saturated_frac=saturated_frac,
            completed_mass=completed_mass,
        )
    entry_ids = np.nonzero(weights[:, lo:hi].sum(axis=1) > 0)[0]  # (E,)
    live = resp_mass[:, lo:hi] > 1e-9  # (C, H)
    mean_ds = np.where(live, resp_time[:, lo:hi] / np.maximum(resp_mass[:, lo:hi], 1e-30),
                       -np.inf)
    resp_es = np.full((len(entry_ids), hi - lo), -np.inf)
    for k, e in enumerate(entry_ids):
        resp_es[k] = mean_ds[reach[e]].max(axis=0, initial=-np.inf)
    w_es = weights[entry_ids, lo:hi]
    valid = (w_es > 0) & np.isfinite(resp_es)
    if valid.any():
        resp_arr, wt_arr = resp_es[valid], w_es[valid]
        avg = float(np.average(resp_arr, weights=wt_arr))
        order = np.argsort(resp_arr)
        cum = np.cumsum(wt_arr[order]) / wt_arr.sum()
        p95 = float(resp_arr[order][np.searchsorted(cum, 0.95)])
    else:
        avg, p95 = float("nan"), float("nan")
    measured = int((weights[:, lo:hi] > 0).sum())
    return CohortResult(
        avg_response=avg,
        p95_response=p95,
        avg_backlog=avg_backlog,
        avg_cost=avg_cost,
        backlog=backlog,
        comm_cost=cost,
        n_cohorts=measured,
        completed_frac=(int(valid.sum()) / max(measured, 1)),
        saturated_frac=saturated_frac,
        completed_mass=completed_mass,
    )


def _device_inputs(topo: Topology, net: NetworkCosts, cpt: _Compact, service=None):
    """The scan's slot-invariant inputs, resident on the device per content;
    a topology with a fields grouping adds its keyed flags and key shares."""
    svc = np.broadcast_to(np.asarray(1.0 if service is None else service, np.float32),
                          (topo.n_instances,))
    if (svc <= 0).any():
        raise ValueError("service times must be positive")
    keyed = {} if not topo.keyed else dict(keyed=topo.comp_keyed.astype(np.float32),
                                           key_share=topo.inst_key_share)
    return _resident(dict(
        U=net.U,
        mu=np.asarray(topo.inst_mu, np.float32),
        inv_service=1.0 / svc,
        sel_cmp=cpt.sel_cmp,
        stream_cmp=cpt.stream_cmp,
        valid_cmp=cpt.valid,
        succ_map=cpt.succ_map,
        term_f=_terminal_mask(topo),
        adj_rows=cpt.adj_rows,
        lanes=cpt.lanes,
        **keyed,
    ))


def _run_chunked_cohort(
    prob,
    dev: dict,
    cpt: _Compact,
    scheduler: str,
    use_pallas: bool,
    age_cap: int,
    n_components: int,
    shared: bool,
    pred: np.ndarray,  # (T+W+1, L) if shared else (S, T+W+1, L) — host-resident
    act: np.ndarray | None,  # (T, L) / (S, T, L); None: perfect prediction
    q0: np.ndarray,  # (I, Sc, W+1) if shared else (S, I, Sc, W+1)
    Vs: list,
    betas: list,
    ev_host,  # numpy (mu_t, gamma_t, alive_t) triple, stacked or shared, or None
    ev_shared: bool,
    T: int,
    W: int,
    chunk: int | None,
    slots_per_launch: int = 1,
    mesh=None,  # instance mesh -> _scan_cohort_sharded (DESIGN.md §13)
    metrics_spec=None,  # static MetricsSpec | None (DESIGN.md §14)
):
    """Stream the fused scan ``chunk`` slots at a time (DESIGN.md §11.2).

    Arrival streams and event traces stay host-resident; each call to
    :func:`_scan_cohort_fused` sees one chunk of slots plus the carried
    queue state (donated buffers), so device memory is bounded by the chunk
    size, not T. A chunk uploads only its rows of the packed stream lanes:
    slots t0..t1+W of the prediction stream and, where one was given,
    t0..t1-1 of the actual stream. Per-chunk response-accumulator slabs —
    indexed by chunk-local source slot — are added into full-horizon host arrays at
    offset ``t0 - age_cap``; columns before source slot 0 are provably zero
    (no mass can predate the run) and are sliced off. Per-slot backlog/cost
    concatenate bitwise across chunk boundaries (the scan body compiles
    identically for any chunk length); only the response sums re-associate,
    which is exact on dyadic-arithmetic systems.

    Returns numpy ``(resp_mass, resp_time, backlog, cost, capped, served,
    streams)``, each with a leading scenario axis; resp_* are
    (S, C, T + W + 1) and ``streams`` is a list of (S, T, w) metric-stream
    slabs (empty when ``metrics_spec`` is None) — per-slot rows concatenate
    bitwise across chunk boundaries exactly like backlog/cost.
    """
    Sn = len(Vs)
    q0_b = np.broadcast_to(q0, (Sn,) + q0.shape) if shared else q0
    W1 = q0_b.shape[-1]
    f32 = np.float32
    with obs_span("potus/cohort-fused/upload"):
        carry = _initial_carry(_to_device(q0_b, f32), age_cap=age_cap)
        if mesh is not None:
            # place the carry on the mesh up front; chunk inputs get resharded
            # by the jitted scan per its shard_map in_specs
            carry = tuple(
                jax.device_put(cr, named(mesh, sp))
                for cr, sp in zip(carry, cohort_state_specs()[:5])
            )
    resp_mass = np.zeros((Sn, n_components, T + W1), f32)
    resp_time = np.zeros((Sn, n_components, T + W1), f32)
    backlogs: list[np.ndarray] = []
    costs: list[np.ndarray] = []
    capped_tot = np.zeros(Sn, np.float64)
    served_tot = np.zeros(Sn, np.float64)
    n_streams = 0 if metrics_spec is None else len(scan_stream_names(metrics_spec))
    stream_chunks: list[list[np.ndarray]] = [[] for _ in range(n_streams)]

    tc = T if chunk is None else int(chunk)
    for t0 in range(0, T, tc) or [0]:
        t1 = min(t0 + tc, T)
        lead = () if shared else (slice(None),)
        with obs_span("potus/cohort-fused/upload"):
            ev_c = None
            if ev_host is not None:
                esl = (slice(t0, t1),) if ev_shared else (slice(None), slice(t0, t1))
                ev_c = tuple(_to_device(e[esl]) for e in ev_host)
            kwargs = dict(
                pred_s=_to_device_stream(pred[lead + (slice(t0, t1 + W1),)]),
                actual_s=(None if act is None
                          else _to_device_stream(act[lead + (slice(t0, t1),)])),
                Vs=_to_device(Vs, f32),
                betas=_to_device(betas, f32),
                events_s=ev_c,
                events_shared=ev_shared,
                scheduler=scheduler,
                use_pallas=use_pallas,
                age_cap=age_cap,
                n_components=n_components,
                shared_inputs=shared,
                slots_per_launch=slots_per_launch,
                metrics_spec=metrics_spec,
                **dev,
            )
        if mesh is None and tracing_enabled():
            _remember_traced_scan(prob, carry, dict(kwargs, edges=cpt.edges))
        with obs_span("potus/cohort-fused/chunk", t0=t0, t1=t1,
                      sharded=mesh is not None):
            if mesh is None:
                states, ys = _scan_cohort_fused(
                    prob, carry, edges=cpt.edges, **kwargs)
            else:
                states, ys = _scan_cohort_sharded(mesh, prob, carry, **kwargs)
        carry = states[:5]
        with obs_span("potus/cohort-fused/fetch"):
            h, cost, capped, served = (_fetch(y) for y in ys[:4])
            slabs = [_fetch(slab) for slab in ys[4:]]
            rm, rt = _fetch(states[5]), _fetch(states[6])
        for k, slab in enumerate(slabs):
            stream_chunks[k].append(slab)
        g0 = t0 - age_cap  # global source slot of the slab's first column
        lo = max(0, -g0)
        resp_mass[:, :, g0 + lo : t1 + W1] += rm[:, :, lo:]
        resp_time[:, :, g0 + lo : t1 + W1] += rt[:, :, lo:]
        backlogs.append(h)
        costs.append(cost)
        capped_tot += capped
        served_tot += served
    return (
        resp_mass,
        resp_time,
        np.concatenate(backlogs, axis=1),
        np.concatenate(costs, axis=1),
        capped_tot,
        served_tot,
        [np.concatenate(chunks, axis=1) for chunks in stream_chunks],
    )


#: (problem, state, keyword arguments) of the last scan dispatched while
#: tracing was on, their arrays as ``jax.ShapeDtypeStruct`` (the state is
#: donated, the inputs need not stay alive); see :func:`scan_hlo_text`
_traced_scan = None


def _remember_traced_scan(prob, states, kwargs) -> None:
    global _traced_scan

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype) if isinstance(x, jax.Array) else x

    _traced_scan = (prob, tuple(map(spec, states)),
                    {k: spec(v) for k, v in kwargs.items()})


def scan_hlo_text() -> str | None:
    """Compiled HLO text of the scan last dispatched while tracing was on
    (``None`` if none was): the instruction names a device profile shows,
    with ``op_name`` metadata naming the slot step's stages (DESIGN.md
    §14.2).

    The persistent compile cache keys a program without its metadata, so the
    executable that ran may carry the names of whichever program first
    compiled the same computation. This compiles the scan once more with the
    metadata in the key, after clearing JAX's in-memory caches: call it
    after the work it describes."""
    if _traced_scan is None:
        return None
    prob, states, kwargs = _traced_scan
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.clear_caches()
    jax.config.update(flag, True)
    try:
        return _scan_cohort_fused.lower(prob, states, **kwargs).compile().as_text()
    finally:
        jax.config.update(flag, was)


def _run_cohort_fused_impl(
    topo: Topology,
    net: NetworkCosts,
    inst_container: np.ndarray,
    actual,  # (T, I, C) actual arrivals, or ArrivalSpec
    predicted: np.ndarray | None,  # (T, I, C) predicted arrivals (None => perfect)
    T: int,
    cfg: SimConfig,
    warmup: int = 50,
    drain_margin: int | None = None,
    age_cap: int = 64,
    events=None,  # EventTrace | None — disruption trace (core.events, DESIGN.md §9)
    service=None,  # (I,) | scalar — per-tuple service time in mu units (DESIGN.md §10)
    chunk: int | None = None,  # streaming scan: device slots per chunk (DESIGN.md §11.2)
    slots_per_launch: int = 1,  # megakernel: slots fused per kernel launch (DESIGN.md §12)
    sharded: bool = False,  # shard the scan over an instance mesh (DESIGN.md §13)
    mesh=None,  # explicit mesh override (tests/benchmarks); implies sharded
    metrics=None,  # MetricsSpec | None — in-scan metric streams (DESIGN.md §14)
) -> CohortResult:
    """Fused cohort engine implementation behind ``simulate(EngineSpec)``.

    ``service`` adds the token-length service-time axis: ``topo.inst_mu``
    (and event-trace ``mu_t`` rows) stay in raw capacity units — tokens/slot
    for a serving fleet — and each bolt instance completes
    ``mu[i] / service[i]`` tuples per slot. This is how a request trace runs
    unchanged on both a :class:`repro.serving.fleet.ReplicaFleet` and this
    in-graph oracle (``engine_opts={"service": ...}`` through
    ``run_sweep``).

    ``age_cap`` bounds the tracked response of any tuple: mass older than
    ``age_cap`` slots accumulates in the oldest bucket and reports response
    ``age_cap`` (DESIGN.md §8) — choose it above the largest response the
    system exhibits (the default comfortably covers the paper's stable
    operating points; high-V sweeps need more). A too-shallow cap shows up
    as ``CohortResult.saturated_frac > 0`` (response biased low, one-sided).
    Disruption runs need the cap to also cover the outage length (stranded
    mass keeps aging while its instance is down).
    """
    if age_cap < 2:
        raise ValueError(f"age_cap must be >= 2, got {age_cap}")
    if chunk is not None and chunk <= 0:
        raise ValueError(f"chunk must be a positive slot count, got {chunk}")
    if slots_per_launch < 1:
        raise ValueError(f"slots_per_launch must be >= 1, got {slots_per_launch}")
    if mesh is None and sharded:
        mesh = instance_mesh(topo.n_instances)
    if mesh is not None:
        _check_sharded_scheduler(cfg.scheduler)
        if topo.n_instances % mesh.shape[COHORT_AXIS] != 0:
            raise ValueError(
                f"mesh size {mesh.shape[COHORT_AXIS]} does not divide "
                f"I={topo.n_instances}"
            )
    W = cfg.window
    with obs_span("potus/cohort-fused/prep"):
        actual = materialize_arrivals(actual, topo, T + W + 1)
        # compact schedulers never need the (I, I) edge mask — build the O(I)
        # problem so fleet-scale (and sharded) runs stay linear in I
        prob = (_compact_prob(topo, inst_container)
                if cfg.scheduler in COMPACT_SCHEDULERS
                else make_problem(topo, net, inst_container))
        cpt = _compact(topo)
        packed = _prep_streams(actual, predicted, T, W, cpt)
        ev_host = host_trace(events, T)
    with obs_span("potus/cohort-fused/upload"):
        dev = _device_inputs(topo, net, cpt, service)
    resp_mass, resp_time, backlog, cost, capped, served, streams = _run_chunked_cohort(
        prob, dev, cpt, cfg.scheduler, cfg.use_pallas, age_cap, topo.n_components,
        True, *packed, [cfg.V], [cfg.beta],
        ev_host, True, T, W, chunk, slots_per_launch, mesh=mesh,
        metrics_spec=metrics,
    )
    with obs_span("potus/cohort-fused/reduce"):
        weights = _entry_weights(_actual_stream(packed, T), cpt, topo.n_components)
        sat = float(capped[0]) / max(float(served[0]), 1e-9)
        _maybe_warn_saturation(sat, age_cap,
                               label=f"scheduler={cfg.scheduler} V={cfg.V} W={W}")
        result = _aggregate(
            resp_mass[0], resp_time[0], weights, _reachability(topo),
            backlog[0], cost[0], sat, float(served[0]),
            T, W, warmup, drain_margin,
        )
        if metrics is not None:
            frame = build_frame(
                metrics, [s[0] for s in streams], n_slots=T,
                payload_floats=_fused_payload_floats(topo, net, age_cap, W, mesh),
            )
            result = dataclasses.replace(result, metrics=frame)
    return result


def _fused_payload_floats(topo, net, age_cap, W, mesh) -> int:
    """Per-slot cross-device payload of this run, for the ``payload`` stream."""
    n_shards = 1 if mesh is None else mesh.shape[COHORT_AXIS]
    return cohort_slot_payload_floats(
        topo.n_instances, topo.n_components, net.U.shape[0],
        age_cap + W + 1, n_shards,
    )


def _check_sharded_scheduler(scheduler: str) -> None:
    """Sharded cohort runs require a compact scheduler: ``potus-loop`` keeps
    the dense (I, I) reference path, which has no shard layout."""
    if scheduler not in COMPACT_SCHEDULERS:
        from .engine import UnsupportedEngineOption  # lazy: engine imports us

        raise UnsupportedEngineOption(
            "cohort-fused", "sharded",
            reason=f"scheduler {scheduler!r} keeps the dense (I, I) reference "
                   f"path; sharded runs support {COMPACT_SCHEDULERS}",
        )


def run_fused_sweep(
    topo: Topology,
    net: NetworkCosts,
    inst_container: np.ndarray,
    arr_map: dict,  # name -> (actual, predicted|None), from sweep normalization
    T: int,
    spec,
    warmup: int = 50,
    drain_margin: int | None = None,
    age_cap: int = 64,
    events_map: dict | None = None,  # name -> EventTrace|None, from sweep normalization
    service=None,  # (I,) | scalar — per-tuple service time in mu units (DESIGN.md §10)
    chunk: int | None = None,  # streaming scan: device slots per chunk (DESIGN.md §11.2)
    slots_per_launch: int = 1,  # megakernel: slots fused per kernel launch (DESIGN.md §12)
    metrics=None,  # MetricsSpec | None — per-scenario metric streams (DESIGN.md §14)
) -> tuple[list[CohortResult], int]:
    """Run a whole :class:`repro.core.sweep.SweepSpec` grid on the fused
    engine: scenarios partition by (scheduler, window, use_pallas, and
    whether they carry a disruption trace) exactly like the JAX engine, and
    each partition runs as one vmapped scan — response-time grids (Figs.
    4/6) and disruption grids compile once per partition instead of looping
    Python scenarios. Returns (results in grid order, n_batches).

    With ``spec.sharded`` every partition's vmapped scan runs over the
    instance mesh (:func:`_scan_cohort_sharded`); a partition whose
    scheduler has no shard layout (``potus-loop``) raises
    ``UnsupportedEngineOption`` rather than silently running dense
    (DESIGN.md §13)."""
    if age_cap < 2:
        raise ValueError(f"age_cap must be >= 2, got {age_cap}")
    if slots_per_launch < 1:
        raise ValueError(f"slots_per_launch must be >= 1, got {slots_per_launch}")
    scenarios = spec.scenarios()
    # raising lookup, like arr_map: a named trace missing from the map is a
    # caller error, not an undisturbed run silently labeled as disturbed
    events_map = {"none": None, **(events_map or {})}
    missing = [e for e in spec.events if e not in events_map]
    if missing:
        raise KeyError(f"spec names event scenarios {missing} not present in events_map")
    from .engine import check_keyed  # lazy: engine imports us

    for scn in scenarios:  # a fields grouping lands on one path only (DESIGN.md §15)
        check_keyed("cohort-fused", topo, scn.scheduler, scn.use_pallas,
                    getattr(spec, "sharded", False))
    mesh = None
    if getattr(spec, "sharded", False):
        for scn in scenarios:  # fail before any partition runs — no silent fallback
            _check_sharded_scheduler(scn.scheduler)
        mesh = instance_mesh(topo.n_instances)
    probs: dict[bool, object] = {}

    def prob_for(scheduler: str):
        compact = scheduler in COMPACT_SCHEDULERS
        if compact not in probs:
            probs[compact] = (_compact_prob(topo, inst_container) if compact
                              else make_problem(topo, net, inst_container))
        return probs[compact]

    with obs_span("potus/cohort-fused/prep"):
        cpt = _compact(topo)
    with obs_span("potus/cohort-fused/upload"):
        dev = _device_inputs(topo, net, cpt, service)
    reach = None

    def trace_of(scn):
        return events_map[getattr(scn, "events", "none")]

    groups: dict[tuple, list] = {}
    for scn in scenarios:
        key = (scn.scheduler, scn.window, scn.use_pallas, trace_of(scn) is not None)
        groups.setdefault(key, []).append(scn)

    results: list[CohortResult | None] = [None] * len(scenarios)
    for (scheduler, W, use_pallas, has_events), group in groups.items():
        shared = len({scn.arrival for scn in group}) == 1
        with obs_span("potus/cohort-fused/prep"):
            prob = prob_for(scheduler)
            if shared:  # one prep + one weights matrix for the whole partition
                prepped = [_prep_streams(*arr_map[group[0].arrival], T, W, cpt)]
                pred_s, act_s, q0_s = prepped[0]
            else:
                prepped = [_prep_streams(*arr_map[scn.arrival], T, W, cpt)
                           for scn in group]
                pred_s = np.stack([p[0] for p in prepped])
                act_s = (None if all(p[1] is None for p in prepped)
                         else np.stack([_actual_stream(p, T) for p in prepped]))
                q0_s = np.stack([p[2] for p in prepped])
            ev_host, ev_shared = None, True
            if has_events:
                ev_host, ev_shared = stacked_host_traces(
                    [getattr(scn, "events", "none") for scn in group],
                    [trace_of(scn) for scn in group], T,
                )
        resp_mass, resp_time, backlog, cost, capped, served, streams = _run_chunked_cohort(
            prob, dev, cpt, scheduler, use_pallas, age_cap,
            topo.n_components, shared, pred_s, act_s, q0_s,
            [scn.V for scn in group], [scn.beta for scn in group],
            ev_host, ev_shared, T, W, chunk, slots_per_launch, mesh=mesh,
            metrics_spec=metrics,
        )
        with obs_span("potus/cohort-fused/reduce"):
            if reach is None:
                reach = _reachability(topo)
            weights_s = [_entry_weights(_actual_stream(p, T), cpt, topo.n_components)
                         for p in prepped]
            for s, scn in enumerate(group):
                sat = float(capped[s]) / max(float(served[s]), 1e-9)
                _maybe_warn_saturation(
                    sat, age_cap,
                    label=(f"scheduler={scheduler} V={scn.V} W={W} "
                           f"arrival={scn.arrival} "
                           f"events={getattr(scn, 'events', 'none')}"),
                )
                result = _aggregate(
                    resp_mass[s], resp_time[s], weights_s[0 if shared else s], reach,
                    backlog[s], cost[s], sat, float(served[s]), T, W, warmup,
                    drain_margin,
                )
                if metrics is not None:
                    frame = build_frame(
                        metrics, [slab[s] for slab in streams], n_slots=T,
                        payload_floats=_fused_payload_floats(topo, net, age_cap, W, mesh),
                    )
                    result = dataclasses.replace(result, metrics=frame)
                results[scn.index] = result
    return results, len(groups)
