"""POTUS — Predictive Online Tuple Scheduling (paper Algorithm 1), in JAX.

Per time slot, each instance ``i`` solves its slice of the drift-plus-penalty
subproblem (15): ship tuples to successor instances ``i'`` in ascending order
of the price

    l[i,i'](t) = V * U[k(i), k(i')] + Q_in[i'](t) - beta * Q_out[i, c(i')](t)

considering only candidates with ``l < 0``, each shipment bounded by the
remaining transmission capacity ``gamma_i`` and the (virtual) output-queue
budget of the target component. Actual same-slot arrivals at spouts
(``Q_rem(t, 0)``) are *always* dispatched (eq. 4 / Alg. 1 line 5-6), evenly
across the successor component's instances if the candidate set is empty.

Two interchangeable implementations of the greedy (DESIGN.md §7):

* ``method="sort"`` (default) — the **sort-based water-fill fast path**. Each
  row's finite negative prices are reduced to one entry per successor
  component (its cheapest candidate), sorted ascending, and the transmission
  budget ``gamma_i`` is water-filled against the cumulative per-component
  ``q_out`` budgets with a prefix sum — no sequential argmin loop.
* ``method="loop"`` — the original ``lax.fori_loop`` of argmin picks, kept as
  the executable reference; the two agree elementwise (tested against each
  other and against the ``core.reference`` integer oracle).

The price matrix has a Pallas TPU kernel (`repro.kernels.potus_price`), and
``use_pallas=True`` routes the whole per-row allocation through the fused
schedule kernel (`repro.kernels.potus_schedule`), in which prices never
round-trip to HBM (DESIGN.md §7).

The scheduler is *fluid* (float tuple counts). On integral inputs the greedy
allocations stay integral except for the even-split mandatory dispatch; the
exact integer oracle lives in ``core.reference`` and the two are compared in
tests.

Disruption traces (``core.events``, DESIGN.md §9) enter through the optional
``caps`` argument: a :class:`SlotCaps` of per-slot liveness and effective
capacities. :func:`apply_caps` folds it into the static problem — dead
instances' price columns go +inf (masked out of ``edge_mask``), their rows
get zero transmission budget, and the mandatory even-split divides over the
*alive* instances of the successor component — so every execution path
(sort, loop, Pallas, sharded) prices disruptions out with no special cases.
With an identity trace the fold is numerically a no-op (bit-identical X).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .network import NetworkCosts
from .topology import Topology

__all__ = ["SchedProblem", "SlotCaps", "apply_caps", "potus_prices", "potus_schedule", "make_problem"]

_INF = jnp.inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SchedProblem:
    """Static description of the scheduling problem consumed per slot."""

    edge_mask: jax.Array  # (I, I) bool — comp(i) -> comp(i') is a DAG edge
    inst_comp: jax.Array  # (I,) int32
    inst_container: jax.Array  # (I,) int32
    gamma: jax.Array  # (I,) f32
    comp_count: jax.Array  # (C,) f32 — parallelism per component
    is_spout: jax.Array  # (I,) bool
    max_succ: int = dataclasses.field(metadata=dict(static=True))
    n_components: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SlotCaps:
    """One slot of a disruption trace (``core.events``, DESIGN.md §9).

    ``alive`` is always the *global* (I,) liveness vector — it masks decision
    columns and sizes the alive-instance counts — while ``row_alive``, ``mu``
    and ``gamma`` are shaped like the caller's decision rows (the full I rows
    on the dense path, this shard's rows under ``core.sharded``). ``mu`` and
    ``gamma`` are the effective capacities of ``EventTrace`` (already zero
    where dead).
    """

    alive: jax.Array  # (I,) f32 0/1 — global liveness (decision columns)
    row_alive: jax.Array  # (R,) f32 0/1 — liveness of the caller's rows
    mu: jax.Array  # (R,) f32 — effective processing capacity
    gamma: jax.Array  # (R,) f32 — effective transmission capacity


def caps_for_slot(mu_row: jax.Array, gamma_row: jax.Array, alive_row: jax.Array) -> SlotCaps:
    """Dense-path caps: rows and columns are the same I instances."""
    return SlotCaps(alive=alive_row, row_alive=alive_row, mu=mu_row, gamma=gamma_row)


def apply_caps(
    prob: SchedProblem, must_send: jax.Array, caps: SlotCaps | None
) -> tuple[SchedProblem, jax.Array]:
    """Fold a disruption slot into the scheduling problem (DESIGN.md §9).

    Dead targets leave ``edge_mask`` (their prices become +inf on every
    path, Pallas included), dead sources get ``gamma = 0`` and their
    mandatory dispatch is cancelled (the arrivals are held, not dropped —
    the engines carry them as admission backlog), and ``comp_count``
    becomes the per-component *alive* instance count so the even-split of
    eq. (4) lands on live instances only. With an all-alive slot every fold
    is numerically exact (``& True``, ``* 1.0``, integer recount), so an
    identity trace is bit-transparent.
    """
    if caps is None:
        return prob, must_send
    alive_cols = caps.alive > 0.0
    comp_count = jnp.zeros_like(prob.comp_count).at[prob.inst_comp].add(caps.alive)
    prob = dataclasses.replace(
        prob,
        edge_mask=prob.edge_mask & alive_cols[None, :],
        gamma=caps.gamma,
        comp_count=comp_count,
    )
    return prob, must_send * caps.row_alive[:, None]


def hold_mask_for(prob: SchedProblem, caps: SlotCaps) -> jax.Array:
    """(R, C) — 1 on streams whose mandatory arrivals cannot ship this slot
    (dead source row, or successor component with no alive instance); the
    engines hold those tuples instead of dropping them (DESIGN.md §9)."""
    comp_alive = jnp.zeros_like(prob.comp_count).at[prob.inst_comp].add(caps.alive)
    dead_comp = (comp_alive <= 0.0).astype(caps.alive.dtype)  # (C,)
    return jnp.clip((1.0 - caps.row_alive)[:, None] + dead_comp[None, :], 0.0, 1.0)


def make_problem(topo: Topology, net: NetworkCosts, inst_container: np.ndarray) -> SchedProblem:
    return SchedProblem(
        edge_mask=jnp.asarray(topo.edge_mask_instances()),
        inst_comp=jnp.asarray(topo.inst_comp),
        inst_container=jnp.asarray(inst_container, dtype=jnp.int32),
        gamma=jnp.asarray(topo.inst_gamma),
        comp_count=jnp.asarray(topo.comp_parallelism, dtype=jnp.float32),
        is_spout=jnp.asarray(topo.comp_is_spout[topo.inst_comp]),
        max_succ=int(topo.max_out_instances()),
        n_components=int(topo.n_components),
    )


def _price_rows(
    u_pair: jax.Array,  # (R, I) = U[k(i), k(j)] for a block of source rows
    q_in_cols: jax.Array,  # (I,)
    q_out_rows: jax.Array,  # (R, C)
    inst_comp_cols: jax.Array,  # (I,)
    edge_mask_rows: jax.Array,  # (R, I)
    V,
    beta,
) -> jax.Array:
    """Price block ``l`` (eq. 16) for a block of source rows; +inf off-edge.
    Shared by the dense path and the sharded row-block path."""
    l = V * u_pair + q_in_cols[None, :] - beta * q_out_rows[:, inst_comp_cols]
    return jnp.where(edge_mask_rows, l, _INF)


def potus_prices(
    prob: SchedProblem,
    U: jax.Array,  # (K, K)
    q_in: jax.Array,  # (I,)
    q_out: jax.Array,  # (I, C)
    V: float,
    beta: float,
    use_pallas: bool = False,
) -> jax.Array:
    """(I, I) price matrix ``l`` (eq. 16); +inf on non-edges."""
    if use_pallas:
        from repro.kernels import ops as kops

        return kops.potus_price(
            U, q_in, q_out, prob.inst_container, prob.inst_comp, prob.edge_mask, V, beta
        )
    u_pair = U[prob.inst_container[:, None], prob.inst_container[None, :]]  # (I, I)
    return _price_rows(u_pair, q_in, q_out, prob.inst_comp, prob.edge_mask, V, beta)


def _greedy_row(
    l_row: jax.Array,  # (I,)
    qout_row: jax.Array,  # (C,) output-queue budget of source i
    gamma_i: jax.Array,  # ()
    inst_comp: jax.Array,  # (I,)
    max_succ: int,
):
    """Algorithm 1 lines 9-14 for one source instance (reference loop path)."""
    I = l_row.shape[0]

    def body(_, carry):
        x_row, budget, used, active = carry
        cand = active & (l_row < 0.0) & jnp.isfinite(l_row)
        l_eff = jnp.where(cand, l_row, _INF)
        j = jnp.argmin(l_eff)
        feasible = l_eff[j] < _INF
        cj = inst_comp[j]
        alloc = jnp.where(feasible, jnp.maximum(jnp.minimum(gamma_i - used, budget[cj]), 0.0), 0.0)
        x_row = x_row.at[j].add(alloc)
        budget = budget.at[cj].add(-alloc)
        used = used + alloc
        active = active & (jnp.arange(I) != j)
        return x_row, budget, used, active

    init = (jnp.zeros((I,), l_row.dtype), qout_row, jnp.array(0.0, l_row.dtype), jnp.ones((I,), bool))
    x_row, budget, used, _ = jax.lax.fori_loop(0, max_succ, body, init)
    return x_row, budget, used


def _fill_components(
    m: jax.Array,  # (C,) cheapest candidate price per component (+inf = none)
    j_c: jax.Array,  # (C,) int32 — that candidate's instance index (I = none)
    budget: jax.Array,  # (C,) per-component q_out budget (0 where no candidate)
    gamma_i: jax.Array,  # ()
):
    """Water-fill ``gamma_i`` against per-component budgets in ascending
    ``(price, index)`` order. Returns ``(fill_sorted, j_sorted, perm)`` where
    ``perm`` maps sorted positions back to component slots, so callers can
    place the fill onto instance columns (dense X, a scatter) or back into
    component order (the compact one-dispatch path, ``core.compact``, sorts
    it by ``perm``). Shared by both so the two allocations are identical by
    construction."""
    C = m.shape[0]
    _, j_sorted, b_sorted, perm = jax.lax.sort(
        (m, j_c, budget, jnp.arange(C, dtype=jnp.int32)), num_keys=2
    )
    prefix = jnp.cumsum(b_sorted)
    before = jnp.concatenate([jnp.zeros((1,), prefix.dtype), prefix[:-1]])
    fill = jnp.minimum(prefix, gamma_i) - jnp.minimum(before, gamma_i)
    return fill, j_sorted, perm


def _waterfill_row(
    l_row: jax.Array,  # (I,)
    qout_row: jax.Array,  # (C,) output-queue budget of source i
    gamma_i: jax.Array,  # ()
    inst_comp: jax.Array,  # (I,)
    n_components: int,
):
    """Sort-based water-fill: the same allocation as ``_greedy_row`` without
    the sequential argmin loop (DESIGN.md §7).

    Each greedy pick either drains its target component's whole ``q_out``
    budget (so later candidates of that component receive 0) or exhausts
    ``gamma_i`` (so *everything* later receives 0). Only the **cheapest
    candidate of each component** can therefore receive tuples, and the row
    collapses to one (price, target, budget) entry per successor component.
    Sorting those entries by ascending price — index tie-break matching
    ``argmin`` — and water-filling ``gamma_i`` against the cumulative budget
    prefix sum reproduces the loop's allocation exactly.
    """
    I = l_row.shape[0]
    C = n_components
    key = jnp.where(l_row < 0.0, l_row, _INF)  # finite negatives; non-edges are +inf
    # cheapest candidate per component, ties to the lowest instance index
    m = jnp.full((C,), _INF, key.dtype).at[inst_comp].min(key)
    idx = jnp.where(key == m[inst_comp], jnp.arange(I, dtype=jnp.int32), I)
    j_c = jnp.full((C,), I, jnp.int32).at[inst_comp].min(idx)
    budget = jnp.where(m < 0.0, jnp.maximum(qout_row, 0.0), 0.0)
    # ascending (price, index); componentless entries carry zero budget
    fill, j_sorted, _ = _fill_components(m, j_c, budget, gamma_i)
    return jnp.zeros((I,), l_row.dtype).at[j_sorted].add(fill, mode="drop")


def _allocate_rows(
    l: jax.Array,  # (R, I) prices, +inf on non-candidates' edges
    q_out: jax.Array,  # (R, C)
    gamma: jax.Array,  # (R,)
    inst_comp: jax.Array,  # (I,) component of each *column*
    n_components: int,
    max_succ: int,
    method: str,
) -> jax.Array:
    """Greedy allocation for a block of rows; shared by the dense and the
    sharded (row-block) execution paths."""
    if method == "sort":
        return jax.vmap(_waterfill_row, in_axes=(0, 0, 0, None, None))(
            l, q_out, gamma, inst_comp, n_components
        )
    if method == "loop":
        x, _, _ = jax.vmap(_greedy_row, in_axes=(0, 0, 0, None, None))(
            l, q_out, gamma, inst_comp, max_succ
        )
        return x
    raise ValueError(f"unknown method {method!r} (expected 'sort' or 'loop')")


def _mandatory_dispatch(
    x: jax.Array,  # (R, I) greedy allocation for a block of rows
    must_send: jax.Array,  # (R, C) — spout Q_rem(t, 0); zeros elsewhere
    edge_mask: jax.Array,  # (R, I)
    inst_comp: jax.Array,  # (I,) component of each column
    comp_count: jax.Array,  # (C,)
    n_components: int,
) -> jax.Array:
    """Mandatory dispatch of actual arrivals (eq. 4, Alg. 1 line 5-6):
    any shortfall vs the greedy shipment is split evenly across the successor
    component's instances."""
    comp_onehot = jax.nn.one_hot(inst_comp, n_components, dtype=x.dtype)  # (I, C)
    # full f32: the TPU's default precision would round the mass through bf16
    shipped = jnp.dot(x, comp_onehot, precision=jax.lax.Precision.HIGHEST)  # (R, C)
    shortfall = jnp.maximum(must_send - shipped, 0.0)  # (R, C)
    extra = jnp.where(
        edge_mask,
        shortfall[:, inst_comp] / comp_count[inst_comp][None, :],
        0.0,
    )
    return x + extra


@partial(jax.jit, static_argnames=("use_pallas", "method"))
def potus_schedule(
    prob: SchedProblem,
    U: jax.Array,  # (K, K) per-slot container costs
    q_in: jax.Array,  # (I,)
    q_out: jax.Array,  # (I, C)
    must_send: jax.Array,  # (I, C) — spout Q_rem(t, 0); zeros elsewhere
    V: float,
    beta: float,
    use_pallas: bool = False,
    method: str = "sort",
    caps: SlotCaps | None = None,
) -> jax.Array:
    """One slot of Algorithm 1 for every instance. Returns X (I, I).

    ``method="sort"`` is the water-fill fast path, ``"loop"`` the reference
    argmin loop; with ``use_pallas=True`` the sort path runs the fused
    Pallas schedule kernel (prices and allocation in one kernel), while the
    loop path keeps using the standalone Pallas price kernel. ``caps``
    applies one slot of a disruption trace (DESIGN.md §9) on every path.
    """
    prob, must_send = apply_caps(prob, must_send, caps)
    if use_pallas and method == "sort":
        from repro.kernels import ops as kops

        x = kops.potus_schedule_alloc(
            U, q_in, q_out, prob.inst_container, prob.inst_comp, prob.edge_mask,
            prob.gamma, V, beta,
        )
    else:
        l = potus_prices(prob, U, q_in, q_out, V, beta, use_pallas=use_pallas)
        x = _allocate_rows(
            l, q_out, prob.gamma, prob.inst_comp, prob.n_components, prob.max_succ, method
        )
    return _mandatory_dispatch(
        x, must_send, prob.edge_mask, prob.inst_comp, prob.comp_count, prob.n_components
    )
