"""Queueing model and per-slot dynamics (paper §3.4, eqs. (2)-(10)).

Fluid (float) tuple counts; state is a pytree consumed by ``lax.scan``.

Per-slot order of events (paper Fig. 3):
  1. observe Q(t), U(t); make decision X(t)
  2. spouts drain output windows ``Q_rem`` in ascending lookahead order
     (actual tuples first, then predicted — eq. (4) guarantees the w=0 slice
     is fully dispatched), window shifts (eqs. (5)-(7))
  3. tuples shipped at t-1 land in bolt input queues, bolts serve up to
     ``mu`` (eq. (8)) and emit ``nu = served * selectivity`` into their
     output queues (eq. (9))
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .potus import SchedProblem
from .topology import Topology

__all__ = [
    "SimState", "init_state", "init_state_batch", "effective_qout",
    "slot_update", "slot_update_rows",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimState:
    q_in: jax.Array  # (I,)
    q_rem: jax.Array  # (I, C, W+1) — spouts only, zeros for bolts
    q_out_bolt: jax.Array  # (I, C) — bolts only
    transit: jax.Array  # (I,) — tuples landing in q_in next slot (X(t-1))


def init_state(topo: Topology, window: int, arrivals_prefix: np.ndarray) -> SimState:
    """``arrivals_prefix``: (window+1, I, C) — λ(0..W) pre-loaded into Q_rem."""
    I, C = topo.n_instances, topo.n_components
    q_rem = jnp.asarray(np.moveaxis(arrivals_prefix, 0, -1), dtype=jnp.float32)
    is_spout = topo.comp_is_spout[topo.inst_comp]
    q_rem = q_rem * jnp.asarray(is_spout, jnp.float32)[:, None, None]
    return SimState(
        q_in=jnp.zeros((I,), jnp.float32),
        q_rem=q_rem,
        q_out_bolt=jnp.zeros((I, C), jnp.float32),
        transit=jnp.zeros((I,), jnp.float32),
    )


def init_state_batch(topo: Topology, window: int, arrivals_prefixes: np.ndarray) -> SimState:
    """Stacked initial states for a scenario sweep (DESIGN.md §6).

    ``arrivals_prefixes``: (S, window+1, I, C) — one λ(0..W) prefix per
    scenario. Returns a :class:`SimState` whose leaves carry a leading
    scenario axis of size S, ready for ``jax.vmap`` over the sweep.
    """
    states = [init_state(topo, window, p) for p in arrivals_prefixes]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def effective_qout(prob: SchedProblem, state: SimState) -> jax.Array:
    """Q_out(t): spouts derive it from the lookahead window (eq. 3)."""
    spout_qout = state.q_rem.sum(axis=-1)
    return jnp.where(prob.is_spout[:, None], spout_qout, state.q_out_bolt)


def slot_update_rows(
    state: SimState,  # leaves over a block of R rows
    X: jax.Array,  # (R, I) decision rows for this slot
    landing: jax.Array,  # (R,) tuples landing at these rows' instances (full column sums)
    new_arrivals: jax.Array,  # (R, C) — λ(t + W + 1), entering the window
    mu: jax.Array,  # (R,) processing capacity this slot
    selectivity_rows: jax.Array,  # (R, C) — selectivity[comp(i), :]
    is_spout: jax.Array,  # (R,)
    comp_onehot: jax.Array,  # (I, C) — one-hot component of each *column*
    hold_mask: jax.Array | None = None,  # (R, C) 1 where pos-0 leftovers must be held
) -> tuple[SimState, dict[str, jax.Array]]:
    """Per-slot dynamics for a block of rows (paper eqs. (2)-(10)).

    Row-local except for ``landing``: the tuples arriving at each row's
    instance are column sums of the *global* decision matrix, which the dense
    path computes directly and the sharded path reduces with a ``psum``
    across row shards (DESIGN.md §7).

    Without disruptions eq. (4) guarantees the w=0 window slice is fully
    dispatched, so the shifted-out position is empty. Under an event trace a
    dead spout (or a successor component with no alive instance) cannot ship,
    and dropping the remainder would destroy tuples — ``hold_mask`` marks
    those streams and their pos-0 leftover is carried into the next slot's
    current position instead (admission-backlog semantics, matching the
    cohort engines; DESIGN.md §9). An all-alive slot has ``hold_mask == 0``
    everywhere, which is numerically a no-op.
    """
    # full f32: the TPU's default precision would round the mass through bf16
    shipped = jnp.dot(X, comp_onehot, precision=jax.lax.Precision.HIGHEST)  # (R, C) leaving i toward c

    # --- spouts: drain Q_rem in ascending w (actual first), shift window ----
    cum_before = jnp.cumsum(state.q_rem, axis=-1) - state.q_rem
    drained = jnp.clip(shipped[:, :, None] - cum_before, 0.0, state.q_rem)
    q_rem = state.q_rem - drained
    leftover = q_rem[..., 0]  # (R, C) pos-0 remainder about to shift out
    q_rem = jnp.concatenate([q_rem[..., 1:], new_arrivals[..., None]], axis=-1)
    if hold_mask is not None:
        q_rem = q_rem.at[..., 0].add(leftover * hold_mask)
    q_rem = q_rem * is_spout[:, None, None]

    # --- bolts: arrivals from X(t-1), service, emission --------------------
    is_bolt = ~is_spout
    total_in = state.q_in + state.transit
    served = jnp.minimum(total_in, mu) * is_bolt
    q_in = (total_in - served) * is_bolt  # eq. (8)
    nu = served[:, None] * selectivity_rows  # (R, C) eq. (9) input
    q_out_bolt = (
        jnp.maximum(state.q_out_bolt - shipped, 0.0) + nu
    ) * is_bolt[:, None]

    transit = landing * is_bolt  # everything ships into bolt inputs

    new_state = SimState(q_in=q_in, q_rem=q_rem, q_out_bolt=q_out_bolt, transit=transit)
    info = dict(shipped=shipped, served=served, drained=drained)
    return new_state, info


def slot_update(
    prob: SchedProblem,
    state: SimState,
    X: jax.Array,  # (I, I) decision for this slot
    new_arrivals: jax.Array,  # (I, C) — λ(t + W + 1), entering the window
    mu: jax.Array,  # (I,) processing capacity this slot
    selectivity_rows: jax.Array,  # (I, C) — selectivity[comp(i), :]
    hold_mask: jax.Array | None = None,  # (I, C) — see slot_update_rows
) -> tuple[SimState, dict[str, jax.Array]]:
    comp_onehot = jax.nn.one_hot(prob.inst_comp, prob.n_components, dtype=X.dtype)
    return slot_update_rows(
        state, X, X.sum(axis=0), new_arrivals, mu, selectivity_rows,
        prob.is_spout, comp_onehot, hold_mask=hold_mask,
    )
