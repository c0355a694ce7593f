"""One-dispatch slot math for the fused cohort engine (DESIGN.md §12).

The fused cohort engine's hot loop used to materialize the dense decision
matrix ``X`` (I, I) every slot — price tile, greedy water-fill, per-edge
column sums, and an (I, I) landing ratio — even though each scheduler's
decision has at most one *point* target plus one *even spread* per
(source instance, successor component) pair. This module re-expresses each
per-slot scheduler decision in that **successor-component-compact** form:

    CompactDecision(shipped, point, j_point, even_per, cost)

* ``shipped[i, c]``  — total mass source ``i`` ships toward component ``c``;
* ``point[i, c]``    — the part aimed at one instance ``j_point[i, c]``
  (POTUS's cheapest candidate, JSQ's winner; ``I`` = no target);
* ``even_per[i, c]`` — the part landing on *each* alive instance of ``c``
  (the mandatory even split of eq. 4, shuffle's uniform dispatch);
* ``cost``           — the slot's communication cost ``sum(X * u_pair)``.

For POTUS the collapse is exact: within a component the candidate ordering
over columns ``j`` is row-independent, because the row only enters the price
``l[i,j] = (V·U[k_i,k_j] + q_in[j]) − β·q_out[i,c]`` through a per-(i, c)
constant shift. The cheapest candidate per (container, component) —
``M[k,c] = min_j (V·U[k,k_j] + q_in[j])`` with its argmin ``J[k,c]`` — is an
O(K·I) reduction shared by all rows, and subtracting the constant afterwards
commutes bitwise with the min (the selected element is identical; the
``l < 0`` candidate filter applies after the shift, since if the cheapest
candidate is non-negative every candidate in that component is). The one
caveat: two *different* raw prices can round to the same shifted price, in
which case the dense path's tie-break could pick the other column — impossible
on the dyadic-arithmetic test tier, a 1-ulp event otherwise (same class as
the documented POTUS split caveat, DESIGN.md §12).

Every function here is pure ``jnp`` on plain arrays so the identical code
runs (a) under the engine's ``lax.scan`` (XLA path) and (b) inside the Pallas
fused-slot/megakernel bodies (``kernels/potus_slot.py``). The XLA path's
decision moves no value through a per-element index op, which the TPU runs
one element at a time: it reduces per component with windowed (K, ·)
scatter-mins and -adds (one index per instance, a column of K each), lifts
(K, C) tables to rows with row gathers, water-fills with two sorts (one to
order the components, one to put them back), and spreads (I, S) streams over
(I, C) with the one-hot sum the kernel path shares. ``kernel_safe=True``
swaps the ops Mosaic cannot lower — gathers, scatters, ``cumsum``, slices at
a traced offset and ``lax.sort`` — for masked per-component passes, one-hot
contractions and placements, and the O(C²) precedence-rank water-fill (the
same substitution ``kernels/potus_schedule.py`` makes), listed in DESIGN.md
§12.2; both variants agree bitwise on the dyadic tier and to 1 ulp
elsewhere.

**Instance sharding** (DESIGN.md §13): the same row-independence that powers
the collapse makes the decision shard over an instance mesh. With
``axis="i"`` (and ``n_shards`` devices) every ``(I, …)`` input is this
shard's row block, and the per-(container, component) candidate min folds
across shards with one ``lax.pmin`` of the (K, C) ``(M, J)`` pair (argmin
indices converted to *global* instance ids first, so the
lowest-global-index tie-break survives the fold bitwise — ``min`` selects
an element, it never rounds). One more (K, C) integer ``pmin`` recovers the
target's *container* (only the owning shard knows it); per-component
reductions (``_u_col_sums``, JSQ's winner) fold the same way, and
``compact_slot_step`` adds the only O(I)-sized collective — a ``psum`` of
the landing age-buckets, the physical tuple transfer. Nothing (I, I)-shaped
ever crosses devices. ``axis=None`` is exactly the dense path; on a 1-shard
mesh every collective is the identity, so sharded-vs-dense parity is
bitwise there and on the dyadic tier for any shard count (cross-shard
``psum`` re-associates float sums, which dyadic masses cannot observe).
``axis`` and ``kernel_safe`` are mutually exclusive — collectives cannot
lower into a Pallas body, which is why the megakernel runs per-shard only
on single-shard meshes (DESIGN.md §13).

**Fields groupings** (DESIGN.md §15): where the problem carries ``keyed``
(the components whose in-edges are keyed) every scheduler decides on the
shuffle edges only. A keyed edge ships by Heron's default rule
(:func:`_ship_amounts_compact`), POTUS water-fills what of gamma the keyed
edges left, and the keyed mass lands on the component's instances by their
static ``key_share``. Only the XLA path of one shard implements it; a
problem without ``keyed`` traces exactly the code it traced before.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.obs.metrics import compute_scan_streams, scan_stream_names

from .engine import UnsupportedEngineOption
from .potus import _fill_components

__all__ = [
    "COMPACT_SCHEDULERS", "CompactProblem", "CompactDecision", "StepConsts",
    "compact_decide", "compact_slot_step",
]

_EPS = 1e-12  # same negligible-mass threshold as the engines' FIFOs
_INF = jnp.inf
_BIG = 1e30  # finite stand-in for +inf ahead of one-hot contractions (0*inf = NaN)
#: contractions that move tuple mass or select values through a one-hot run
#: at full f32 precision: the TPU's default precision rounds f32 operands
#: through bf16, which would break mass conservation there
_HI = jax.lax.Precision.HIGHEST

#: schedulers with a compact one-dispatch decision (``potus-loop`` keeps the
#: dense reference path in ``core.cohort_fused``)
COMPACT_SCHEDULERS = ("potus", "shuffle", "jsq")


class CompactProblem(NamedTuple):
    """Per-slot scheduling inputs, with any disruption caps already folded
    (alive counts, effective gamma) — the compact analog of
    ``potus.apply_caps`` without the (I, I) edge mask."""

    inst_comp: jax.Array  # (I,) int32 — component of each instance
    inst_cont: jax.Array  # (I,) int32 — container of each instance
    gamma: jax.Array  # (I,) effective transmission budget
    comp_count: jax.Array  # (C,) alive instances per component
    adj_rows: jax.Array  # (I, C) 1.0 where comp(i) -> c is a DAG edge
    alive: jax.Array  # (I,) 1.0 on alive instances
    # fields groupings (DESIGN.md §15); None where no component is keyed
    keyed: jax.Array | None = None  # (C,) 1.0 where c's in-edges are keyed
    key_share: jax.Array | None = None  # (I,) share of its keyed component's inflow


class CompactDecision(NamedTuple):
    shipped: jax.Array  # (I, C)
    point: jax.Array  # (I, C) mass aimed at j_point
    j_point: jax.Array  # (I, C) int32 target instance; I = none
    even_per: jax.Array  # (I, C) mass landing on each alive instance of c
    cost: jax.Array  # () communication cost of the slot


def _onehot_cols(idx: jax.Array, n: int, dtype) -> jax.Array:
    """(..., n) one-hot of ``idx`` via 2-D iota (Pallas-TPU lowerable)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, idx.shape + (n,), idx.ndim)
    return (idx[..., None] == iota).astype(dtype)


def _row(x: jax.Array, kernel_safe: bool) -> jax.Array:
    """(n,) -> (1, n). Inside a kernel a vector reduced along the lanes lives
    down the sublanes, and Mosaic cannot broadcast it across them; an
    explicit 2-D transpose of the column moves it instead."""
    return x[:, None].T if kernel_safe else x[None, :]


def _vsum(x: jax.Array, kernel_safe: bool) -> jax.Array:
    """Total of a (n,) vector; Mosaic reduces a column, not a bare vector."""
    return x[:, None].sum() if kernel_safe else x.sum()


def _colmin_per_comp(t1: jax.Array, inst_comp: jax.Array, C: int, kernel_safe: bool):
    """Per-component column reduction of ``t1`` (K, I): value min ``M`` (K, C)
    and lowest-index argmin ``J`` (K, C); ``I`` where a component is empty."""
    K, I = t1.shape
    if kernel_safe:
        # one masked (K, I) pass per component: C is small and static, and
        # selects move values without arithmetic, so M, J match the scatter
        # path bitwise (_BIG, not inf: M flows into one-hot contractions)
        iota_i = jax.lax.broadcasted_iota(jnp.int32, (K, I), 1)
        big = jnp.asarray(_BIG, t1.dtype)
        comp_row = _row(inst_comp, True)  # (1, I)
        m_cols, j_cols = [], []
        for c in range(C):
            in_c = comp_row == c
            m_c = jnp.min(jnp.where(in_c, t1, big), axis=1, keepdims=True)  # (K, 1)
            j_c = jnp.min(jnp.where(in_c & (t1 == m_c), iota_i, I), axis=1, keepdims=True)
            m_cols.append(m_c)
            j_cols.append(j_c)
        return jnp.concatenate(m_cols, axis=1), jnp.concatenate(j_cols, axis=1)
    M = jnp.full((K, C), _INF, t1.dtype).at[:, inst_comp].min(t1)
    hit = jnp.where(t1 == M[:, inst_comp], jnp.arange(I, dtype=jnp.int32)[None, :], I)
    J = jnp.full((K, C), I, jnp.int32).at[:, inst_comp].min(hit)
    return M, J


def _rows_of(A: jax.Array, inst_cont: jax.Array, kernel_safe: bool) -> jax.Array:
    """(I, ...) = A[k_i, ...] — row gather, or its one-hot contraction (the
    matmul sums one exact product plus zeros, so the two agree bitwise).
    ``A`` must be finite: ``0 * inf`` would poison the contraction."""
    if kernel_safe:
        oh = _onehot_cols(inst_cont, A.shape[0], A.dtype)  # (I, K)
        return jax.lax.dot_general(oh, A, (((1,), (0,)), ((), ())), precision=_HI,
                                   preferred_element_type=A.dtype)
    return A[inst_cont]


def _u_cols(U: jax.Array, inst_cont: jax.Array, kernel_safe: bool) -> jax.Array:
    """(K, I) = U[:, k_j]."""
    if kernel_safe:
        oh = _onehot_cols(inst_cont, U.shape[0], U.dtype)  # (I, K)
        return jax.lax.dot_general(U, oh, (((1,), (1,)), ((), ())), precision=_HI,
                                   preferred_element_type=U.dtype)
    return U[:, inst_cont]


def _u_col_sums(U: jax.Array, cp: CompactProblem, kernel_safe: bool,
                axis: str | None = None) -> jax.Array:
    """(K, C) per-component sums of alive columns of ``U[:, k_j]``.

    Under sharding (``axis``) the columns of ``U[:, k_j]`` are this shard's
    instances; the (K, C) partials fold with one ``psum`` (re-associates the
    dense column order — invisible on the dyadic tier, identity on 1 shard).
    """
    C = cp.comp_count.shape[0]
    u_cols = _u_cols(U, cp.inst_cont, kernel_safe) * _row(cp.alive, kernel_safe)  # (K, I)
    if kernel_safe:
        oh = _onehot_cols(cp.inst_comp, C, U.dtype)  # (I, C)
        out = jax.lax.dot_general(u_cols, oh, (((1,), (0,)), ((), ())), precision=_HI,
                                  preferred_element_type=U.dtype)
    else:
        out = jnp.zeros((U.shape[0], C), U.dtype).at[:, cp.inst_comp].add(u_cols)
    if axis is not None:
        out = jax.lax.psum(out, axis)
    return out


def _fold_min_with_payload(m_loc: jax.Array, p_loc: jax.Array, sentinel,
                           axis: str) -> tuple[jax.Array, jax.Array]:
    """Fold a (value, payload) argmin pair across ``axis``: global min of
    ``m_loc`` plus the smallest payload among shards attaining it. With
    payloads pre-offset to global instance ids this reproduces the dense
    lowest-global-index tie-break bitwise (``pmin`` selects elements)."""
    m = jax.lax.pmin(m_loc, axis)
    p = jax.lax.pmin(jnp.where(m_loc == m, p_loc, sentinel), axis)
    return m, p


def _owner_gather(idx_g: jax.Array, values: jax.Array, off: jax.Array,
                  n_local: int, sentinel_fill: int, axis: str) -> jax.Array:
    """values[idx_g] for global instance ids ``idx_g`` when only the owning
    shard holds ``values`` (its (n_local,) row block): the owner contributes
    the element, everyone else an int sentinel folded away by ``pmin``.
    Out-of-range ids (the I_glob "no target" sentinel) yield
    ``sentinel_fill`` — callers only read those entries where the associated
    mass is zero."""
    own = (idx_g >= off) & (idx_g < off + n_local)
    local = jnp.clip(idx_g - off, 0, n_local - 1)
    contrib = jnp.where(own, values[local], jnp.int32(2**30))
    return jnp.minimum(jax.lax.pmin(contrib, axis), sentinel_fill)


def _fill_rows_sort(m, j_c, budget, gamma):
    """(I, C) sort-based water-fill, in component order (XLA path). The
    sorted fill goes back into component order by a second sort keyed on
    ``perm`` — a permutation, so values move and nothing rounds — rather
    than a scatter, which the TPU runs one element at a time."""

    def one(m_r, j_r, b_r, g_r):
        fill, _, perm = _fill_components(m_r, j_r, b_r, g_r)
        return jax.lax.sort((perm, fill), num_keys=1)[1]

    return jax.vmap(one)(m, j_c, budget, gamma)


def _fill_rows_rank(m, j_c, budget, gamma):
    """(I, C) precedence-rank water-fill — the sort-free equivalent used
    inside kernels (same substitution as ``kernels/potus_schedule.py``):
    entry d precedes e iff ``(m_d, j_d) < (m_e, j_e)`` lexicographically, so
    the budget mass ahead of each entry is a masked sum over the C entries
    (one (I, C) pass each, C static) instead of a cumsum over a sorted axis.
    Agrees with the sort path bitwise whenever the prefix sums round
    identically (always on the dyadic tier)."""
    before = jnp.zeros_like(budget)
    for d in range(m.shape[1]):
        m_d, j_d = m[:, d:d + 1], j_c[:, d:d + 1]
        prec_d = (m_d < m) | ((m_d == m) & (j_d < j_c))  # entry d precedes e
        before = before + jnp.where(prec_d, budget[:, d:d + 1], 0.0)
    after = before + budget
    g = gamma[:, None]
    return jnp.minimum(after, g) - jnp.minimum(before, g)


def _point_price(U, cp, J):
    """(I, C) = U[k_i, container of J[k_i, c]], the price of each row's
    point target, read only where its fill is positive (there ``j_c`` is
    ``J``'s target). The (K, C) table comes from the windowed min that found
    ``J`` — exactly one column of each component matches — and is lifted to
    rows by a row gather. Where ``J == I`` it holds ``_BIG``, which the cost
    multiplies by a zero fill."""
    I = cp.inst_comp.shape[0]
    hit = jnp.where(jnp.arange(I, dtype=jnp.int32)[None, :] == J[:, cp.inst_comp],
                    U[:, cp.inst_cont], _BIG)  # (K, I)
    u_tgt = jnp.full(J.shape, _BIG, U.dtype).at[:, cp.inst_comp].min(hit)
    return u_tgt[cp.inst_cont]


def _potus_decide(cp, U, q_in, q_out, must_send, V, beta, kernel_safe,
                  axis=None, n_shards=1):
    I = cp.inst_comp.shape[0]  # this shard's rows when axis is set
    C = cp.comp_count.shape[0]
    I_all = I * n_shards if axis is not None else I
    edge = cp.adj_rows > 0.0
    # shared per-(container, component) cheapest candidate: O(K·I), no (I, I).
    # _BIG stands in for +inf so downstream one-hot contractions stay NaN-free;
    # it only ever reaches entries whose budget is 0.
    big = jnp.asarray(_BIG, U.dtype)
    t1 = jnp.where(_row(cp.alive, kernel_safe) > 0.0,
                   V * _u_cols(U, cp.inst_cont, kernel_safe) + _row(q_in, kernel_safe), big)
    M, J = _colmin_per_comp(t1, cp.inst_comp, C, kernel_safe)
    if axis is not None:
        # fold the shard-local (M, J) into the global cheapest candidate:
        # one small pmin pair, with J lifted to global instance ids first so
        # the dense lowest-index tie-break is preserved bitwise
        off = jax.lax.axis_index(axis) * I
        J = jnp.where(J < I, J + off, I_all)
        M, J = _fold_min_with_payload(M, J, I_all, axis)
    m_raw = _rows_of(M, cp.inst_cont, kernel_safe) - beta * q_out  # row-constant shift
    cand = edge & (m_raw < 0.0)
    m = jnp.where(cand, m_raw, _INF)
    j_row = _rows_of(J.astype(U.dtype), cp.inst_cont, kernel_safe).astype(jnp.int32)
    j_c = jnp.where(edge, j_row, I_all)
    budget = jnp.where(cand, jnp.maximum(q_out, 0.0), 0.0)
    fill_rows = _fill_rows_rank if kernel_safe else _fill_rows_sort
    fill = fill_rows(m, j_c, budget, cp.gamma)
    # mandatory dispatch (eq. 4): even split over the alive instances
    can_even = edge & (cp.comp_count > 0.0)[None, :]
    shortfall = jnp.where(can_even, jnp.maximum(must_send - fill, 0.0), 0.0)
    even_per = shortfall / jnp.maximum(cp.comp_count, 1.0)[None, :]
    # cost: the point part reads U at the target, the even part uses the
    # per-component alive-column sum of U — both O(I·C)
    u_sum = _u_col_sums(U, cp, kernel_safe, axis)  # (K, C)
    if axis is not None:
        # only the target's owning shard knows its container: one more (K, C)
        # integer pmin; the K-1 clamp is only reached where fill == 0
        k_j = _owner_gather(J, cp.inst_cont, off, I, U.shape[0] - 1, axis)  # (K, C)
        u_point = U[cp.inst_cont[:, None], _rows_of(k_j, cp.inst_cont, False)]
    elif kernel_safe:
        # U[k, container of J[k, c]] per (container, component), then the
        # row lift: two masked (K, ·) passes per component, exact selects
        K = U.shape[0]
        cont_row = _row(cp.inst_cont.astype(U.dtype), True)  # (1, I)
        iota_i = jax.lax.broadcasted_iota(jnp.int32, (K, I), 1)
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1).astype(U.dtype)
        u_tgt = []
        for cc in range(C):
            k_t = jnp.sum(jnp.where(J[:, cc:cc + 1] == iota_i, cont_row, 0.0),
                          axis=1, keepdims=True)  # (K, 1); 0 where J == I
            u_tgt.append(jnp.sum(jnp.where(k_t == iota_k, U, 0.0), axis=1, keepdims=True))
        # fill is 0 wherever j_c is not J's target, so the rows agree with
        # the point price of j_c on every entry the cost reads
        u_point = _rows_of(jnp.concatenate(u_tgt, axis=1), cp.inst_cont, True)
    else:
        u_point = _point_price(U, cp, J)
    # under sharding the cost is this shard's partial (rows are local);
    # compact_slot_step psums it with the other slot scalars
    cost = (fill * u_point).sum() + (even_per * _rows_of(u_sum, cp.inst_cont,
                                                         kernel_safe)).sum()
    return CompactDecision(fill + shortfall, fill, j_c, even_per, cost)


def _ship_amounts_compact(cp, q_out, must_send):
    """Same gamma-throttled proportional shipment as ``baselines._ship_amounts``."""
    total = q_out.sum(axis=1, keepdims=True)
    scale = jnp.where(
        total > 0, jnp.minimum(1.0, cp.gamma[:, None] / jnp.maximum(total, 1e-9)), 0.0
    )
    return jnp.maximum(q_out * scale, must_send)


def _keyed_cost(cp, U, key_ship):
    """Communication cost of the keyed shipments: each lands on instance j
    of its component at share ``key_share[j]``, so a source in container k
    pays ``sum_j key_share[j] U[k, k_j]`` per tuple — a (K, C) sum shared
    by all rows, as :func:`_u_col_sums` is for the even spread."""
    C = cp.keyed.shape[0]
    u_key = jnp.zeros((U.shape[0], C), U.dtype).at[:, cp.inst_comp].add(
        U[:, cp.inst_cont] * cp.key_share[None, :])
    return (key_ship * u_key[cp.inst_cont]).sum()


def _shuffle_decide(cp, U, q_in, q_out, must_send, V, beta, kernel_safe,
                    axis=None, n_shards=1):
    I = cp.inst_comp.shape[0]
    C = cp.comp_count.shape[0]
    I_all = I * n_shards if axis is not None else I
    ship = _ship_amounts_compact(cp, q_out, must_send)
    can = (cp.adj_rows > 0.0) & (cp.comp_count > 0.0)[None, :]
    per_target = jnp.where(can, ship / jnp.maximum(cp.comp_count, 1.0)[None, :], 0.0)
    shipped = per_target * cp.comp_count[None, :]
    u_sum = _u_col_sums(U, cp, kernel_safe, axis)  # (K, C)
    cost = (per_target * _rows_of(u_sum, cp.inst_cont, kernel_safe)).sum()
    zeros = jnp.zeros((I, C), ship.dtype)
    return CompactDecision(shipped, zeros, jnp.full((I, C), I_all, jnp.int32),
                           per_target, cost)


def _jsq_decide(cp, U, q_in, q_out, must_send, V, beta, kernel_safe,
                axis=None, n_shards=1):
    I = cp.inst_comp.shape[0]
    C = cp.comp_count.shape[0]
    I_all = I * n_shards if axis is not None else I
    ship = _ship_amounts_compact(cp, q_out, must_send)
    # winner[c] = argmin q_in over the alive instances of c (ties -> lowest)
    cand = _onehot_cols(cp.inst_comp, C, jnp.bool_) & (cp.alive > 0.0)[:, None]  # (I, C)
    masked_q = jnp.where(cand, q_in[:, None], _INF)
    winner = jnp.argmin(masked_q, axis=0).astype(jnp.int32)  # (C,)
    if axis is not None:
        # fold the per-component winner like the POTUS candidate: global-id
        # lift, pmin on (value, id), then an owner pmin for its container
        off = jax.lax.axis_index(axis) * I
        w_min = jnp.min(masked_q, axis=0)  # (C,)
        w_min, winner = _fold_min_with_payload(w_min, winner + off, I_all, axis)
        win_ok = w_min < _INF  # some alive instance of c exists somewhere
        k_win = _owner_gather(winner, cp.inst_cont, off, I, U.shape[0] - 1, axis)
        u_win = U[cp.inst_cont[:, None], k_win[None, :]]  # (I, C)
    elif kernel_safe:
        oh_w = _onehot_cols(winner, I, U.dtype)  # (C, I)
        win_alive = jnp.sum(oh_w * cp.alive[None, :], axis=1)
        iota_c = jax.lax.broadcasted_iota(jnp.int32, (C, I), 0)
        win_comp_ok = jnp.sum(
            oh_w * (cp.inst_comp[None, :] == iota_c).astype(U.dtype), axis=1)
        k_win = jnp.sum(oh_w * cp.inst_cont[None, :].astype(U.dtype),
                        axis=1).astype(jnp.int32)  # (C,)
        u_rows = _rows_of(U, cp.inst_cont, True)  # (I, K) = U[k_i, :]
        u_win = jnp.sum(_onehot_cols(k_win, U.shape[0], U.dtype)[None, :, :]
                        * u_rows[:, None, :], axis=-1)  # (I, C)
        win_ok = (win_comp_ok > 0.0) & (win_alive > 0.0)
    else:
        win_ok = (cp.inst_comp[winner] == jnp.arange(C, dtype=jnp.int32)) & (
            cp.alive[winner] > 0.0
        )
        u_win = U[cp.inst_cont[:, None], cp.inst_cont[winner][None, :]]  # (I, C)
    can = (cp.adj_rows > 0.0) & win_ok[None, :]
    shipped = jnp.where(can, ship, 0.0)
    j_point = jnp.where(can, winner[None, :], I_all)
    cost = (shipped * u_win).sum()
    return CompactDecision(shipped, shipped, j_point, jnp.zeros_like(shipped), cost)


_DECIDERS = {"potus": _potus_decide, "shuffle": _shuffle_decide, "jsq": _jsq_decide}


def compact_decide(
    scheduler: str,
    cp: CompactProblem,
    U: jax.Array,
    q_in: jax.Array,
    q_out: jax.Array,
    must_send: jax.Array,
    V,
    beta,
    kernel_safe: bool = False,
    axis: str | None = None,
    n_shards: int = 1,
) -> CompactDecision:
    """One slot's scheduling decision in compact form; ``scheduler`` must be
    in :data:`COMPACT_SCHEDULERS`.

    With ``axis`` set (a mesh axis name, inside ``shard_map``) every (I, …)
    argument is this shard's row block of the global problem, ``q_in``
    included — the local column min covers exactly the local instances, so
    no all-gather is needed. ``j_point`` then holds *global* instance ids
    with ``I · n_shards`` as the "no target" sentinel, and ``cost`` is the
    shard-local partial (``compact_slot_step`` folds it). Incompatible with
    ``kernel_safe`` — collectives cannot lower into a Pallas body.

    With ``cp.keyed`` set (DESIGN.md §15) the keyed edges ship by Heron's
    rule and carry neither a point nor an even part; both ``kernel_safe``
    and ``axis`` then raise :class:`UnsupportedEngineOption`.
    """
    if axis is not None and kernel_safe:
        raise ValueError("compact_decide: axis (sharded) and kernel_safe are "
                         "mutually exclusive — Pallas bodies cannot contain "
                         "collectives (DESIGN.md §13)")
    decide = _DECIDERS[scheduler]
    if cp.keyed is None:
        return decide(cp, U, q_in, q_out, must_send, V, beta, kernel_safe, axis, n_shards)
    if kernel_safe or axis is not None:
        path = "the Pallas slot kernel" if kernel_safe else "the instance-sharded step"
        raise UnsupportedEngineOption(
            "cohort-fused", "keyed", reason=f"{path} lands no fields grouping (DESIGN.md §15)")
    # fields groupings (DESIGN.md §15): the scheduler sees the shuffle edges
    # only; a keyed edge ships by Heron's rule, and POTUS water-fills what
    # of gamma the keyed edges left (shuffle and JSQ throttle every edge
    # together by that rule already)
    keyed = (cp.adj_rows > 0.0) & (cp.keyed > 0.0)[None, :]
    key_ship = jnp.where(keyed, _ship_amounts_compact(cp, q_out, must_send), 0.0)
    steer = cp._replace(adj_rows=jnp.where(keyed, 0.0, cp.adj_rows))
    if scheduler == "potus":
        steer = steer._replace(gamma=jnp.maximum(cp.gamma - key_ship.sum(axis=1), 0.0))
    dec = decide(steer, U, q_in, q_out, must_send, V, beta, kernel_safe, axis, n_shards)
    return dec._replace(shipped=dec.shipped + key_ship,
                        cost=dec.cost + _keyed_cost(cp, U, key_ship))


# ---------------------------------------------------------------------------
# the full one-dispatch slot step (stages 1-5 of DESIGN.md §8, compact form)
# ---------------------------------------------------------------------------

class StepConsts(NamedTuple):
    """Slot-invariant arrays consumed by :func:`compact_slot_step` — one
    bundle so the engine's scan body and the Pallas kernel body (which
    reconstructs it from refs) share the step verbatim."""

    U: jax.Array  # (K, K)
    mu: jax.Array  # (I,) raw capacity units
    inv_service: jax.Array  # (I,)
    sel_cmp: jax.Array  # (I, S)
    stream_cmp: jax.Array  # (I, S)
    valid_cmp: jax.Array  # (I, S)
    succ_map: jax.Array  # (I, S) int32
    term_f: jax.Array  # (I,) share of served mass completing there
    comp_onehot: jax.Array  # (I, C)
    inst_comp: jax.Array  # (I,) int32
    inst_cont: jax.Array  # (I,) int32
    gamma: jax.Array  # (I,)
    comp_count: jax.Array  # (C,)
    spout_f: jax.Array  # (I,) 1.0 on spout instances
    adj_rows: jax.Array  # (I, C)
    V: jax.Array  # ()
    beta: jax.Array  # ()
    keyed: jax.Array | None = None  # (C,) fields-grouped components (DESIGN.md §15)
    key_share: jax.Array | None = None  # (I,)


def _to_dense(c: StepConsts, x_cmp: jax.Array) -> jax.Array:
    """(I, S) -> (I, C) as a one-hot sum over the static S: a source's
    streams go to distinct components, so each entry receives one value plus
    zeros (exact), and the C sentinel slot has no column to land in."""
    I, S = x_cmp.shape
    C = c.comp_onehot.shape[1]
    out = jnp.zeros((I, C), x_cmp.dtype)
    for s in range(S):  # S is tiny and static
        out = out + _onehot_cols(c.succ_map[:, s], C, x_cmp.dtype) * x_cmp[:, s:s + 1]
    return out


def _to_dense3(c: StepConsts, x_cmp: jax.Array) -> jax.Array:
    """(I, S, A) -> (I, C, A); the kernel landing works per slot instead."""
    I, S, A = x_cmp.shape
    C = c.comp_onehot.shape[1]
    rows = jnp.arange(I)[:, None]
    return jnp.zeros((I, C + 1, A), x_cmp.dtype).at[rows, c.succ_map, :].add(x_cmp)[:, :C]


def _to_cmp(c: StepConsts, x: jax.Array, kernel_safe: bool) -> jax.Array:
    """(I, C) -> (I, S)."""
    I, C = x.shape
    S = c.succ_map.shape[1]
    if kernel_safe:
        cols = []
        for s in range(S):
            oh = _onehot_cols(c.succ_map[:, s], C, x.dtype)
            cols.append(jnp.sum(x * oh, axis=1))
        return jnp.stack(cols, axis=1) * c.valid_cmp
    gather_idx = jnp.minimum(c.succ_map, C - 1)
    return jnp.take_along_axis(x, gather_idx, axis=1) * c.valid_cmp


def _land_kernel(c: StepConsts, j_point, w_pt, w_ev, d_land):
    """Kernel-safe landing, one successor slot at a time: the point part of
    slot ``s`` lands through an (I, I) one-hot of its target, the even part
    folds into (C, Atot) per-component sums. Returns ``(land, ev_cb)`` —
    the scatter landing and the even-spread einsum of the XLA path."""
    I, S, Atot = d_land.shape
    C = w_pt.shape[1]
    dt = d_land.dtype
    dims = (((0,), (0,)), ((), ()))  # contract the source-instance axis
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (I, C), 1)
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (I, I), 1)
    land = jnp.zeros((I, Atot), dt)
    ev_cb = jnp.zeros((C, Atot), dt)
    for s in range(S):  # S is tiny and static
        in_s = c.succ_map[:, s:s + 1] == iota_c  # (I, C); no column for C
        d_s = d_land[:, s, :]  # (I, Atot)
        w_pt_s = jnp.sum(jnp.where(in_s, w_pt, 0.0), axis=1, keepdims=True)
        j_s = jnp.min(jnp.where(in_s, j_point, I), axis=1, keepdims=True)  # (I, 1)
        oh_t = (j_s == iota_i).astype(dt)  # (I, I); target I -> zero row
        land = land + jax.lax.dot_general(oh_t, w_pt_s * d_s, dims, precision=_HI,
                                          preferred_element_type=dt)
        w_ev_c = jnp.where(in_s, w_ev, 0.0)  # (I, C)
        ev_cb = ev_cb + jax.lax.dot_general(w_ev_c, d_s, dims, precision=_HI,
                                            preferred_element_type=dt)
    return land, ev_cb


def _drain_ages(buckets: jax.Array, amount: jax.Array, kernel_safe: bool) -> jax.Array:
    # local copy of cohort_fused.drain_ages (import would be circular); Pallas
    # TPU has no cumsum, so kernels take the prefix sum as a contraction with
    # an upper-triangular ones matrix (exact sums of dyadic masses)
    if kernel_safe:
        A = buckets.shape[-1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (A, A), 0)
        tri = (rows <= jax.lax.broadcasted_iota(jnp.int32, (A, A), 1)).astype(buckets.dtype)
        flat = buckets.reshape(-1, A)
        cum = jax.lax.dot_general(flat, tri, (((1,), (0,)), ((), ())),
                                  precision=_HI,
                                  preferred_element_type=buckets.dtype).reshape(buckets.shape)
    else:
        cum = jnp.cumsum(buckets, axis=-1)
    return jnp.clip(amount[..., None] - (cum - buckets), 0.0, buckets)


def compact_slot_step(
    c: StepConsts,
    state,
    xs,
    *,
    scheduler: str,
    age_cap: int,
    kernel_safe: bool = False,
    axis: str | None = None,
    n_shards: int = 1,
    metrics_spec=None,
):
    """One slot of the cohort dynamics (stages 1-5 of DESIGN.md §8) with the
    compact one-dispatch decision — no (I, I) tensor anywhere. Mirrors
    ``cohort_fused._fused_step`` stage for stage; the dense path remains in
    that module for the ``potus-loop`` reference scheduler.

    ``xs`` is ``(act_t, pred_t, new_pred, t)`` plus optionally one slot of a
    disruption trace ``(mu_row, gamma_row, alive_row)``; the caps fold
    (DESIGN.md §9) happens here in compact form — alive counts, effective
    gamma, cancelled mandatory dispatch on dead rows — matching
    ``potus.apply_caps`` numerically.

    With ``axis`` set (inside ``shard_map`` over an instance mesh,
    DESIGN.md §13) every (I, …) array in ``c``, ``state``, and ``xs`` —
    including the disruption trace rows — is this shard's row block;
    ``c.comp_count`` and ``U`` stay replicated, and the response
    accumulators are replicated (every shard folds the same global (C, Atot)
    ``cmass``). Cross-device traffic per slot: the decision fold inside
    :func:`compact_decide` (a few (K, C) pmins), one (C,) psum of alive
    counts under events, the (I_glob, Atot) landing psum — the physical
    tuple transfer — plus (C, Atot) even-spread/served psums and the scalar
    metrics. Nothing (I, I)-shaped crosses devices.
    """
    act_t, pred_t, new_pred, t, *ev = xs
    q_rem, admit, q_in_tag, q_out_tag, transit, resp_mass, resp_time = state
    I, S, W1 = q_rem.shape
    C = c.comp_onehot.shape[1]
    Atot = q_in_tag.shape[-1]
    spout_f = c.spout_f
    bolt_f = 1.0 - spout_f
    dt = q_rem.dtype

    # -- 1. reconcile window pos-0 with actual arrivals of slot t ------------
    with jax.named_scope("reconcile"):
        pred_m = _to_cmp(c, pred_t, kernel_safe) * c.stream_cmp
        act_m = _to_cmp(c, act_t, kernel_safe) * c.stream_cmp
        tp = jnp.minimum(pred_m, act_m)
        tn = act_m - tp
        r = jnp.where(pred_m > 0, q_rem[:, :, 0] / jnp.where(pred_m > 0, pred_m, 1.0), 0.0)
        q_rem = jnp.concatenate([(r * tp + tn)[:, :, None], q_rem[:, :, 1:]], axis=-1)

    # -- 2. observe queue state, schedule (compact decision) -----------------
    with jax.named_scope("decide"):
        q_in_arr = q_in_tag.sum(-1)
        q_out_cmp = jnp.where(spout_f[:, None] > 0, q_rem.sum(-1), q_out_tag.sum(-1))
        q_out_arr = _to_dense(c, q_out_cmp)
        must_send = _to_dense(c, (q_rem[:, :, 0] + admit) * spout_f[:, None])
        if ev:
            mu_row, gamma_row, alive_row = ev[0]
            mu_eff = mu_row * c.inv_service
            if kernel_safe:
                comp_count = jax.lax.dot_general(
                    alive_row[None, :], c.comp_onehot, (((1,), (0,)), ((), ())),
                    precision=_HI, preferred_element_type=dt)[0]
            else:
                comp_count = jnp.zeros((C,), dt).at[c.inst_comp].add(alive_row)
            if axis is not None:
                comp_count = jax.lax.psum(comp_count, axis)
            cp = CompactProblem(c.inst_comp, c.inst_cont, gamma_row, comp_count,
                                c.adj_rows, alive_row, c.keyed, c.key_share)
            must_send = must_send * alive_row[:, None]
        else:
            mu_eff = c.mu * c.inv_service
            cp = CompactProblem(c.inst_comp, c.inst_cont, c.gamma, c.comp_count,
                                c.adj_rows, jnp.ones((I,), dt), c.keyed, c.key_share)
        dec = compact_decide(scheduler, cp, c.U, q_in_arr, q_out_arr, must_send,
                             c.V, c.beta, kernel_safe, axis, n_shards)
        backlog = _vsum(q_in_arr, kernel_safe) + c.beta * q_out_arr.sum()
        cost = dec.cost
        if axis is not None:
            backlog = jax.lax.psum(backlog, axis)
            cost = jax.lax.psum(cost, axis)

    # -- 3. drain sources oldest-first, split over targets -------------------
    with jax.named_scope("drain"):
        shipped_cmp = _to_cmp(c, dec.shipped, kernel_safe)
        src_spout = jnp.concatenate(
            [jnp.zeros((I, S, age_cap), dt), q_rem, admit[:, :, None]], axis=-1
        )
        src_bolt = jnp.concatenate([q_out_tag, jnp.zeros((I, S, 1), dt)], axis=-1)
        src_ext = jnp.where(spout_f[:, None, None] > 0, src_spout, src_bolt)  # (I, S, Atot+1)
        drained = _drain_ages(src_ext, shipped_cmp, kernel_safe)
        q_rem = q_rem - drained[:, :, age_cap:Atot] * spout_f[:, None, None]
        admit = admit - drained[:, :, Atot] * spout_f[:, None]
        q_out_tag = q_out_tag - drained[:, :, :Atot] * bolt_f[:, None, None]

        # landing: the admission slot re-tags to age 0 (bucket age_cap) on landing
        d_land = jnp.concatenate(
            [drained[:, :, :age_cap],
             drained[:, :, age_cap:age_cap + 1] + drained[:, :, Atot:],
             drained[:, :, age_cap + 1:Atot]], axis=-1,
        )  # (I, S, Atot)
        sh_safe = jnp.where(dec.shipped > 0, dec.shipped, 1.0)
        live = dec.shipped > _EPS
        w_pt = jnp.where(live, dec.point / sh_safe, 0.0)
        w_ev = jnp.where(live, dec.even_per / sh_safe, 0.0)
        if kernel_safe:
            land, ev_cb = _land_kernel(c, dec.j_point, w_pt, w_ev, d_land)
            ev_rows = jax.lax.dot_general(c.comp_onehot, ev_cb, (((1,), (0,)), ((), ())),
                                          precision=_HI, preferred_element_type=dt)  # (I, Atot)
        else:
            d_dense = _to_dense3(c, d_land)  # (I, C, Atot)
            wd = (w_pt[:, :, None] * d_dense).reshape(I * C, Atot)
            if axis is not None:
                # point targets are global ids: scatter the local sources' mass
                # into the global landing buffer, fold it (the one O(I)-sized
                # collective — the physical tuple transfer), keep our row block
                I_all = I * n_shards
                land_g = jnp.zeros((I_all + 1, Atot), dt).at[
                    dec.j_point.reshape(I * C)].add(wd)[:I_all]
                land_g = jax.lax.psum(land_g, axis)
                land = jax.lax.dynamic_slice_in_dim(land_g, jax.lax.axis_index(axis) * I, I)
            else:
                land = jnp.zeros((I + 1, Atot), dt).at[dec.j_point.reshape(I * C)].add(wd)[:I]
            # even spread: per-component contraction, broadcast to alive instances
            ev_cb = jnp.einsum("ic,icb->cb", w_ev, d_dense, precision=_HI)  # (C, Atot)
            if axis is not None:
                ev_cb = jax.lax.psum(ev_cb, axis)
            ev_rows = ev_cb[c.inst_comp]
        land = land + cp.alive[:, None] * ev_rows
        if c.keyed is not None:
            with jax.named_scope("keyed"):
                # fields groupings: what a source drained toward a keyed
                # component (its whole shipment there: no point or even
                # part) lands on instance j at share key_share[j] — the even
                # spread's per-component contraction, weighted by the share
                w_key = jnp.where(live & (c.keyed > 0.0)[None, :], 1.0, 0.0).astype(dt)
                key_cb = jnp.einsum("ic,icb->cb", w_key, d_dense, precision=_HI)
                land = land + c.key_share[:, None] * key_cb[c.inst_comp]

    # -- 4. land last slot's transit, serve bolts ----------------------------
    with jax.named_scope("land"):
        avail = q_in_tag + transit
        served_amt = jnp.minimum(avail.sum(-1), mu_eff) * bolt_f
        served_b = _drain_ages(avail, served_amt, kernel_safe)
        q_in_tag = (avail - served_b) * bolt_f[:, None]
        cmass = jax.lax.dot_general(
            c.comp_onehot, served_b * c.term_f[:, None], (((0,), (0,)), ((), ())),
            precision=_HI, preferred_element_type=dt,
        )  # (C, Atot)
        if axis is not None:
            # fold served mass so the replicated response accumulators see the
            # global per-component completions on every shard
            cmass = jax.lax.psum(cmass, axis)
        if kernel_safe:
            ages = jax.lax.broadcasted_iota(jnp.int32, (1, Atot), 1).astype(dt)  # 2-D int iota
            resp_row = jnp.maximum(age_cap - ages, 0.0)  # (1, Atot)
            # accumulator columns [t, t + Atot) — always in range (len >= Tc + Atot);
            # Pallas TPU cannot slice a value at a traced offset, so the bucket
            # rows land through an (Atot, L) one-hot placement (one exact product
            # per column, the same sums as the scatter below)
            L = resp_mass.shape[-1]
            col = jax.lax.broadcasted_iota(jnp.int32, (Atot, L), 1)
            b = jax.lax.broadcasted_iota(jnp.int32, (Atot, L), 0)
            place = (col == b + jnp.asarray(t, jnp.int32)).astype(dt)
            dims = (((1,), (0,)), ((), ()))
            resp_mass = resp_mass + jax.lax.dot_general(
                cmass, place, dims, precision=_HI, preferred_element_type=dt)
            resp_time = resp_time + jax.lax.dot_general(
                cmass * resp_row, place, dims, precision=_HI, preferred_element_type=dt)
        else:
            resp_per_b = jnp.maximum(age_cap - jnp.arange(Atot, dtype=dt), 0.0)
            idx = t + jnp.arange(Atot)
            resp_mass = resp_mass.at[:, idx].add(cmass, mode="drop")
            resp_time = resp_time.at[:, idx].add(cmass * resp_per_b[None, :], mode="drop")
        capped_served = _vsum(cmass[:, 0], kernel_safe)
        term_served = cmass.sum()
        q_out_tag = q_out_tag + served_b[:, None, :] * c.sel_cmp[:, :, None] * bolt_f[:, None, None]

    # -- 5. admit leftover actuals, shift windows and age axes ---------------
    with jax.named_scope("admit"):
        admit = admit + q_rem[:, :, 0] * spout_f[:, None]
        q_rem = jnp.concatenate(
            [q_rem[:, :, 1:], (_to_cmp(c, new_pred, kernel_safe) * c.stream_cmp)[:, :, None]],
            axis=-1,
        )

        def shift(x):  # age b+1 -> b; the oldest bucket saturates (A-cap rule)
            head = x[..., 0:1] + x[..., 1:2]
            return jnp.concatenate([head, x[..., 2:], jnp.zeros_like(x[..., 0:1])], axis=-1)

        state = (q_rem, admit, shift(q_in_tag), shift(q_out_tag), shift(land),
                 resp_mass, resp_time)
    out = (backlog, cost, capped_served, term_served)
    if metrics_spec is not None:
        # §14 metric streams ride as extra scan outputs. Under sharding the
        # (I,)-vector inputs are all-gathered so every shard emits the same
        # replicated global row (the quantile/sort reductions need the full
        # vector); scalars fold with psum. Never on the kernel path — the
        # engine gates metrics off it (collectives cannot lower into Pallas).
        with jax.named_scope("metrics"):
            landed = land.sum(-1)
            price = c.V * c.U.mean(axis=0)[c.inst_cont] + q_in_arr
            comp_backlog = jnp.einsum("i,ic->c", q_in_arr, c.comp_onehot)
            held = admit.sum()
            dropped = (r * (pred_m - tp)).sum()
            tp_s, fp_s, tn_s = tp.sum(), (pred_m - tp).sum(), tn.sum()
            if axis is not None:
                q_in_g = jax.lax.all_gather(q_in_arr, axis, tiled=True)
                price_g = jax.lax.all_gather(price, axis, tiled=True)
                landed_g = jax.lax.all_gather(landed, axis, tiled=True)
                comp_backlog = jax.lax.psum(comp_backlog, axis)
                held = jax.lax.psum(held, axis)
                dropped = jax.lax.psum(dropped, axis)
                tp_s = jax.lax.psum(tp_s, axis)
                fp_s = jax.lax.psum(fp_s, axis)
                tn_s = jax.lax.psum(tn_s, axis)
            else:
                q_in_g, price_g, landed_g = q_in_arr, price, landed
            ctx = {
                "h": backlog, "q_in": q_in_g, "price": price_g, "landed": landed_g,
                "transit_total": landed_g.sum(), "comp_backlog": comp_backlog,
                "held": held, "dropped": dropped, "tp": tp_s, "fp": fp_s, "tn": tn_s,
                "capped": capped_served, "served": term_served,
            }
            out = out + compute_scan_streams(scan_stream_names(metrics_spec), ctx)
    return state, out
