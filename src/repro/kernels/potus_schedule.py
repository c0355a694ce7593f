"""Fused POTUS schedule kernel — price tile *and* per-row allocation in one
Pallas kernel, so the (I × I) price matrix never round-trips to HBM
(DESIGN.md §7).

The grid walks row stripes of ``block_i`` source instances. Each program:

1. streams the row stripe's price tiles (the §4 one-hot-matmul formulation,
   ``block_j`` columns at a time), folding them into a per-(row, component)
   running minimum ``m`` and argmin column ``j_c`` — the only state the
   water-fill needs, ``(block_i, C)`` instead of ``(block_i, I)``;
2. water-fills ``gamma_i`` against the per-component ``q_out`` budgets in
   ascending (price, column) order. The sort is replaced by an O(C²) rank
   reduction — for each component, the budget mass strictly preceding it —
   which is branch-free and MXU/VPU friendly for the small C of real
   topologies;
3. streams the stripe again, scattering each component's fill to its argmin
   column of the output tile.

Only the compact allocation ``X`` stripe is written back; the mandatory
dispatch of actual arrivals (eq. 4) stays in XLA (`core.potus`). Off-TPU the
kernel runs in interpret mode; parity with the XLA sort path is tested in
``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_interpret

__all__ = ["potus_schedule_kernel", "potus_schedule_call"]


def potus_schedule_kernel(vb_ref, kc_i_ref, gamma_ref, qout_i_ref, kc_j_ref,
                          comp_j_ref, qin_j_ref, u_ref, mask_ref, x_ref, *,
                          block_j: int):
    V = vb_ref[0, 0]
    beta = vb_ref[0, 1]
    K = u_ref.shape[0]
    C = qout_i_ref.shape[1]
    bi = kc_i_ref.shape[0]
    Jp = kc_j_ref.shape[0]
    n_tiles = Jp // block_j

    kc_i = kc_i_ref[:, 0]  # (bi,)
    oh_i = (jax.lax.broadcasted_iota(jnp.int32, (bi, K), 1) == kc_i[:, None]).astype(jnp.float32)
    u_rows = jnp.dot(oh_i, u_ref[...], preferred_element_type=jnp.float32)  # (bi, K)
    qout = qout_i_ref[...]  # (bi, C)
    gamma = gamma_ref[:, 0]  # (bi,)

    def price_tile(t):
        """Candidate prices for one (bi, block_j) tile; +inf off-candidates."""
        cols = pl.ds(t * block_j, block_j)
        kc_j = kc_j_ref[cols, 0]  # (bj,)
        comp_j = comp_j_ref[cols, 0]  # (bj,)
        qin_j = qin_j_ref[cols, 0]  # (bj,)
        mask = mask_ref[:, cols]  # (bi, bj)
        oh_j = (jax.lax.broadcasted_iota(jnp.int32, (block_j, K), 1)
                == kc_j[:, None]).astype(jnp.float32)
        u_tile = jnp.dot(u_rows, oh_j.T, preferred_element_type=jnp.float32)  # (bi, bj)
        oh_c = (jax.lax.broadcasted_iota(jnp.int32, (block_j, C), 1)
                == comp_j[:, None]).astype(jnp.float32)
        qo_tile = jnp.dot(qout, oh_c.T, preferred_element_type=jnp.float32)  # (bi, bj)
        l = V * u_tile + qin_j[None, :] - beta * qo_tile
        key = jnp.where((mask > 0) & (l < 0.0), l, jnp.inf)
        return key, oh_c

    def reduce_body(t, carry):
        m, j_c = carry  # (bi, C) running min price / argmin column
        key, oh_c = price_tile(t)
        col_ids = t * block_j + jax.lax.broadcasted_iota(jnp.int32, (1, block_j, 1), 1)
        key_c = jnp.where(oh_c[None, :, :] > 0, key[:, :, None], jnp.inf)  # (bi, bj, C)
        m_tile = jnp.min(key_c, axis=1)  # (bi, C)
        idx_c = jnp.where(key_c == m_tile[:, None, :], col_ids, Jp)
        j_tile = jnp.min(idx_c, axis=1)  # (bi, C)
        better = (m_tile < m) | ((m_tile == m) & (j_tile < j_c))
        return jnp.where(better, m_tile, m), jnp.where(better, j_tile, j_c)

    m0 = jnp.full((bi, C), jnp.inf, jnp.float32)
    j0 = jnp.full((bi, C), Jp, jnp.int32)
    m, j_c = jax.lax.fori_loop(0, n_tiles, reduce_body, (m0, j0))

    # --- water-fill gamma over components in ascending (price, column) -----
    budget = jnp.where(m < 0.0, jnp.maximum(qout, 0.0), 0.0)  # (bi, C)
    prec = (m[:, :, None] < m[:, None, :]) | (
        (m[:, :, None] == m[:, None, :]) & (j_c[:, :, None] < j_c[:, None, :])
    )  # (bi, C', C): component C' strictly precedes component C
    before = jnp.sum(budget[:, :, None] * prec, axis=1)  # (bi, C)
    fill = (jnp.minimum(before + budget, gamma[:, None])
            - jnp.minimum(before, gamma[:, None]))  # (bi, C)

    def write_body(t, _):
        col_ids = t * block_j + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_j), 2)
        sel = j_c[:, :, None] == col_ids  # (bi, C, bj)
        x_tile = jnp.sum(jnp.where(sel, fill[:, :, None], 0.0), axis=1)  # (bi, bj)
        x_ref[:, pl.ds(t * block_j, block_j)] = x_tile
        return 0

    jax.lax.fori_loop(0, n_tiles, write_body, 0)


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def potus_schedule_call(U, q_in, q_out, inst_container, inst_comp, edge_mask,
                        gamma, V: float, beta: float, block_i: int = 8,
                        block_j: int = 128, interpret: bool | None = None):
    """Greedy allocation X (I, I) of Algorithm 1 lines 9-14 (no mandatory
    dispatch), computed by the fused Pallas kernel."""
    I = q_in.shape[0]
    K = U.shape[0]
    C = q_out.shape[1]
    block_i = min(block_i, I)
    block_j = min(block_j, I)
    pad_i = (-I) % block_i
    pad_j = (-I) % block_j
    Ip, Jp = I + pad_i, I + pad_j

    kc = inst_container.astype(jnp.int32).reshape(I, 1)
    cp = inst_comp.astype(jnp.int32).reshape(I, 1)
    qin = q_in.astype(jnp.float32).reshape(I, 1)
    kc_i = jnp.pad(kc, ((0, pad_i), (0, 0)))
    gamma_i = jnp.pad(gamma.astype(jnp.float32).reshape(I, 1), ((0, pad_i), (0, 0)))
    qout_i = jnp.pad(q_out.astype(jnp.float32), ((0, pad_i), (0, 0)))
    kc_j = jnp.pad(kc, ((0, pad_j), (0, 0)))
    cp_j = jnp.pad(cp, ((0, pad_j), (0, 0)), constant_values=C)  # pad cols: no component
    qin_j = jnp.pad(qin, ((0, pad_j), (0, 0)))
    mask = jnp.pad(edge_mask.astype(jnp.float32), ((0, pad_i), (0, pad_j)))

    vb = jnp.stack([jnp.asarray(V, jnp.float32), jnp.asarray(beta, jnp.float32)]).reshape(1, 2)
    x = pl.pallas_call(
        functools.partial(potus_schedule_kernel, block_j=block_j),
        grid=(Ip // block_i,),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
            pl.BlockSpec((block_i, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_i, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_i, C), lambda i: (i, 0)),
            pl.BlockSpec((Jp, 1), lambda i: (0, 0)),
            pl.BlockSpec((Jp, 1), lambda i: (0, 0)),
            pl.BlockSpec((Jp, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, K), lambda i: (0, 0)),
            pl.BlockSpec((block_i, Jp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_i, Jp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Ip, Jp), jnp.float32),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(vb, kc_i, gamma_i, qout_i, kc_j, cp_j, qin_j, U.astype(jnp.float32), mask)
    return x[:I, :I]
