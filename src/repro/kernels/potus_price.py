"""POTUS price matrix (eq. 16) as a Pallas TPU kernel — the paper's
decision-making hot spot at fleet scale.

TPU adaptation (DESIGN.md §4): the two gathers — ``U[k(i), k(j)]`` and
``q_out[i, comp(j)]`` — are reformulated as one-hot **matmuls** so the whole
price tile is produced by the MXU instead of scatter/gather units:

  u_tile  = onehot(kc_i) @ U @ onehot(kc_j)^T         (bi,K)(K,K)(K,bj)
  qo_tile = q_out_i @ onehot(comp_j)^T                 (bi,C)(C,bj)
  l       = V*u_tile + q_in_j^T - beta*qo_tile, masked to DAG edges

Grid tiles (block_i × block_j) of the (I × I) price matrix; U stays resident
in VMEM (K ≤ ~1024 hosts -> ≤ 4 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_interpret

__all__ = ["potus_price_kernel", "potus_price_call"]


def potus_price_kernel(vb_ref, kc_i_ref, kc_j_ref, comp_j_ref, qin_j_ref, qout_i_ref,
                       u_ref, mask_ref, l_ref):
    V = vb_ref[0, 0]
    beta = vb_ref[0, 1]
    K = u_ref.shape[0]
    C = qout_i_ref.shape[1]
    kc_i = kc_i_ref[:, 0]  # (bi,)
    kc_j = kc_j_ref[:, 0]  # (bj,)
    comp_j = comp_j_ref[:, 0]  # (bj,)
    bi, bj = kc_i.shape[0], kc_j.shape[0]

    oh_i = (jax.lax.broadcasted_iota(jnp.int32, (bi, K), 1) == kc_i[:, None]).astype(jnp.float32)
    oh_j = (jax.lax.broadcasted_iota(jnp.int32, (bj, K), 1) == kc_j[:, None]).astype(jnp.float32)
    u_rows = jnp.dot(oh_i, u_ref[...], preferred_element_type=jnp.float32)  # (bi, K)
    u_tile = jnp.dot(u_rows, oh_j.T, preferred_element_type=jnp.float32)  # (bi, bj)

    oh_c = (jax.lax.broadcasted_iota(jnp.int32, (bj, C), 1) == comp_j[:, None]).astype(jnp.float32)
    qo_tile = jnp.dot(qout_i_ref[...], oh_c.T, preferred_element_type=jnp.float32)

    l = V * u_tile + qin_j_ref[:, 0][None, :] - beta * qo_tile
    l_ref[...] = jnp.where(mask_ref[...] > 0, l, jnp.inf)


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def potus_price_call(U, q_in, q_out, inst_container, inst_comp, edge_mask,
                     V: float, beta: float, block_i: int = 128, block_j: int = 128,
                     interpret: bool | None = None):
    """Returns the (I, I) price matrix l (eq. 16), +inf off the DAG edges."""
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
    kc_j = jnp.pad(kc, ((0, pad_j), (0, 0)))
    cp_j = jnp.pad(cp, ((0, pad_j), (0, 0)))
    qin_j = jnp.pad(qin, ((0, pad_j), (0, 0)))
    qout_i = jnp.pad(q_out.astype(jnp.float32), ((0, pad_i), (0, 0)))
    mask = jnp.pad(edge_mask.astype(jnp.float32), ((0, pad_i), (0, pad_j)))

    vb = jnp.stack([jnp.asarray(V, jnp.float32), jnp.asarray(beta, jnp.float32)]).reshape(1, 2)
    l = pl.pallas_call(
        potus_price_kernel,
        grid=(Ip // block_i, Jp // block_j),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
            pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_j, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_j, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_j, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_i, C), lambda i, j: (i, 0)),
            pl.BlockSpec((K, K), lambda i, j: (0, 0)),
            pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Ip, Jp), jnp.float32),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(vb, kc_i, kc_j, cp_j, qin_j, qout_i, U.astype(jnp.float32), mask)
    return l[:I, :I]
