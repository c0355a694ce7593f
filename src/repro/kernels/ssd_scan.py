"""Mamba2 SSD intra-chunk kernel (diagonal block + chunk input states).

Grid (b, nc, H): each program handles one (batch, chunk, head) tile:

  y_diag = (C B^T ⊙ decay ⊙ dt) X          -- (Q,Q) masked quadratic form
  state  = X^T (B ⊙ (decay_to_end · dt))   -- (P,S) chunk contribution

All contractions are MXU matmuls with fp32 accumulation; the decay mask is
built from a cumulative-ΔA block in VMEM. The cross-chunk linear recurrence
stays in ``lax.scan`` (sequential by construction, negligible FLOPs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_interpret

__all__ = ["ssd_intra_chunk_kernel", "ssd_intra_chunk_call"]


def ssd_intra_chunk_kernel(x_ref, dt_ref, dA_ref, b_ref, c_ref, y_ref, s_ref):
    x = x_ref[0, 0, :, 0, :].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)  # (Q,)
    dA = dA_ref[0, 0, :, 0].astype(jnp.float32)  # (Q,) cumulative
    B = b_ref[0, 0].astype(jnp.float32)  # (Q, S)
    C = c_ref[0, 0].astype(jnp.float32)  # (Q, S)
    Q = x.shape[0]

    seg = dA[:, None] - dA[None, :]  # (Q, Q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.where(qi >= ki, jnp.exp(seg), 0.0)

    cb = jnp.dot(C, B.T, preferred_element_type=jnp.float32)  # (Q, Q)
    w = cb * decay * dt[None, :]
    y = jnp.dot(w, x, preferred_element_type=jnp.float32)  # (Q, P)
    y_ref[0, 0, :, 0, :] = y.astype(y_ref.dtype)

    decay_to_end = jnp.exp(dA[-1] - dA) * dt  # (Q,)
    state = jnp.dot(x.T, B * decay_to_end[:, None], preferred_element_type=jnp.float32)
    s_ref[0, 0, 0] = state.astype(s_ref.dtype)  # (P, S)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk_call(xc, dtc, dA_cum, Bc, Cc, interpret: bool | None = None):
    """xc: (b, nc, Q, H, P); dtc/dA_cum: (b, nc, Q, H); Bc/Cc: (b, nc, Q, S).
    Returns y_diag (b, nc, Q, H, P), states (b, nc, H, P, S)."""
    b, nc, Q, H, P = xc.shape
    S = Bc.shape[-1]
    y, states = pl.pallas_call(
        ssd_intra_chunk_kernel,
        grid=(b, nc, H),
        in_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda i, n, h: (i, n, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda i, n, h: (i, n, 0, h)),
            pl.BlockSpec((1, 1, Q, 1), lambda i, n, h: (i, n, 0, h)),
            pl.BlockSpec((1, 1, Q, S), lambda i, n, h: (i, n, 0, 0)),
            pl.BlockSpec((1, 1, Q, S), lambda i, n, h: (i, n, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda i, n, h: (i, n, 0, h, 0)),
            pl.BlockSpec((1, 1, 1, P, S), lambda i, n, h: (i, n, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xc.shape, xc.dtype),
            jax.ShapeDtypeStruct((b, nc, H, P, S), jnp.float32),
        ],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(xc, dtc, dA_cum, Bc, Cc)
    return y, states
