"""Flash attention (GQA, causal/bidirectional) as a Pallas TPU kernel.

Blockwise online-softmax: grid (B, Hq, Sq/block_q); the KV stream for the
matching KV head lives in VMEM ((S, D) per block — fits comfortably for the
block sizes used) and is consumed in ``block_k`` chunks by a fori loop with
a running (m, l, acc) accumulator. Causal blocks strictly above the diagonal
are skipped via the loop bound; MXU matmuls via ``jnp.dot`` with fp32
accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import pallas_interpret

__all__ = ["flash_attention_kernel", "flash_attention_call"]

NEG_INF = -1e30


def flash_attention_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                           scale: float, seq_len: int):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, D)
    bq = q.shape[0]
    nk_total = seq_len // block_k

    if causal:
        # last kv block that intersects the causal triangle of this q block
        last = (qi + 1) * bq  # exclusive kv upper bound
        nk = (last + block_k - 1) // block_k
    else:
        nk = nk_total

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos = i * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, q_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention_call(q, k, v, causal: bool = True, block_q: int = 128,
                         block_k: int = 128, interpret: bool | None = None):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D) -> (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    grid = (B, Hq, S // block_q)
    kernel = functools.partial(
        flash_attention_kernel,
        block_k=block_k,
        causal=causal,
        scale=1.0 / np.sqrt(D),
        seq_len=S,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(q, k, v)
