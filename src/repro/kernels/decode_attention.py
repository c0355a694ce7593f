"""Single-token KV-cache attention as a Pallas TPU kernel.

Grid (B, Hkv): each program attends one request's query group (G = Hq/Hkv
query heads) against that KV head's cache stream, in ``block_s`` chunks with
an online-softmax accumulator. The per-request valid length ``pos`` arrives
as a (1,1) VMEM scalar; fully-masked chunks past ``pos`` are skipped by the
loop bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import pallas_interpret

__all__ = ["decode_attention_kernel", "decode_attention_call"]

NEG_INF = -1e30


def decode_attention_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, block_s: int,
                            scale: float, seq_len: int):
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
    G = q.shape[0]
    pos = pos_ref[0, 0]
    n_valid = pos + 1
    n_chunks = (n_valid + block_s - 1) // block_s

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * block_s, block_s), 0, :].astype(jnp.float32)  # (bs, D)
        v = v_ref[0, pl.ds(i * block_s, block_s), 0, :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (G, bs)
        idx = i * block_s + jax.lax.broadcasted_iota(jnp.int32, (G, block_s), 1)
        s = jnp.where(idx <= pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((G,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G,), jnp.float32)
    a0 = jnp.zeros((G, q_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_call(q, k_cache, v_cache, pos, block_s: int = 256,
                          interpret: bool | None = None):
    """q: (B, Hq, D); caches: (B, S, Hkv, D); pos: (B,) -> (B, Hq, D)."""
    B, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    qg = q.reshape(B, Hkv, G, D)
    pos2d = pos.reshape(B, 1).astype(jnp.int32)
    kernel = functools.partial(
        decode_attention_kernel, block_s=block_s, scale=1.0 / np.sqrt(D), seq_len=S
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h: (b, 0)),
            pl.BlockSpec((1, 1, G, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, S, 1, D), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, S, 1, D), lambda b, h: (b, 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(pos2d, qg, k_cache, v_cache)
    return out.reshape(B, Hq, D)
