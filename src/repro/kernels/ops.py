"""Jit'd public wrappers around the Pallas kernels.

Model code calls these through ``cfg.use_pallas``. Each kernel compiles on
the TPU and runs in interpret mode on the CPU, chosen from the backend when
the call is traced (``repro.kernels.pallas_interpret``); tests run on the CPU
(``JAX_PLATFORMS=cpu``) and so interpret, and any other backend raises.
Layouts are adapted from model-native (B, S, H, D) to kernel-native
(B, H, S, D).

None of the kernels contain cross-device collectives, so under ``shard_map``
they operate on the local shard only.
"""
from __future__ import annotations

import jax.numpy as jnp

from .cohort_drain import cohort_drain_call
from .decode_attention import decode_attention_call
from .flash_attention import flash_attention_call
from .potus_price import potus_price_call
from .potus_schedule import potus_schedule_call
from .ssd_scan import ssd_intra_chunk_call

__all__ = [
    "flash_attention", "decode_attention", "ssd_intra_chunk", "potus_price",
    "potus_schedule_alloc", "cohort_drain_split",
]


def flash_attention(q, k, v, causal: bool = True):
    """q: (B, S, Hq, D); k, v: (B, S, Hkv, D) -> (B, S, Hq, D)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_call(qt, kt, vt, causal=causal)
    return jnp.swapaxes(out, 1, 2)


def decode_attention(q, k_cache, v_cache, pos):
    """q: (B, Hq, D); caches: (B, S, Hkv, D); pos: (B,) -> (B, Hq, D)."""
    return decode_attention_call(q, k_cache, v_cache, pos)


def ssd_intra_chunk(xc, dtc, dA_cum, Bc, Cc):
    return ssd_intra_chunk_call(xc, dtc, dA_cum, Bc, Cc)


def potus_price(U, q_in, q_out, inst_container, inst_comp, edge_mask, V, beta):
    return potus_price_call(
        U, q_in, q_out, inst_container, inst_comp, edge_mask, V, beta)


def potus_schedule_alloc(U, q_in, q_out, inst_container, inst_comp, edge_mask, gamma, V, beta):
    """Fused price + water-fill allocation (DESIGN.md §7); returns X (I, I)
    before the mandatory dispatch of actual arrivals."""
    return potus_schedule_call(
        U, q_in, q_out, inst_container, inst_comp, edge_mask, gamma, V, beta)


def cohort_drain_split(src_ext, shipped, ratio, inst_comp, age_bucket):
    """Fused segmented drain + proportional target split of the cohort engine
    (DESIGN.md §8); returns the landing buckets ``land`` (I, Atot)."""
    return cohort_drain_call(src_ext, shipped, ratio, inst_comp, age_bucket)
