"""Fused cohort drain kernel — segmented prefix-sum drain *and* proportional
split across successor targets in one VMEM pass (DESIGN.md §8).

The fused cohort engine's per-slot hot spot is the landing computation

    land[j, b] = sum_i ratio[i, j] * drained[i, comp(j), b]

where ``drained`` is the oldest-first water-fill of each source's age-tagged
buffer (``clip(shipped - cum_before, 0, bucket)``). The XLA path materializes
the full ``(I, C, Atot)`` drained tensor plus an ``(I, C, Atot)`` matmul
intermediate in HBM every slot; this kernel keeps both in VMEM.

The grid is ``(target tiles, source tiles)``, source-major accumulation: each
program loads one stripe of the extended source buffer ``src_ext``
(``(block_i, C, Aext)`` — window/backlog layout for spouts, age buckets for
bolts, one trailing admission slot), water-fills it against the requested
``shipped`` amounts, folds the trailing admission slot into the age-0 bucket
(same pattern as ``kernels/potus_schedule.py``'s in-kernel reductions), and
contracts the stripe against its block of the split-ratio matrix on the MXU,
accumulating the ``(block_j, Atot)`` landing tile across source tiles. Only
``land`` is written back; the state-update slices of the drain stay in XLA
(they are elementwise and fuse there).

Off-TPU the kernel runs in interpret mode; parity with the XLA path is
tested in ``tests/test_cohort_fused.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_interpret

__all__ = ["cohort_drain_kernel", "cohort_drain_call"]


def cohort_drain_kernel(src_ref, ship_ref, ratio_ref, oh_ref, land_ref, *,
                        age_bucket: int, n_age: int):
    """One (target-tile, source-tile) program of the fused drain+split."""
    src = src_ref[...]  # (bi, C, Aext)
    ship = ship_ref[...]  # (bi, C)
    # oldest-first water-fill along the age axis (masked prefix sum)
    cum = jnp.cumsum(src, axis=-1)
    drained = jnp.clip(ship[:, :, None] - (cum - src), 0.0, src)
    # fold the trailing admission slot into the age-0 bucket (it drains last
    # but lands re-tagged as current-slot mass)
    land_src = drained[:, :, :n_age].at[:, :, age_bucket].add(drained[:, :, n_age])
    bi, C = ship.shape
    # contract sources on the MXU: (bj, bi) x (bi, C * n_age)
    tmp = jax.lax.dot_general(
        ratio_ref[...], land_src.reshape(bi, C * n_age),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(-1, C, n_age)  # (bj, C, n_age)
    # keep each target column's own component plane
    contrib = jnp.sum(tmp * oh_ref[...][:, :, None], axis=1)  # (bj, n_age)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        land_ref[...] = contrib

    @pl.when(pl.program_id(1) > 0)
    def _accum():
        land_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("age_bucket", "block_i", "block_j", "interpret"))
def cohort_drain_call(src_ext, shipped, ratio, inst_comp, age_bucket: int,
                      block_i: int = 8, block_j: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    """Landing buckets ``land`` (I, Atot) for one cohort slot.

    ``src_ext``: (I, C, Atot + 1) extended drain buffer; ``shipped``: (I, C)
    requested amounts; ``ratio``: (I, I) per-target split fractions;
    ``inst_comp``: (I,) component of each target column; ``age_bucket``: the
    age-0 bucket index the trailing admission slot folds into.
    """
    I, C, Aext = src_ext.shape
    n_age = Aext - 1
    block_i = min(block_i, I)
    block_j = min(block_j, I)
    pad_i = (-I) % block_i
    pad_j = (-I) % block_j
    Ip, Jp = I + pad_i, I + pad_j

    src_p = jnp.pad(src_ext.astype(jnp.float32), ((0, pad_i), (0, 0), (0, 0)))
    ship_p = jnp.pad(shipped.astype(jnp.float32), ((0, pad_i), (0, 0)))
    ratio_p = jnp.pad(ratio.astype(jnp.float32), ((0, pad_i), (0, pad_j)))
    oh = jax.nn.one_hot(inst_comp, C, dtype=jnp.float32)  # (I, C)
    oh_p = jnp.pad(oh, ((0, pad_j), (0, 0)))

    land = pl.pallas_call(
        functools.partial(cohort_drain_kernel, age_bucket=age_bucket, n_age=n_age),
        grid=(Jp // block_j, Ip // block_i),
        in_specs=[
            pl.BlockSpec((block_i, C, Aext), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((block_i, C), lambda j, i: (i, 0)),
            pl.BlockSpec((block_i, block_j), lambda j, i: (i, j)),
            pl.BlockSpec((block_j, C), lambda j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_j, n_age), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((Jp, n_age), jnp.float32),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(src_p, ship_p, ratio_p, oh_p)
    return land[:I]
