"""Fused one-dispatch slot kernel — schedule, drain, split, serve, and
queue/age-mass update for K slots in one Pallas launch (DESIGN.md §12).

The fused cohort engine's hot loop used to issue several dispatches per slot
(price tile, water-fill, drain+split, queue update), round-tripping prices
and age-mass tiles through HBM between them. This kernel runs the *entire*
slot step — stages 1–5 of DESIGN.md §8, in the compact one-dispatch form of
``core/compact.py`` — inside one ``pallas_call``, so the per-(container,
component) price minima, the water-fill, and the landing tiles never leave
VMEM. With ``n_slots > 1`` it is the **megakernel**: K consecutive slots per
launch, amortizing launch overhead across the scan.

Memory layout (DESIGN.md §12):

* slot-invariant constants (``U``, topology index vectors, masks) load once
  per launch and are reused by every unrolled slot;
* the five queue-state arrays (``q_rem``, ``admit``, ``q_in``, ``q_out``,
  ``transit``) live in **double-buffered VMEM scratch pairs** ``(2, ...)``:
  slot ``k`` reads parity ``k % 2`` and writes parity ``(k + 1) % 2``. The
  slot loop is a *static* Python unroll, so the parity is a compile-time
  index — no dynamic scratch addressing, and the compiler can overlap slot
  ``k``'s tail stores with slot ``k+1``'s head loads;
* the response accumulators ``(C, L)`` and the per-slot metric rows are
  carried as SSA values and written back once at launch end.

The body *is* :func:`repro.core.compact.compact_slot_step` with
``kernel_safe=True`` — the same function the XLA path scans — so parity
between the kernel and the unfused composition is by construction up to the
documented kernel-safe substitutions (DESIGN.md §12.2), which are bitwise
on the dyadic tier. The engine launches this kernel only for compact
schedulers without a disruption trace; per-slot caps fall back to the
compact XLA step (DESIGN.md §12 lists the fallback conditions). The kernel
is grid-less, so a whole slot must fit VMEM: on a v5e that holds up to
I=256 of the fleet topology (``tests/test_tpu_compile.py``). It compiles on
the TPU and interprets on the CPU; parity is tested in
``tests/test_potus_slot.py``.

Under the instance-sharded scan (``EngineSpec(engine="cohort-fused",
sharded=True)``, DESIGN.md §13) the kernel runs per shard **only on a
single-shard mesh**: a multi-shard slot step must fold its decision with
``pmin``/``psum`` collectives, which cannot lower inside a Pallas body, so
the engine falls back to the compact XLA step there — same semantics, one
collective set per slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compact import StepConsts, compact_slot_step

from . import pallas_interpret

__all__ = ["potus_slot_kernel", "potus_slot_call"]


def potus_slot_kernel(
    # slot-invariant constants
    u_ref, mu_ref, invs_ref, sel_ref, stream_ref, valid_ref, succ_ref,
    term_ref, compoh_ref, icomp_ref, icont_ref, gamma_ref, ccount_ref,
    spout_ref, adj_ref, vb_ref,
    # per-launch inputs: K slots of arrivals plus the accumulator offset
    act_ref, pred_ref, nxt_ref, t0_ref,
    # queue state in
    qrem_ref, admit_ref, qin_ref, qout_ref, transit_ref, rmass_ref, rtime_ref,
    # outputs
    oqrem_ref, oadmit_ref, oqin_ref, oqout_ref, otransit_ref,
    ormass_ref, ortime_ref, met_ref,
    # double-buffered queue-state scratch
    sqrem, sadmit, sqin, sqout, stransit,
    *, scheduler: str, age_cap: int, n_slots: int,
):
    """One launch: ``n_slots`` consecutive slots of the cohort dynamics."""
    c = StepConsts(
        U=u_ref[...], mu=mu_ref[:, 0], inv_service=invs_ref[:, 0],
        sel_cmp=sel_ref[...], stream_cmp=stream_ref[...],
        valid_cmp=valid_ref[...], succ_map=succ_ref[...], term_f=term_ref[:, 0],
        comp_onehot=compoh_ref[...], inst_comp=icomp_ref[:, 0],
        inst_cont=icont_ref[:, 0], gamma=gamma_ref[:, 0],
        comp_count=ccount_ref[0], spout_f=spout_ref[:, 0],
        adj_rows=adj_ref[...], V=vb_ref[0, 0], beta=vb_ref[0, 1],
    )
    # parity-0 buffers <- launch input state
    sqrem[0] = qrem_ref[...]
    sadmit[0] = admit_ref[...]
    sqin[0] = qin_ref[...]
    sqout[0] = qout_ref[...]
    stransit[0] = transit_ref[...]
    rmass = rmass_ref[...]
    rtime = rtime_ref[...]
    t0 = t0_ref[0, 0]

    mets = []
    for k in range(n_slots):  # static unroll: the parity is a static index
        p, q = k % 2, (k + 1) % 2
        state = (sqrem[p], sadmit[p], sqin[p], sqout[p], stransit[p], rmass, rtime)
        xs = (act_ref[k], pred_ref[k], nxt_ref[k], t0 + k)
        state, met = compact_slot_step(
            c, state, xs, scheduler=scheduler, age_cap=age_cap, kernel_safe=True,
        )
        sqrem[q], sadmit[q], sqin[q], sqout[q], stransit[q] = state[:5]
        rmass, rtime = state[5], state[6]
        mets.append(jnp.stack(met))  # (4,): backlog, cost, capped, served

    p = n_slots % 2
    oqrem_ref[...] = sqrem[p]
    oadmit_ref[...] = sadmit[p]
    oqin_ref[...] = sqin[p]
    oqout_ref[...] = sqout[p]
    otransit_ref[...] = stransit[p]
    ormass_ref[...] = rmass
    ortime_ref[...] = rtime
    met_ref[...] = jnp.stack(mets, axis=1)  # (4, n_slots)


@functools.partial(jax.jit, static_argnames=("scheduler", "age_cap", "n_slots",
                                             "interpret"))
def potus_slot_call(
    consts: StepConsts,
    state,  # (q_rem, admit, q_in, q_out, transit, resp_mass, resp_time)
    act, pred, nxt,  # (n_slots, I, C) each
    t0,  # () int32 — chunk-local slot index of this launch's first slot
    scheduler: str = "potus",
    age_cap: int = 64,
    n_slots: int = 1,
    interpret: bool | None = None,
):
    """Run ``n_slots`` slots in one launch; returns ``(state, metrics)`` with
    ``metrics = (backlog, cost, capped, served)``, each ``(n_slots,)``."""
    q_rem, admit, q_in, q_out, transit, resp_mass, resp_time = state
    I, S, W1 = q_rem.shape
    C = consts.comp_onehot.shape[1]
    Atot = q_in.shape[-1]
    L = resp_mass.shape[-1]
    dt = q_rem.dtype  # f32 in the engine; f64 under the x64 parity tier
    col = lambda x, dtype=dt: x.astype(dtype).reshape(I, 1)

    out_shape = (
        jax.ShapeDtypeStruct((I, S, W1), dt),
        jax.ShapeDtypeStruct((I, S), dt),
        jax.ShapeDtypeStruct((I, Atot), dt),
        jax.ShapeDtypeStruct((I, S, Atot), dt),
        jax.ShapeDtypeStruct((I, Atot), dt),
        jax.ShapeDtypeStruct((C, L), dt),
        jax.ShapeDtypeStruct((C, L), dt),
        jax.ShapeDtypeStruct((4, n_slots), dt),
    )
    outs = pl.pallas_call(
        functools.partial(potus_slot_kernel, scheduler=scheduler,
                          age_cap=age_cap, n_slots=n_slots),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, I, S, W1), dt),
            pltpu.VMEM((2, I, S), dt),
            pltpu.VMEM((2, I, Atot), dt),
            pltpu.VMEM((2, I, S, Atot), dt),
            pltpu.VMEM((2, I, Atot), dt),
        ],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(
        consts.U.astype(dt), col(consts.mu), col(consts.inv_service),
        consts.sel_cmp.astype(dt), consts.stream_cmp.astype(dt),
        consts.valid_cmp.astype(dt), consts.succ_map.astype(jnp.int32),
        col(consts.term_f), consts.comp_onehot.astype(dt),
        col(consts.inst_comp, jnp.int32), col(consts.inst_cont, jnp.int32),
        col(consts.gamma), consts.comp_count.astype(dt).reshape(1, C),
        col(consts.spout_f), consts.adj_rows.astype(dt),
        jnp.stack([consts.V, consts.beta]).astype(dt).reshape(1, 2),
        act.astype(dt), pred.astype(dt), nxt.astype(dt),
        jnp.asarray(t0, jnp.int32).reshape(1, 1),
        q_rem, admit.astype(dt), q_in.astype(dt),
        q_out.astype(dt), transit.astype(dt),
        resp_mass.astype(dt), resp_time.astype(dt),
    )
    met = outs[7]
    return outs[:7], (met[0], met[1], met[2], met[3])
