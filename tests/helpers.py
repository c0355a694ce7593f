"""Shared test adapters over the unified engine facade (DESIGN.md §12).

The legacy entry points (``run_sim`` / ``run_cohort_sim`` /
``run_cohort_fused``) were removed one release after ``simulate(EngineSpec)``
landed. The differential suites still speak their (topo, net, placement,
arrivals, T, SimConfig) shape, so these adapters translate that shape into
an :class:`~repro.core.engine.EngineSpec` and call :func:`simulate` — every
test therefore exercises the facade routing, not a private impl.
"""
from __future__ import annotations

from repro.core import EngineSpec, simulate


def _base(topo, net, placement, arrivals, T, cfg, engine, **kw):
    return EngineSpec(
        topo=topo, net=net, placement=placement, arrivals=arrivals, T=T,
        engine=engine, scheduler=cfg.scheduler, V=cfg.V, beta=cfg.beta,
        window=cfg.window, use_pallas=cfg.use_pallas, **kw,
    )


def run_sim(topo, net, placement, arrivals, T, cfg, mu=None, events=None,
            chunk=None):
    """The scan engine via the facade (``engine="sharded"`` when
    ``cfg.sharded``)."""
    engine = "sharded" if cfg.sharded else "jax"
    kw = {}
    if mu is not None:
        kw["mu"] = mu
    if chunk is not None:
        kw["chunk"] = chunk
    return simulate(_base(topo, net, placement, arrivals, T, cfg, engine,
                          events=events, **kw))


def run_cohort_sim(topo, net, placement, arrivals, predicted, T, cfg,
                   warmup=50, drain_margin=None, events=None):
    """The Python discrete-event cohort engine via the facade."""
    return simulate(_base(topo, net, placement, arrivals, T, cfg, "cohort",
                          predicted=predicted, warmup=warmup,
                          drain_margin=drain_margin, events=events))


def run_cohort_fused(topo, net, placement, arrivals, predicted, T, cfg,
                     warmup=50, drain_margin=None, age_cap=64, events=None,
                     service=None, chunk=None, sharded=False):
    """The fused cohort engine via the facade."""
    kw = {}
    if chunk is not None:
        kw["chunk"] = chunk
    return simulate(_base(topo, net, placement, arrivals, T, cfg,
                          "cohort-fused", predicted=predicted, warmup=warmup,
                          drain_margin=drain_margin, age_cap=age_cap,
                          events=events, service=service, sharded=sharded,
                          **kw))
