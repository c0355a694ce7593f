"""Unified engine facade: one frozen spec, one ``simulate()`` (DESIGN.md §12).

The four engines grew their own spellings of the same knobs — ``run_sim``
takes ``chunk=``/``events=``/``mu=``, ``run_cohort_fused`` takes
``service=``/``age_cap=``/``slots_per_launch=``, the sharded engine hides
behind ``SimConfig.sharded`` — and each rejected the options it lacks with an
ad-hoc message (or silently ignored them). This module is the single front
door:

* :class:`EngineSpec` — a frozen record of *everything* a run needs: the
  system (topology, network, placement), the arrival spec, the horizon, and
  every engine knob, spelled once;
* :func:`simulate` — validates the spec against the engine×option support
  matrix and dispatches to the engine implementation. Same spec, same
  result object as the legacy entry point, bit for bit;
* :class:`UnsupportedEngineOption` — the one error every engine raises for
  an option it does not support, naming the option, the engine, and the
  nearest engine that does support it.

The legacy entry points (``run_sim``, ``run_cohort_sim``,
``run_cohort_fused``) were removed one release after this facade landed, as
announced by their :class:`DeprecationWarning` shims; ``run_sweep`` keeps
its grid API (a sweep is a *set* of specs) but raises the same normalized
errors.

``sharded`` appears twice by design: ``engine="sharded"`` is the plain-jax
scan engine row-sharded over an instance mesh (DESIGN.md §7, (I, I) decision
per slot), while ``EngineSpec(engine="cohort-fused", sharded=True)`` shards
the compact one-dispatch cohort engine — full response-time semantics, no
(I, I) anywhere (DESIGN.md §13).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any

from repro.obs.trace import span as obs_span

__all__ = ["EngineSpec", "UnsupportedEngineOption", "simulate", "ENGINES",
           "OPTION_SUPPORT", "check_metrics_spec"]

#: engines :func:`simulate` dispatches to
ENGINES = ("jax", "sharded", "cohort", "cohort-fused")

#: which engines support which :class:`EngineSpec` option (an option absent
#: here is universal). ``simulate`` and ``run_sweep`` both validate against
#: this one matrix; ``tests/test_engine_api.py`` exercises every pair.
OPTION_SUPPORT = {
    "use_pallas": ("jax", "cohort", "cohort-fused"),
    "chunk": ("jax", "cohort-fused"),
    "mu": ("jax", "sharded"),
    "predicted": ("cohort", "cohort-fused"),
    "warmup": ("cohort", "cohort-fused"),
    "drain_margin": ("cohort", "cohort-fused"),
    "service": ("cohort-fused",),
    "age_cap": ("cohort-fused",),
    "slots_per_launch": ("cohort-fused",),
    # engine="sharded" *is* sharded; on cohort-fused the flag shards the
    # compact scan over the instance mesh (DESIGN.md §13)
    "sharded": ("sharded", "cohort-fused"),
    # every engine takes metrics=; *stream* availability is finer-grained
    # (obs.ENGINE_STREAMS) and checked by check_metrics_spec (DESIGN.md §14)
    "metrics": ("jax", "sharded", "cohort", "cohort-fused"),
    # not a field: a topology with a fields grouping (DESIGN.md §15), which
    # lands only on cohort-fused's compact XLA step, without Pallas, unsharded
    # (check_keyed)
    "keyed": ("cohort-fused",),
}

#: proximity order used to name the "nearest" supporting engine: the scan
#: engines are closest to each other, the two cohort (response-time) engines
#: are closest to each other
_NEAREST = {
    "jax": ("sharded", "cohort-fused", "cohort"),
    "sharded": ("jax", "cohort-fused", "cohort"),
    "cohort": ("cohort-fused", "jax", "sharded"),
    "cohort-fused": ("cohort", "jax", "sharded"),
}


class UnsupportedEngineOption(ValueError):
    """An :class:`EngineSpec` option the selected engine does not implement.

    The message always names the option, the rejecting engine, and the
    nearest engine that supports the option — one error shape for every
    engine×option pair instead of per-engine ad-hoc messages.
    """

    def __init__(self, engine: str, option: str, supported: tuple = (),
                 reason: str = ""):  # noqa: D107
        self.engine = engine
        self.option = option
        self.reason = reason
        supported = supported or OPTION_SUPPORT.get(option, ENGINES)
        self.nearest = next((e for e in _NEAREST.get(engine, ENGINES)
                             if e in supported), None)
        hint = (f"; the nearest engine that does is engine={self.nearest!r}"
                if self.nearest else "")
        why = f" ({reason})" if reason else ""
        super().__init__(
            f"engine={engine!r} does not support option {option!r}{why}{hint}"
        )


def check_engine_option(engine: str, option: str) -> None:
    """Raise :class:`UnsupportedEngineOption` unless ``engine`` supports
    ``option`` per :data:`OPTION_SUPPORT` (shared with ``run_sweep``)."""
    supported = OPTION_SUPPORT.get(option, ENGINES)
    if engine not in supported:
        raise UnsupportedEngineOption(engine, option, supported)


def check_keyed(engine: str, topo, scheduler: str, use_pallas: bool,
                sharded: bool) -> None:
    """Refuse a topology with a fields grouping (``topo.keyed``, DESIGN.md
    §15) everywhere but the path that lands one: ``cohort-fused``'s compact
    XLA step on one shard. Nothing falls back to routing the keyed edges as
    shuffle edges."""
    if not getattr(topo, "keyed", False):
        return
    check_engine_option(engine, "keyed")
    from .compact import COMPACT_SCHEDULERS  # lazy: compact imports us

    if use_pallas:
        why = "the Pallas slot kernel lands no fields grouping"
    elif sharded:
        why = "the instance-sharded scan lands no fields grouping"
    elif scheduler not in COMPACT_SCHEDULERS:
        why = f"scheduler {scheduler!r} keeps the dense (I, I) path, which has no grouping"
    else:
        return
    raise UnsupportedEngineOption(engine, "keyed", reason=why)


def check_metrics_spec(engine: str, metrics):
    """Coerce ``EngineSpec(metrics=...)`` to a ``MetricsSpec`` (or None) and
    reject streams the engine cannot compute in-graph, with the same
    normalized error shape as a whole unsupported option (shared with
    ``run_sweep``)."""
    from repro.obs.metrics import MetricsSpec, stream_engines, unsupported_streams

    spec = MetricsSpec.coerce(metrics)
    if spec is None:
        return None
    bad = unsupported_streams(engine, spec)
    if bad:
        raise UnsupportedEngineOption(
            engine, f"metrics[{bad[0]}]", supported=stream_engines(bad[0]),
            reason=f"stream {bad[0]!r} needs engine state {engine!r} lacks")
    return spec


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """One run, fully specified — the argument to :func:`simulate`.

    System fields (``topo``, ``net``, ``placement``, ``arrivals``, ``T``)
    plus every engine knob under its one canonical name. Options left at
    their defaults are "unset": setting a non-default value on an engine
    that lacks the option raises :class:`UnsupportedEngineOption`.
    """

    topo: Any  # Topology
    net: Any  # NetworkCosts
    placement: Any  # (I,) instance -> container
    arrivals: Any  # (T', I, C) array | ArrivalSpec
    T: int
    engine: str = "cohort-fused"  # jax | sharded | cohort | cohort-fused
    # scheduling knobs (SimConfig fields, canonical spelling)
    scheduler: str = "potus"
    V: float = 3.0
    beta: float = 1.0
    window: int = 0
    use_pallas: bool = False
    # engine knobs
    predicted: Any = None  # distinct predicted arrivals (cohort engines)
    events: Any = None  # EventTrace | FleetScenario trace (DESIGN.md §9)
    mu: Any = None  # capacity override (scan engines)
    chunk: int | None = None  # streaming scan (DESIGN.md §11.2)
    service: Any = None  # token-length service-time axis (DESIGN.md §10)
    warmup: int = 50
    drain_margin: int | None = None
    age_cap: int = 64
    slots_per_launch: int = 1  # megakernel slots per launch (DESIGN.md §12)
    sharded: bool = False  # shard cohort-fused over the instance mesh (DESIGN.md §13)
    metrics: Any = None  # MetricsSpec | stream names | True (DESIGN.md §14)

    def config(self):
        """The legacy :class:`~repro.core.simulator.SimConfig` equivalent."""
        from .simulator import SimConfig

        return SimConfig(V=self.V, beta=self.beta, window=self.window,
                         scheduler=self.scheduler, use_pallas=self.use_pallas,
                         sharded=self.engine == "sharded" or self.sharded)

    def _set_options(self):
        """Option names carrying a non-default value. None-default options
        (arrays, traces) are "set" when anything is passed at all — `!=`
        would be ambiguous on array values."""
        defaults = {f.name: f.default for f in dataclasses.fields(EngineSpec)
                    if f.name in OPTION_SUPPORT}
        return [name for name, default in defaults.items()
                if (getattr(self, name) is not None if default is None
                    else getattr(self, name) != default)]

    def validate(self) -> None:
        if self.engine not in ENGINES:
            raise ValueError(
                f"unknown engine {self.engine!r}; expected one of {ENGINES}")
        for option in self._set_options():
            check_engine_option(self.engine, option)
        check_keyed(self.engine, self.topo, self.scheduler, self.use_pallas,
                    self.sharded)


#: per-process index of :func:`simulate` calls, the request span's identifier
_CALLS = itertools.count()


def simulate(spec: EngineSpec):
    """Run one fully-specified simulation; the unified entry point.

    Routes to the engine implementations (``_run_sim_impl`` /
    ``_run_cohort_sim_impl`` / ``_run_cohort_fused_impl``), whose parity is
    asserted on the dyadic tier by ``tests/test_engine_api.py``. Returns the
    engine's native result type: :class:`~repro.core.simulator.SimResult`
    for the scan engines, :class:`~repro.core.cohort.CohortResult` for the
    cohort engines.
    """
    with obs_span("potus/engine/simulate", call=next(_CALLS), engine=spec.engine):
        return _simulate(spec)


def _simulate(spec: EngineSpec):
    spec.validate()
    cfg = spec.config()
    metrics = check_metrics_spec(spec.engine, spec.metrics)
    if spec.engine in ("jax", "sharded"):
        from .simulator import _run_sim_impl

        return _run_sim_impl(spec.topo, spec.net, spec.placement, spec.arrivals,
                             spec.T, cfg, mu=spec.mu, events=spec.events,
                             chunk=spec.chunk, metrics=metrics)
    if spec.engine == "cohort":
        from .cohort import _run_cohort_sim_impl

        return _run_cohort_sim_impl(
            spec.topo, spec.net, spec.placement, spec.arrivals, spec.predicted,
            spec.T, cfg, warmup=spec.warmup, drain_margin=spec.drain_margin,
            events=spec.events, metrics=metrics,
        )
    from .cohort_fused import _run_cohort_fused_impl

    return _run_cohort_fused_impl(
        spec.topo, spec.net, spec.placement, spec.arrivals, spec.predicted,
        spec.T, cfg, warmup=spec.warmup, drain_margin=spec.drain_margin,
        age_cap=spec.age_cap, events=spec.events, service=spec.service,
        chunk=spec.chunk, slots_per_launch=spec.slots_per_launch,
        sharded=spec.sharded, metrics=metrics,
    )
