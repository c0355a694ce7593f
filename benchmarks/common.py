"""Shared benchmark scaffolding: the paper's §5.1 experimental setup, plus
the machine-readable bench-JSON schema shared by every ``BENCH_*.json``
emitter (``BENCH_cohort.json``, ``BENCH_disruption.json``) so the perf
trajectory stays diffable across PRs."""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

from repro.core import (
    build_topology,
    container_costs,
    fat_tree,
    feasible_rates,
    jellyfish,
    poisson_arrivals,
    random_apps,
    t_heron_placement,
    trace_synthetic,
)

QUICK = os.environ.get("REPRO_BENCH_FULL", "0") != "1"
# SMOKE: CI-sized grid — tiny T and fleet sizes so the whole driver finishes
# in a couple of minutes on a shared runner (used by the ci.yml benchmarks job)
SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
T_SIM = 40 if SMOKE else (300 if QUICK else 1500)
T_COHORT = 40 if SMOKE else (300 if QUICK else 800)


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.2f},{self.derived}"


# ---------------------------------------------------------------------------
# machine-readable bench JSON (one schema for every BENCH_*.json)
# ---------------------------------------------------------------------------

BENCH_JSON_SCHEMA = "repro-bench/v2"


def bench_row(
    section: str,
    engine: str,
    scheduler: str,
    I: int,
    T: int,
    wall_s: float,
    speedup: float = 1.0,
    scenario: str = "steady",
    **extra,
) -> dict:
    """One row of the shared bench schema. ``speedup`` is the section's
    headline ratio against its stated baseline (fused vs Python event loop
    for the cohort sections, POTUS vs the reactive baseline's transient
    response for the disruption section); ``scenario`` names the workload/
    disruption case. Extra metric keys ride along untyped."""
    row = dict(section=section, engine=engine, scheduler=scheduler, I=int(I),
               T=int(T), wall_s=round(float(wall_s), 4),
               speedup=round(float(speedup), 2), scenario=scenario)
    row.update(extra)
    return row


def write_bench_json(default_path: str, env_var: str, rows: list[dict]) -> None:
    """Dump ``rows`` under the shared schema (path overridable via
    ``env_var``); silently skips when a section produced no rows."""
    if not rows:
        return
    path = os.environ.get(env_var, default_path)
    with open(path, "w") as f:
        json.dump({"schema": BENCH_JSON_SCHEMA, "rows": rows}, f, indent=2)
        f.write("\n")
    print(f"# wrote {path} ({len(rows)} rows)", file=sys.stderr)


@dataclasses.dataclass
class System:
    name: str
    topo: object
    net: object
    rates: np.ndarray
    placement: np.ndarray


_SYSTEMS: dict = {}


def paper_system(topology: str = "fat-tree", seed: int = 0) -> System:
    """5 apps, depth 3-5, 3-6 components, mu 3-5 (paper §5.1), on a 16-server
    fabric with 2 containers each."""
    key = (topology, seed)
    if key in _SYSTEMS:
        return _SYSTEMS[key]
    rng = np.random.default_rng(seed)
    topo = build_topology(random_apps(rng, n_apps=5), gamma=24.0)
    if topology == "fat-tree":
        server_dist, _ = fat_tree(4)
    else:
        server_dist, _ = jellyfish(np.random.default_rng(seed + 1), 24, 16)
    net = container_costs(topology, server_dist)
    rates = feasible_rates(topo, utilization=0.7)
    placement = t_heron_placement(topo, net, rates, max_per_container=8)
    sys = System(topology, topo, net, rates, placement)
    _SYSTEMS[key] = sys
    return sys


def arrivals_for(sys: System, kind: str, T: int, seed: int = 7) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "poisson":
        return poisson_arrivals(rng, sys.rates, T + 64)
    return trace_synthetic(rng, sys.rates, T + 64)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the variable
    itself, so nothing else is set), else ``<checkout>/.jax_cache`` — a fixed
    path, so that later runs from this checkout find what earlier ones
    compiled. Entry points call this; importing ``repro`` never does."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0
