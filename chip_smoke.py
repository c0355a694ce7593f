"""Smoke run of the fused cohort engine and the served path on one TPU.

    python chip_smoke.py             # phases (a)-(c) on one chip
    python chip_smoke.py --chips 4   # the instance-sharded engine on four chips

Every phase drives the system through the entry points a user calls
(``repro.core.simulate(EngineSpec(...))``, ``PotusDispatcher`` +
``ReplicaFleet``) and checks what comes out:

(a) fleet scale (``_cohort_fleet(16384)``: 4 src->serve->sink chains, C=12,
    ``fat_tree(4)`` with 8 containers per server, K=128), Poisson arrivals at
    utilization 0.85, POTUS and shuffle on the compact XLA step. The mass
    ledger closes on the chip: completed mass plus what the last slot still
    holds (its backlog sample, the mass in transit and the admission backlog,
    read from the in-scan metric streams of a second, metrics-on run) equals
    the injected mass. The same spec runs once more on the host CPU backend;
    shuffle agrees to f32 rounding, POTUS on its means within the near-tie
    floor of the paper-grid tier.
(b) the same fleet under ``k_failures`` (the caps fold of the compact step).
(c) the served path: ``PotusDispatcher`` + ``ReplicaFleet`` of R=64
    ``SimReplica``s; tokens dispatched = served + backlog + in flight.

With ``--chips 4`` only the sharded engine runs: ``sharded=True`` at
I=131072 over the four chips, against the same spec unsharded on one chip.

Each phase prints one JSON line (wall and XLA compile seconds, values,
checks); the last line is ``{"ok": ..., "device": {...}}``. The script exits
non-zero, printing no result, when JAX finds no TPU, and exits non-zero on
any failed check. It keeps JAX's compile cache at ``JAX_COMPILATION_CACHE_DIR``
or else at ``<checkout>/.jax_cache``. Chip timings are smoke readings, not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

I_FLEET = 16384  # phases (a), (b)
I_SHARDED = 131072  # --chips 4: the weak-scaling tier's fleet
T, W, AGE_CAP, V = 128, 4, 32, 2.0
# arrivals stop DRAIN > W slots before the horizon, so the last slot's
# lookahead window is empty and no tuple from past the horizon was pre-shipped
DRAIN = 8
LEDGER_STREAMS = ("backlog", "transit", "held", "saturation")
WARMUP = 16
R_SERVE, T_SERVE = 64, 64  # phase (c)

LEDGER_RTOL = 1e-4  # f32 mass ledger over T slots
SHUFFLE_RTOL = 1e-4  # chip vs CPU, per slot: f32 rounding only
# POTUS chip vs CPU (and sharded vs one chip): 1-ulp differences flip price
# near-ties and the trajectories then diverge, so only means are compared,
# at the statistical floor of tests/test_cohort_fused.py's paper-grid tier
POTUS_REL = {"avg_response": 0.10, "p95_response": 0.25, "avg_backlog": 0.10,
             "avg_cost": 0.02}

_compile_s = [0.0]


def _on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += secs  # XLA compile, or its load from the cache


class Phase:
    """Times one phase and its runs and collects its checks; on exit it
    prints its line. ``tpu_run_s`` is the host wall time of the runs on the
    chip less their XLA compile seconds: a smoke reading of the device path
    (host preparation included), not a benchmark number."""

    def __init__(self, name):
        self.name, self.checks, self.values, self.runs = name, {}, {}, {}

    def __enter__(self):
        self.c0, self.t0 = _compile_s[0], time.perf_counter()
        return self

    def run(self, key, fn, on_chip=True):
        c0, t0 = _compile_s[0], time.perf_counter()
        out = fn()
        self.runs[key] = {"wall_s": time.perf_counter() - t0,
                          "compile_s": _compile_s[0] - c0, "on_chip": on_chip}
        return out

    def check(self, name, ok):
        self.checks[name] = bool(ok)

    def __exit__(self, *exc):
        chip = [r for r in self.runs.values() if r["on_chip"]]
        line = {"phase": self.name, "wall_s": time.perf_counter() - self.t0,
                "compile_s": _compile_s[0] - self.c0,
                "tpu_run_s": sum(r["wall_s"] - r["compile_s"] for r in chip),
                "checks": self.checks, "values": self.values, "runs": self.runs}
        if exc[0] is not None:
            line["error"] = "".join(traceback.format_exception(*exc))[-4000:]
        print(json.dumps(line, default=float), flush=True)
        return False

    @property
    def ok(self):
        return all(self.checks.values())


def _close(a, b, rel):
    return math.isclose(a, b, rel_tol=rel) or (math.isnan(a) and math.isnan(b))


# ---------------------------------------------------------------------------
# systems
# ---------------------------------------------------------------------------

def fleet(I, seed=0, T=T, utilization=0.85):
    """``_cohort_fleet(I)`` on ``fat_tree(4)`` with 8 containers per server,
    a seeded placement, Poisson arrivals that stop ``DRAIN`` slots early."""
    import numpy as np

    from benchmarks.systems_bench import _cohort_fleet
    from repro.core import container_costs, fat_tree, feasible_rates, poisson_arrivals

    topo = _cohort_fleet(I)
    sd, _ = fat_tree(4)
    net = container_costs(f"cohort-fleet-{I}", sd, containers_per_server=8)
    rng = np.random.default_rng(seed)
    placement = rng.integers(0, net.n_containers, topo.n_instances).astype(np.int32)
    arr = poisson_arrivals(rng, feasible_rates(topo, utilization), T + W + 1)
    arr[T - DRAIN:] = 0.0
    return topo, net, placement, arr


def injected(topo, arr, T=T):
    mask = topo.adj[topo.inst_comp] & topo.comp_is_spout[topo.inst_comp][:, None]
    return float((arr[:T] * mask[None]).sum(dtype="float64"))


def spec(system, scheduler, T=T, **kw):
    from repro.core import EngineSpec

    topo, net, placement, arr = system
    return EngineSpec(topo=topo, net=net, placement=placement, arrivals=arr, T=T,
                      engine="cohort-fused", scheduler=scheduler, V=V, window=W,
                      age_cap=AGE_CAP, warmup=WARMUP, **kw)


def summary(res):
    import numpy as np

    return {k: float(getattr(res, k)) for k in
            ("avg_response", "p95_response", "avg_backlog", "avg_cost",
             "completed_mass", "saturated_frac")} | {
        "final_backlog": float(np.asarray(res.backlog)[-1])}


def check_result(ph, tag, res):
    """Finite outputs."""
    import numpy as np

    s = summary(res)
    ph.values[tag] = s
    ph.check(f"{tag}:finite", np.isfinite(res.backlog).all()
             and np.isfinite(res.comm_cost).all()
             and all(math.isfinite(s[k]) for k in ("avg_response", "completed_mass")))


def check_ledger(ph, tag, s, res, inj):
    """Run ``s`` again with the ledger's metric streams on: the trajectory
    must not move, and what completed plus what the last slot still holds
    must equal the injected mass (beta = 1, so the backlog sample counts
    each queued tuple once)."""
    import dataclasses

    import numpy as np

    from repro.core import simulate

    m = ph.run(f"{tag}+metrics", lambda: simulate(dataclasses.replace(s, metrics=LEDGER_STREAMS)))
    st = m.metrics.streams
    held = float(st["backlog"][-1, 0]) + float(st["transit"][-2, 0]) + float(st["held"][-2, 0])
    done = float(m.completed_mass) - float(st["saturation"][-1, 1])  # through slot T-2
    ph.values[f"{tag}:ledger"] = {"completed_before_last": done, "held_at_last": held,
                                  "injected": inj}
    ph.check(f"{tag}:metrics_on_same_run", np.array_equal(m.backlog, res.backlog))
    ph.check(f"{tag}:ledger", _close(done + held, inj, LEDGER_RTOL))


def compare(ph, tag, scheduler, a, b):
    """``a`` vs ``b`` of the same spec on two devices (or layouts)."""
    import numpy as np

    if scheduler == "shuffle":
        ph.check(f"{tag}:backlog", np.allclose(a.backlog, b.backlog, rtol=SHUFFLE_RTOL))
        ph.check(f"{tag}:cost", np.allclose(a.comm_cost, b.comm_cost, rtol=SHUFFLE_RTOL))
        ph.check(f"{tag}:avg_response", _close(a.avg_response, b.avg_response,
                                               SHUFFLE_RTOL))
    else:
        for k, rel in POTUS_REL.items():
            ph.check(f"{tag}:{k}", _close(getattr(a, k), getattr(b, k), rel))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_fleet(name, I, events_fn=None):
    """(a)/(b): POTUS and shuffle at fleet scale on the chip, each once more
    on the host CPU backend."""
    import jax

    from repro.core import simulate

    with Phase(name) as ph:
        system = fleet(I)
        inj = injected(system[0], system[3])
        ph.values["I"], ph.values["injected"] = system[0].n_instances, inj
        events = events_fn(system) if events_fn else None
        for scheduler in ("potus", "shuffle"):
            s = spec(system, scheduler, events=events)
            chip = ph.run(f"{scheduler}/tpu", lambda: simulate(s))
            check_result(ph, f"{scheduler}/tpu", chip)
            check_ledger(ph, f"{scheduler}/tpu", s, chip, inj)
            with jax.default_device(jax.devices("cpu")[0]):
                host = ph.run(f"{scheduler}/cpu", lambda: simulate(s), on_chip=False)
            check_result(ph, f"{scheduler}/cpu", host)
            compare(ph, f"{scheduler}/tpu~cpu", scheduler, chip, host)
    return ph.ok


def kfail_trace(system):
    """k = I/64 bolt instances down for 16 slots from slot 32."""
    import numpy as np

    from repro.core import k_failures

    topo, _, placement, _ = system
    scn = k_failures(topo, topo.n_instances // 64, start=32, duration=16,
                     rng=np.random.default_rng(1))
    return scn.compile(topo, T, placement)


def phase_served(R=R_SERVE, T_serve=T_SERVE, seed=7):
    """(c): the served path; every dispatched token is served, queued or in
    flight."""
    import numpy as np

    from benchmarks.serving_fleet import MEAN_TOKENS, SLOW_TOK, _fleet_setup
    from repro.serving.dispatcher import integral_assign
    from repro.serving.fleet import FleetRequest

    def drive(disp, fleet_, rng):
        lam = 0.75 * SLOW_TOK * R / MEAN_TOKENS / disp.F
        queues = [[] for _ in range(disp.F)]
        out = dict(dispatched=0.0, last=0.0, done=[], rid=0, finite=True)
        for t in range(T_serve):
            arrivals = rng.poisson(lam, disp.F).astype(np.float32)
            for f in range(disp.F):
                for _ in range(int(arrivals[f])):
                    queues[f].append(FleetRequest(out["rid"], float(rng.integers(2, 7)), t,
                                                  frontend=f))
                    out["rid"] += 1
            fluid = disp.route(arrivals, fleet_.backlog_tokens)
            out["finite"] &= bool(np.isfinite(fluid).all() and (fluid >= -1e-6).all())
            assign = integral_assign(fluid, rng=rng)
            out["last"] = 0.0  # tokens dispatched this slot: in flight after it
            for f in range(disp.F):
                for r in range(R):
                    for _ in range(int(assign[f, r])):
                        if not queues[f]:
                            break
                        req = queues[f].pop(0)
                        fleet_.dispatch(r, req)
                        out["last"] += req.tokens
            out["dispatched"] += out["last"]
            out["done"].extend(fleet_.step(t))
        return out

    with Phase("c:served") as ph:
        disp, fleet_ = _fleet_setup(R, "potus")
        o = ph.run("serve", lambda: drive(disp, fleet_, np.random.default_rng(seed)))
        served = fleet_.tokens_served
        backlog = float(fleet_.backlog_tokens.sum())
        ph.values.update(R=R, slots=T_serve, requests=o["rid"], completed=len(o["done"]),
                         dispatched_tokens=o["dispatched"], served_tokens=served,
                         backlog_tokens=backlog, in_flight_tokens=o["last"])
        ph.check("route:finite", o["finite"])
        ph.check("tokens:conserved", o["dispatched"] == served + backlog + o["last"])
        ph.check("requests:completed", len(o["done"]) > 0)
    return ph.ok


def phase_sharded(I=I_SHARDED, T_sh=96):
    """--chips 4: ``sharded=True`` over every chip vs the spec unsharded on
    one chip; each chip holds I/n rows of the queue state."""
    import numpy as np

    import jax

    from repro.core import cohort_fused as cf
    from repro.core import simulate

    with Phase("sharded") as ph:
        devs = jax.devices()
        system = fleet(I, T=T_sh)
        inj = injected(system[0], system[3], T_sh)
        I_n = system[0].n_instances
        ph.values.update(I=I_n, devices=len(devs))
        layout = {}
        real = cf._scan_cohort_sharded

        def spy(mesh, prob, states, **kw):  # record where the carry lives
            out = real(mesh, prob, states, **kw)
            layout["rows"] = [sorted((s.device.id, s.data.shape[1])
                                     for s in x.addressable_shards) for x in out[0][:5]]
            layout["bytes"] = {d.id: (d.memory_stats() or {}).get("bytes_in_use", 0)
                               for d in devs}
            return out

        cf._scan_cohort_sharded = spy
        try:
            s_shard = spec(system, "potus", T=T_sh, sharded=True)
            shard = ph.run("sharded", lambda: simulate(s_shard))
        finally:
            cf._scan_cohort_sharded = real
        one = ph.run("one-chip", lambda: simulate(spec(system, "potus", T=T_sh)))
        check_result(ph, "sharded", shard)
        check_ledger(ph, "sharded", s_shard, shard, inj)
        check_result(ph, "one-chip", one)
        compare(ph, "sharded~one-chip", "potus", shard, one)
        ph.check("completed_mass", _close(shard.completed_mass, one.completed_mass, 1e-2))
        want = sorted((d.id, I_n // len(devs)) for d in devs)
        ph.values["shard_rows"] = layout.get("rows")
        ph.values["bytes_in_use"] = layout.get("bytes")
        ph.check("state:rows_per_chip", bool(layout)
                 and all(r == want for r in layout["rows"]))
        q_bytes = 4 * I_n // len(devs) * (AGE_CAP + W + 1)  # one (I/n, Atot) f32 array
        ph.check("state:on_every_chip", bool(layout) and all(
            b >= q_bytes for b in layout["bytes"].values()))
        ph.values["backlog_max_rel_diff_first_8"] = float(np.max(
            np.abs(shard.backlog[:8] - one.backlog[:8]) / np.maximum(one.backlog[:8], 1.0)))
    return ph.ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded engine across four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no repro checkout around {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend {devs[0].platform!r}); "
              "this check runs only on the chip", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    from benchmarks.common import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if args.chips == 4:
        phases = [phase_sharded]
    else:
        phases = [lambda: phase_fleet("a:fleet", I_FLEET),
                  lambda: phase_fleet("b:k-failures", I_FLEET, kfail_trace),
                  phase_served]
    ok = True
    for run in phases:
        try:
            ok &= run()
        except Exception:  # noqa: BLE001 — the phase line carries the traceback
            ok = False
    print(json.dumps({"ok": ok, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
