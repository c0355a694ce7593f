"""Framework-level microbenchmarks: scheduler scaling (§4.2 complexity),
cohort-engine scaling (fused vs Python event loop), strong/weak scaling of
the instance-sharded cohort engine (DESIGN.md §13), kernels, MoE routers,
and the POTUS serving dispatcher."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    EngineSpec,
    SimConfig,
    SweepSpec,
    build_topology,
    container_costs,
    fat_tree,
    feasible_rates,
    instance_mesh,
    make_problem,
    poisson_arrivals,
    potus_schedule,
    run_sweep,
    sharded_schedule,
    simulate,
)
from repro.core.topology import Component

from .common import QUICK, SMOKE, Row, bench_row, timer

# machine-readable cohort-engine perf rows (shared schema, common.bench_row),
# dumped to BENCH_cohort.json by benchmarks/run.py so the trajectory is
# tracked across PRs
COHORT_BENCH: list[dict] = []


def _timed(fn) -> float:
    with timer() as t:  # same clock as the `with timer()` blocks it races
        fn()
    return t.dt


def _fleet(n_replicas: int, parallel_chains: int = 4):
    """A wide serving fleet topology: chains of depth 3 with n_replicas each."""
    apps = []
    for a in range(parallel_chains):
        apps.append([
            Component("src", a, True, parallelism=max(n_replicas // 8, 1), successors=(1,)),
            Component("serve", a, False, parallelism=n_replicas, proc_capacity=4.0,
                      successors=(2,)),
            Component("sink", a, False, parallelism=max(n_replicas // 4, 1),
                      proc_capacity=8.0),
        ])
    return build_topology(apps, gamma=32.0)


def _fleet_exact(I_target: int):
    """Serving fleet with exactly ``I_target`` instances (64 per chain:
    8 spouts -> 48 replicas -> 8 sinks), keeping the per-row candidate set
    (max_succ = 48) flat as the fleet grows."""
    chains = max(I_target // 64, 1)
    apps = []
    for a in range(chains):
        apps.append([
            Component("src", a, True, parallelism=8, successors=(1,)),
            Component("serve", a, False, parallelism=48, proc_capacity=4.0, successors=(2,)),
            Component("sink", a, False, parallelism=8, proc_capacity=8.0),
        ])
    return build_topology(apps, gamma=32.0)


def scheduler_fastpath() -> list[Row]:
    """Bare Algorithm-1 step at fleet scale (DESIGN.md §7): the sort-based
    water-fill fast path vs the reference argmin loop vs the instance-sharded
    path, as one jitted call per scheduling slot. The fused Pallas kernel is
    timed at a small fleet only — off-TPU it runs in interpret mode, which
    measures the interpreter, not the kernel."""
    rows = []
    # 256 stays in the full list so the Pallas-fused row (interpret-capped
    # to small fleets) appears in real runs, not only under SMOKE
    sizes = [128] if SMOKE else [256, 1024, 4096, 16384]
    times: dict[tuple, float] = {}
    for I_target in sizes:
        topo = _fleet_exact(I_target)
        I, C = topo.n_instances, topo.n_components
        server_dist, _ = fat_tree(4)
        net = container_costs(f"fleet{I}", server_dist, containers_per_server=8)
        rng = np.random.default_rng(0)
        placement = rng.integers(0, net.n_containers, I).astype(np.int32)
        prob = make_problem(topo, net, placement)
        succ_mask = topo.adj[topo.inst_comp]  # (I, C) — successor components
        q_in = jnp.asarray(np.round(rng.uniform(0, 12, I)).astype(np.float32))
        q_out = jnp.asarray(
            (np.round(rng.uniform(0, 12, (I, C))) * succ_mask).astype(np.float32)
        )
        must = jnp.zeros((I, C), jnp.float32)
        U = jnp.asarray(net.U)
        mesh = instance_mesh(I)

        paths: list[tuple[str, object]] = [
            ("sort", lambda: potus_schedule(prob, U, q_in, q_out, must, 2.0, 1.0)),
            ("loop", lambda: potus_schedule(prob, U, q_in, q_out, must, 2.0, 1.0,
                                            method="loop")),
            ("sharded", lambda: sharded_schedule(mesh, prob, U, q_in, q_out, must,
                                                 2.0, 1.0)),
        ]
        if I <= 256:
            paths.append(
                ("pallas-fused-interp",
                 lambda: potus_schedule(prob, U, q_in, q_out, must, 2.0, 1.0,
                                        use_pallas=True))
            )
        for name, fn in paths:
            jax.block_until_ready(fn())  # compile
            n = 1 if I >= 16384 else 3
            with timer() as t:
                for _ in range(n):
                    jax.block_until_ready(fn())
            dt = t.dt / n
            times[(name, I)] = dt
            rows.append(Row(f"scheduler_scale/{name}/I{I}", dt * 1e6,
                            f"instances={I};slots_per_s={1/dt:.2f}"))
        sort_t, loop_t = times[("sort", I)], times[("loop", I)]
        rows.append(Row(f"scheduler_scale/speedup/I{I}", sort_t * 1e6,
                        f"sort_us={sort_t*1e6:.0f};loop_us={loop_t*1e6:.0f};"
                        f"speedup={loop_t/sort_t:.1f}x"))
    return rows


def _cohort_fleet(I_target: int):
    """4 serving chains (src -> serve -> sink, C = 12) with parallelism scaled
    so ``n_instances == I_target`` — the response-time analogue of
    ``_fleet_exact`` (spouts and terminal bolts included so the cohort
    engines have streams to measure)."""
    chains = 4
    per = I_target // chains
    src = max(per // 8, 1)
    sink = max(per // 8, 1)
    apps = []
    for a in range(chains):
        apps.append([
            Component("src", a, True, parallelism=src, successors=(1,)),
            Component("serve", a, False, parallelism=per - src - sink,
                      proc_capacity=4.0, successors=(2,)),
            Component("sink", a, False, parallelism=sink, proc_capacity=8.0),
        ])
    return build_topology(apps, gamma=32.0)


def cohort_scale() -> list[Row]:
    """Fused cohort engine vs the Python event loop at fleet scale: identical
    response-time semantics (tests/test_cohort_fused.py), wall time per
    T-slot simulation, for the paper's two headline schedulers. Shuffle
    isolates the *engine* cost (its decision is trivial, and its dense
    dispatch is the Python loop's worst case); POTUS rows share the jitted
    Algorithm-1 call between both engines, so they bound the win by the
    scheduler's own cost at that scale. The fused rows report warm
    (post-compile) time — the compile is paid once per (topology, T) and
    amortizes over every scenario of a grid — with the one-time compile
    seconds in ``derived``. Compact schedulers (potus/shuffle/jsq) run the
    one-dispatch slot step (DESIGN.md §12) — no dense (I, I) dispatch — so
    POTUS's fused wall time is asserted to stay within 2x of shuffle's at
    fleet scale (ci.yml bench smoke, I=16384; the python-baseline speedups
    are not comparable across schedulers because the event loop's dense
    shuffle dispatch is its own worst case)."""
    rows = []
    sizes = [64, 16384] if SMOKE else [64, 256, 1024, 4096, 16384]
    T = 24 if SMOKE else 128
    age_cap = 32
    for I_target in sizes:
        topo = _cohort_fleet(I_target)
        I = topo.n_instances
        server_dist, _ = fat_tree(4)
        net = container_costs(f"cohort-fleet-{I}", server_dist, containers_per_server=8)
        rng = np.random.default_rng(0)
        placement = rng.integers(0, net.n_containers, I).astype(np.int32)
        rates = feasible_rates(topo, utilization=0.85)
        arr = poisson_arrivals(rng, rates, T + 8)
        # at fleet scale the Python event loop is measured on a truncated
        # horizon and extrapolated linearly (its per-slot cost is
        # T-independent); the fused engine always runs the full horizon
        T_py = T if I <= 1024 else (1 if SMOKE else max(T // 16, 8))
        for sched in ("shuffle", "potus"):
            with timer() as t_py:
                py = simulate(EngineSpec(
                    topo=topo, net=net, placement=placement, arrivals=arr,
                    T=T_py, engine="cohort", scheduler=sched, V=2.0, window=4))
            t_py_full = t_py.dt * (T / T_py)
            fspec = EngineSpec(
                topo=topo, net=net, placement=placement, arrivals=arr, T=T,
                engine="cohort-fused", scheduler=sched, V=2.0, window=4,
                age_cap=age_cap)
            with timer() as t_compile:  # first call: trace + compile + run
                simulate(fspec)
            out: dict = {}

            def fused_once():
                out["res"] = simulate(fspec)

            t_fused = min(_timed(fused_once) for _ in range(2))
            fused = out["res"]
            speedup = t_py_full / t_fused
            if T_py == T:
                db = abs(py.avg_backlog - fused.avg_backlog) / max(py.avg_backlog, 1e-9)
                agree = f"backlog_agree={1 - db:.4f}"
            else:
                agree = f"python_T={T_py};extrapolated=True"
            for engine, dt in (("python", t_py_full), ("fused", t_fused)):
                rows.append(Row(f"cohort_scale/{engine}/{sched}/I{I}", dt / T * 1e6,
                                f"instances={I};T={T};wall_s={dt:.3f}"))
                COHORT_BENCH.append(bench_row(
                    "cohort_scale", engine, sched, I, T, dt,
                    speedup=speedup if engine == "fused" else 1.0,
                    python_T=T_py, extrapolated=T_py != T,
                ))
            rows.append(Row(f"cohort_scale/speedup/{sched}/I{I}", t_fused / T * 1e6,
                            f"python_s={t_py_full:.3f};fused_s={t_fused:.3f};"
                            f"compile_s={t_compile.dt - t_fused:.2f};"
                            f"speedup={speedup:.1f}x;{agree}"))
    rows.extend(_cohort_grid_row())
    return rows


def _cohort_grid_row() -> list[Row]:
    """Fig. 6ab-shaped response grid: one vmapped cohort-fused compile vs the
    sequential Python event loop over the same scenarios."""
    from repro.core.prediction import all_true_negative

    topo = _cohort_fleet(64)
    I = topo.n_instances
    server_dist, _ = fat_tree(4)
    net = container_costs("cohort-grid", server_dist, containers_per_server=8)
    rng = np.random.default_rng(1)
    placement = rng.integers(0, net.n_containers, I).astype(np.int32)
    rates = feasible_rates(topo, utilization=0.7)
    T = 24 if SMOKE else 48
    arr = poisson_arrivals(rng, rates, T + 8)
    amap = {"perfect": arr, "none": (arr, all_true_negative(arr))}
    spec = SweepSpec(V=(1.0, 2.0, 5.0, 10.0), window=1, arrival=("perfect", "none"))
    opts = {"age_cap": 32}

    run_sweep(topo, net, placement, amap, T, spec, engine="cohort-fused",
              engine_opts=opts)  # compile
    t_fused = _timed(lambda: run_sweep(topo, net, placement, amap, T, spec,
                                       engine="cohort-fused", engine_opts=opts))
    t_py = _timed(lambda: run_sweep(topo, net, placement, amap, T, spec,
                                    engine="cohort"))
    n = spec.n_scenarios
    COHORT_BENCH.append(bench_row("cohort_grid", "fused", "potus", I, T, t_fused,
                                  speedup=t_py / t_fused))
    COHORT_BENCH.append(bench_row("cohort_grid", "python", "potus", I, T, t_py))
    return [Row("cohort_scale/grid", t_fused / (n * T) * 1e6,
                f"scenarios={n};batches=1;fused_s={t_fused:.3f};"
                f"python_s={t_py:.3f};speedup={t_py / t_fused:.1f}x")]


def _sharded_probe(I_target: int, T: int, age_cap: int, n_devices: int,
                   sharded: bool, reps: int = 2) -> dict:
    """One cohort-fused measurement in this process: warm wall seconds (min
    over ``reps`` post-compile runs) plus the per-slot cross-device payload
    from ``cohort_slot_payload_floats``. Sharded runs go over
    ``instance_mesh(I, devices=jax.devices()[:n_devices])``, so one process
    drives every shard count the host's devices allow."""
    from repro.core.cohort_fused import _run_cohort_fused_impl
    from repro.core.sharded import cohort_slot_payload_floats

    topo = _cohort_fleet(I_target)
    I = topo.n_instances
    server_dist, _ = fat_tree(4)
    net = container_costs(f"cohort-fleet-{I}", server_dist, containers_per_server=8)
    rng = np.random.default_rng(0)
    placement = rng.integers(0, net.n_containers, I).astype(np.int32)
    arr = poisson_arrivals(rng, feasible_rates(topo, utilization=0.85), T + 8)
    mesh = instance_mesh(I, devices=jax.devices()[:n_devices]) if sharded else None
    cfg = SimConfig(scheduler="potus", V=2.0, window=0)

    def run():
        return _run_cohort_fused_impl(topo, net, placement, arr, None, T, cfg,
                                      age_cap=age_cap, mesh=mesh)

    compile_s = _timed(run)  # trace + compile + first run
    res = None
    times = []
    for _ in range(reps):
        with timer() as t:
            res = run()
        times.append(t.dt)
    n_shards = mesh.shape["i"] if sharded else 1
    return dict(
        I=int(I), devices=n_shards, n_shards=int(n_shards),
        wall_s=min(times), compile_s=compile_s,
        payload_floats=int(cohort_slot_payload_floats(
            I, topo.n_components, net.n_containers, age_cap + 1, n_shards)),
        C=int(topo.n_components), K=int(net.n_containers),
        avg_backlog=float(np.mean(np.asarray(res.backlog))))


def cohort_sharded_scale() -> list[Row]:
    """Strong/weak scaling of the instance-sharded one-dispatch engine
    (DESIGN.md §13) over this process's devices; shard counts past
    ``jax.device_count()`` are skipped.

    Strong tier: fixed fleet (I=16384), 1 -> 4 shards, plus a dense
    (non-``shard_map``) baseline; ci.yml's bench smoke asserts the best
    sharded wall time stays within 10% of dense — at one shard every
    collective is the identity, so sharding must cost ~nothing. Weak tier:
    fixed instances *per shard*, the fleet growing with the mesh up to
    I=131072 at 4 shards.

    Every row reports the per-slot cross-device payload (floats) from
    ``cohort_slot_payload_floats`` — the O(I·C)-bounded collective traffic
    argued in §13.2 (atot and K are horizon/network constants, so the
    I·atot landing term dominates and payload/IC stays bounded). Wall times
    are those of whatever backend runs the process; only a TPU run is a
    device number, and real distribution is checked by the 4-device
    differential in tests/test_distributed.py and ``chip_smoke.py --chips 4``.
    """
    rows: list[Row] = []
    age_cap = 4

    # --- strong scaling: fixed fleet, growing mesh ---------------------------
    T_s = 4 if SMOKE else 16
    I_strong = 16384
    n_dev = jax.device_count()
    strong_shards = [n for n in ((1, 4) if SMOKE else (1, 2, 4)) if n <= n_dev]
    dense = _sharded_probe(I_strong, T_s, age_cap, 1, sharded=False)
    rows.append(Row(f"cohort_sharded/strong/dense/I{dense['I']}",
                    dense["wall_s"] / T_s * 1e6,
                    f"instances={dense['I']};T={T_s};"
                    f"wall_s={dense['wall_s']:.3f}"))
    COHORT_BENCH.append(bench_row(
        "cohort_sharded_strong", "dense", "potus", dense["I"], T_s,
        dense["wall_s"], n_shards=1, devices=1, payload_floats=0,
        IC=dense["I"] * dense["C"]))
    for n in strong_shards:
        p = _sharded_probe(I_strong, T_s, age_cap, n, sharded=True)
        speedup = dense["wall_s"] / p["wall_s"]
        rows.append(Row(
            f"cohort_sharded/strong/shards{p['n_shards']}/I{p['I']}",
            p["wall_s"] / T_s * 1e6,
            f"instances={p['I']};T={T_s};wall_s={p['wall_s']:.3f};"
            f"vs_dense={speedup:.2f}x;payload_floats={p['payload_floats']}"))
        COHORT_BENCH.append(bench_row(
            "cohort_sharded_strong", "sharded", "potus", p["I"], T_s,
            p["wall_s"], speedup=speedup, n_shards=p["n_shards"],
            devices=p["devices"], payload_floats=p["payload_floats"],
            IC=p["I"] * p["C"]))

    # --- weak scaling: fixed instances per shard -----------------------------
    T_w = 2 if SMOKE else 6
    per_shard = 2048 if SMOKE else 32768
    weak_shards = [n for n in ((1, 4) if SMOKE else (1, 2, 4)) if n <= n_dev]
    base_wall = None
    for n in weak_shards:
        p = _sharded_probe(per_shard * n, T_w, age_cap, n, sharded=True)
        if base_wall is None:
            base_wall = p["wall_s"]
        eff = base_wall / p["wall_s"]
        rows.append(Row(
            f"cohort_sharded/weak/shards{p['n_shards']}/I{p['I']}",
            p["wall_s"] / T_w * 1e6,
            f"instances={p['I']};per_shard={per_shard};T={T_w};"
            f"wall_s={p['wall_s']:.3f};weak_eff={eff:.2f};"
            f"payload_floats={p['payload_floats']}"))
        COHORT_BENCH.append(bench_row(
            "cohort_sharded_weak", "sharded", "potus", p["I"], T_w,
            p["wall_s"], speedup=eff, n_shards=p["n_shards"],
            devices=p["devices"], per_shard_I=per_shard,
            payload_floats=p["payload_floats"], IC=p["I"] * p["C"]))
    return rows


def scheduler_scale() -> list[Row]:
    """End-to-end scheduling throughput vs fleet size (jit XLA path vs
    Pallas price), measured through the batched sweep engine: a V-grid of
    scenarios runs as one vmapped scan, and the reported figure is sweep
    wall time per scheduling decision (scenario x slot) — including the
    engine's setup/dispatch overhead, which is what a sweep user pays. At
    small fleets that overhead is a visible fraction of the decision cost;
    at large fleets the scheduler compute dominates."""
    rows = []
    sizes = [8] if SMOKE else ([8, 32, 128] if QUICK else [8, 32, 128, 256, 512])
    for n in sizes:
        topo = _fleet(n)
        I = topo.n_instances
        server_dist, _ = fat_tree(4)
        net = container_costs(f"fleet-{n}", server_dist, containers_per_server=8)
        rng = np.random.default_rng(0)
        placement = rng.integers(0, net.n_containers, I).astype(np.int32)
        rates = feasible_rates(topo, utilization=0.7)

        # decisions get costly at fleet scale; shrink the slot count
        # quadratically with size so QUICK stays snappy while small fleets
        # still run enough decisions to amortize per-sweep setup overhead
        # (Pallas runs in slow interpret mode off-TPU)
        shrink = max(n // 8, 1) ** 2
        T_xla = max(4, (120 if QUICK else 400) // shrink)
        T_pal = max(2, (4 if QUICK else 10) // shrink)
        for path, use_pallas, T, Vs in (
            ("xla", False, T_xla, (1.0, 2.0, 4.0, 8.0, 12.0, 16.0, 24.0, 32.0)),
            ("pallas-interp", True, T_pal, (2.0, 8.0)),
        ):
            arr = poisson_arrivals(rng, rates, T + 4)
            spec = SweepSpec(V=Vs, use_pallas=use_pallas)
            run_sweep(topo, net, placement, arr, T, spec)  # compile
            t0 = time.perf_counter()
            sw = run_sweep(topo, net, placement, arr, T, spec)
            dt = (time.perf_counter() - t0) / (len(sw) * T)
            # 'scheduler_sweep/' (not the old 'scheduler/'): the metric is
            # end-to-end sweep time per decision, not bare call latency
            rows.append(Row(f"scheduler_sweep/{path}/I{I}", dt * 1e6,
                            f"instances={I};decisions_per_s={1/dt:.0f}"))
    return rows


def kernels_micro() -> list[Row]:
    """Interpret-mode kernel calls vs jnp references (correctness-weighted
    latency; real perf numbers require TPU hardware)."""
    from repro.kernels.flash_attention import flash_attention_call
    from repro.kernels import ref as kref

    rows = []
    B, Hq, Hkv, S, D = 1, 8, 2, 512, 64
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(keys[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(keys[2], (B, Hkv, S, D), jnp.float32)

    for name, fn in (
        ("flash_attention/interp", lambda: flash_attention_call(q, k, v)),
        ("flash_attention/xla_ref", lambda: kref.flash_attention_reference(q, k, v)),
    ):
        out = fn()
        jax.block_until_ready(out)
        n = 3 if QUICK else 10
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn())
        dt = (time.perf_counter() - t0) / n
        flops = 4 * B * Hq * S * S * D
        rows.append(Row(f"kernel/{name}", dt * 1e6, f"gflops_rate={flops/dt/1e9:.2f}"))
    return rows


def moe_router_bench() -> list[Row]:
    """Beyond-paper: POTUS (Lyapunov virtual-queue) router vs plain top-k."""
    from repro.configs import get_config
    from repro.models.common import init_params
    from repro.models.moe import init_router_state, moe_ffn, moe_template

    cfg = get_config("granite_moe_1b").reduced().with_(
        n_experts=16, top_k=2, capacity_factor=1.25, d_model=128
    )
    tmpl = moe_template(cfg)
    p = init_params(jax.random.PRNGKey(0), tmpl, jnp.float32)
    rng = np.random.default_rng(0)
    # skewed tokens -> hot experts
    base = rng.standard_normal((1, 1, cfg.d_model)).astype(np.float32)
    x = jnp.asarray(np.concatenate([
        np.repeat(base, 192, axis=1) + 0.05 * rng.standard_normal((1, 192, cfg.d_model)),
        rng.standard_normal((1, 64, cfg.d_model)).astype(np.float32),
    ], axis=1).astype(np.float32))

    rows = []
    for router in ("topk", "potus"):
        c = cfg.with_(router=router)
        rs = init_router_state(c)
        imb, drop = [], []
        with timer() as t:
            for _ in range(10):
                _, aux = moe_ffn(p, x, c, rs)
                if router == "potus":
                    rs = aux["router_state"]
                load = np.asarray(aux["load"])
                imb.append(load.max() / max(load.mean(), 1e-9))
                drop.append(float(aux["dropped_frac"]))
        rows.append(Row(f"moe_router/{router}", t.dt / 10 * 1e6,
                        f"max_over_mean_load={np.mean(imb[3:]):.2f};dropped={np.mean(drop[3:]):.3f}"))
    return rows


def dispatcher_bench() -> list[Row]:
    """POTUS vs Shuffle request routing across heterogeneous replicas."""
    from repro.serving.dispatcher import DispatcherConfig, PotusDispatcher

    rng = np.random.default_rng(0)
    F, R = 2, 8
    hosts = np.arange(R) % 4
    host_costs = (np.abs(np.arange(4)[:, None] - np.arange(4)[None, :]) * 2.0).astype(np.float32)
    rates = np.array([8, 8, 4, 4, 2, 2, 1, 1], float)
    T = 200 if QUICK else 1000
    arrivals = rng.poisson(7.0, size=(T, F)).astype(float)

    rows = []
    for policy in ("potus", "shuffle"):
        disp = PotusDispatcher(F, hosts, np.array([0, 2]), host_costs, rates,
                               DispatcherConfig(V=1.0, beta=1.0, gamma=64.0))
        backlog = np.zeros(R)
        tot_b, tot_cost = 0.0, 0.0
        with timer() as t:
            for ts in range(T):
                if policy == "potus":
                    assign = disp.route(arrivals[ts], backlog)
                    inflow = assign.sum(axis=0)
                    cost = float((assign * host_costs[np.ix_(np.array([0, 2]), hosts)]).sum())
                else:
                    inflow = np.bincount(
                        rng.integers(0, R, int(arrivals[ts].sum())), minlength=R
                    ).astype(float)
                    fhost = np.array([0, 2])[rng.integers(0, F, int(arrivals[ts].sum()))]
                    cost = 0.0  # computed coarsely below
                    cost = float(host_costs[fhost, hosts[rng.integers(0, R, len(fhost))]].sum())
                backlog = np.maximum(backlog + inflow - rates, 0.0)
                tot_b += backlog.sum()
                tot_cost += cost
        rows.append(Row(f"dispatcher/{policy}", t.dt / T * 1e6,
                        f"avg_backlog={tot_b/T:.1f};avg_cost={tot_cost/T:.1f}"))
    return rows
