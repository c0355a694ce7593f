"""Benchmark driver — one section per paper table/figure plus framework
microbenchmarks. Prints ``name,us_per_call,derived`` CSV; the cohort-engine
scaling rows and the disruption-transient rows are additionally dumped as
machine-readable JSON under one shared schema (``benchmarks/common.py``) to
``BENCH_cohort.json`` / ``BENCH_disruption.json`` / ``BENCH_serving.json``
(override the paths with REPRO_BENCH_COHORT_JSON / REPRO_BENCH_DISRUPTION_JSON
/ REPRO_BENCH_SERVING_JSON) so the perf trajectory is tracked across PRs.

Set REPRO_BENCH_FULL=1 for the full (paper-scale) sweeps. ``--profile DIR``
runs the sections under ``jax.profiler`` with the program's spans on
(``repro.obs.trace``), writing the XLA profile and a Perfetto-loadable
``perfetto_trace.json.gz`` under ``DIR/plugins/profile/<run>/`` (DESIGN.md
§14.2). JAX's persistent compile cache is on
(``benchmarks.common.enable_compile_cache``). A section that raises prints
an ``ERROR`` row and the driver goes on, then exits 1.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    from . import disruption, paper_figures, serving_fleet, systems_bench, workload
    from .common import enable_compile_cache, write_bench_json

    sections = [
        ("workload", workload.workload_bench),
        ("fig4", paper_figures.fig4_response_vs_w),
        ("fig5", paper_figures.fig5_backlog_and_cost_vs_v),
        ("fig6ab", paper_figures.fig6ab_predictors),
        ("fig6c", paper_figures.fig6c_misprediction_extremes),
        ("disruption", disruption.disruption_bench),
        ("figD", disruption.figd_disruption),
        ("cohort_scale", systems_bench.cohort_scale),
        ("cohort_sharded", systems_bench.cohort_sharded_scale),
        ("scheduler_scale", systems_bench.scheduler_fastpath),
        ("scheduler_sweep", systems_bench.scheduler_scale),
        ("kernels", systems_bench.kernels_micro),
        ("moe_router", systems_bench.moe_router_bench),
        ("dispatcher", systems_bench.dispatcher_bench),
        ("serving_fleet", serving_fleet.serving_fleet_bench),
    ]
    ap = argparse.ArgumentParser(description="benchmark driver")
    ap.add_argument("only", nargs="?", default=None,
                    help="substring filter on section names")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="write a jax.profiler trace with the program's spans to DIR "
                         "(DESIGN.md §14)")
    args = ap.parse_args()
    only = args.only
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)

    profile_ctx = None
    if args.profile:
        import os

        import jax

        from repro.obs.trace import enable_tracing

        os.makedirs(args.profile, exist_ok=True)
        enable_tracing()
        profile_ctx = jax.profiler.trace(args.profile, create_perfetto_trace=True)
        profile_ctx.__enter__()

    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name, fn in sections:
        if only and only not in name:
            continue
        print(f"# --- {name} ---", file=sys.stderr)
        try:
            for row in fn():
                print(row.csv(), flush=True)
        except Exception as e:  # noqa: BLE001 — report, go on, exit 1 at the end
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)
            failed.append(name)
    write_bench_json("BENCH_cohort.json", "REPRO_BENCH_COHORT_JSON",
                     systems_bench.COHORT_BENCH)
    write_bench_json("BENCH_disruption.json", "REPRO_BENCH_DISRUPTION_JSON",
                     disruption.DISRUPTION_BENCH)
    write_bench_json("BENCH_serving.json", "REPRO_BENCH_SERVING_JSON",
                     serving_fleet.SERVING_BENCH)
    write_bench_json("BENCH_workload.json", "REPRO_BENCH_WORKLOAD_JSON",
                     workload.WORKLOAD_BENCH)

    if profile_ctx is not None:
        profile_ctx.__exit__(None, None, None)
        print(f"# profile: XLA profile and perfetto_trace.json.gz -> {args.profile}",
              file=sys.stderr)
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# failed sections: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
