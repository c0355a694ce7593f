"""Chip benchmark of the fused cohort engine: one run of one cell.

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``chipbench/configs/<config>.json``: the deployment, built by
``chipbench/deploy.py`` or by the code of the kind it names) and a traffic
mix (``chipbench/traffic/<traffic>.json``: the ``simulate`` call, its
arrivals, and the limits of the output check). The run

1. refuses to run (exit 2, no result) unless JAX finds a TPU with as many
   chips as the cell asks for;
2. sets up: builds the deployment, draws the mix's inputs from ``--seed``,
   and makes one call to compile and warm every program the window runs
   (JAX's compile cache lives at ``JAX_COMPILATION_CACHE_DIR`` or else at
   ``<checkout>/.jax_cache``);
3. measures: makes calls, cycling through the drawn inputs, until
   ``--seconds`` have passed, and counts the slots of the calls that
   completed (``--trace 1`` runs the window under the profiler and reports
   the per-layer metrics instead of the end-to-end ones);
4. checks one call, drawn from the seed, against the deployment's plain
   reference (``chipbench/reference.py`` unless its kind brings one) and
   prints each compared number beside its limit, on standard error and as
   the ``checks`` key of the result line;
5. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` and, with ``--trace 1``, ``breakdown``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import deploy, lookup, stages, traffic  # noqa: E402
from chipbench import trace as trace_reducer  # noqa: E402

SCAN_PROGRAM = "_scan_cohort_fused"  # the jitted scan's stable name
# a traced run profiles the first seconds of its window only: reducing the
# trace of the k4 cells takes about 17 s per traced second (TPU v5e host),
# and a whole run has to end within 360 s
TRACE_SLICE_S = 2.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"unknown workload {name!r}")


def device_peaks(kind: str) -> dict:
    """The chip's published peaks (``chipbench/peaks.json``); a device the
    table does not list is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise NoChip(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
    return table[kind]


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"need {chips} TPU chip(s), JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    device_peaks(devs[0].device_kind)
    return devs


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts the compilations (and compile-cache loads) JAX reports."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.n += 1


# ---------------------------------------------------------------------------
# calls into the system under test
# ---------------------------------------------------------------------------

def make_call(mix: dict, topo, net, placement):
    """``call(inputs) -> dict`` of one ``simulate`` run's summary and the
    mass-ledger streams the mix asks for, and the slots one call simulates."""
    from repro.core import EngineSpec, simulate

    T = int(mix["T"])

    def call(inputs):
        r = simulate(EngineSpec(
            topo=topo, net=net, placement=placement, arrivals=inputs["actual"], T=T,
            engine="cohort-fused", scheduler=mix["scheduler"], V=float(mix["V"]),
            window=int(mix["window"]), age_cap=int(mix["age_cap"]),
            warmup=int(mix["warmup"]), metrics=tuple(mix["metrics"])))
        st = r.metrics.streams
        return dict(backlog=np.asarray(r.backlog), cost=np.asarray(r.comm_cost),
                    avg_response=float(r.avg_response),
                    completed_mass=float(r.completed_mass),
                    transit=np.asarray(st["transit"][:, 0]),
                    held=np.asarray(st["held"][:, 0]),
                    served=np.asarray(st["saturation"][:, 1]))
    return call, T


# ---------------------------------------------------------------------------
# the output check
# ---------------------------------------------------------------------------

def _rel(a, b, floor=1.0):
    return np.abs(np.asarray(a, np.float64) - b) / np.maximum(np.abs(b), floor)


def injected(mix: dict, inputs: dict) -> float:
    """Tuple mass offered up to the last observed slot's lookahead window:
    slots 0 .. T-1+W of the drawn arrivals (spout streams only carry any)."""
    return float(inputs["actual"][:int(mix["T"]) + int(mix["window"])].sum(dtype=np.float64))


def ledger_gap(g: dict, offered: float) -> float:
    """Mass conservation over the whole run: what completed through slot
    T-2, plus what the system held when slot T-1 was observed (its backlog
    sample, the mass landed in T-2 and the admission backlog), against the
    mass offered. beta = 1, so the backlog counts each queued tuple once."""
    done = g["completed_mass"] - float(g["served"][-1])
    held = float(g["backlog"][-1]) + float(g["transit"][-2]) + float(g["held"][-2])
    return abs(done + held - offered) / offered


def compare(mix: dict, dep, inputs: dict, got: dict | None = None) -> dict:
    """The numbers the mix's ``limits`` name, between the program's call and
    the deployment's plain reference (``got=None``: the control, the
    reference in bfloat16, in the program's place).

    ``ledger_gap``: mass conservation over all slots (:func:`ledger_gap`).
    ``traj_gap``: the largest relative gap of the per-slot backlog and cost;
    ``response_gap`` and ``completed_gap``: the relative gaps of the mean
    response and of the completed mass. The last three suit a scheduler
    without discrete choices: two sound float32 implementations of POTUS
    break near-ties of prices differently and part from there."""
    names = mix["limits"]
    reference = deploy.reference_of(dep)
    m = reference.Model(dep)
    kw = dict(T=int(mix["T"]), scheduler=mix["scheduler"], W=int(mix["window"]),
              age_cap=int(mix["age_cap"]), warmup=int(mix["warmup"]), V=float(mix["V"]))
    g = got if got is not None else reference.run(m, inputs["actual"], precision="bfloat16",
                                                  **kw)
    out = {}
    if "ledger_gap" in names:
        out["ledger_gap"] = ledger_gap(g, injected(mix, inputs))
    if names.keys() & {"traj_gap", "response_gap", "completed_gap"}:
        ref = reference.run(m, inputs["actual"], **kw)
        out["traj_gap"] = max(float(_rel(g["backlog"], ref["backlog"]).max()),
                              float(_rel(g["cost"], ref["cost"]).max()))
        out["response_gap"] = float(_rel(g["avg_response"], ref["avg_response"], 1e-9))
        out["completed_gap"] = float(_rel(g["completed_mass"], ref["completed_mass"]))
    return {k: v for k, v in out.items() if k in names}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit, which it must not exceed; NaN fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        checks[name] = {"value": value, "limit": limit}
        ok = ok and bool(value <= limit) and not math.isnan(value)
    return ok, checks


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _load_reader(name: str):
    return lookup.module(os.path.join(HERE, "metrics"), name, "per-layer metric").read


def run(workload: str, seed: int, seconds: float, traced: bool,
        require_tpu: bool = True) -> tuple[dict, dict]:
    """One run; returns (result line, checks). ``require_tpu=False`` lets a
    test drive the rest of a run on the CPU."""
    bench = load_benchmark()
    cell = find_cell(bench, workload)
    import jax

    devs = check_devices(int(cell["chips"])) if require_tpu else jax.devices()
    enable_compile_cache()
    compiles = CompileCounter()

    dep = deploy.build_deployment(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    topo, net, placement = deploy.program_inputs(dep)
    draws = traffic.draw(mix, dep.rates, seed)
    call, slots_per_call = make_call(mix, topo, net, placement)
    call(draws[0])  # compile and warm every program of the window
    setup_s = time.perf_counter() - T_START

    from repro.obs import trace as program_trace

    results, walls = {}, []

    def calls_until(t_end: float) -> int:
        """Calls, cycling through the draws, until ``t_end``; how many."""
        n = 0
        while time.perf_counter() < t_end:
            d = len(walls) % len(draws)
            c0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench/call"):
                results[d] = call(draws[d])
            walls.append(time.perf_counter() - c0)
            n += 1
        return n

    compiles_before = compiles.n
    t0 = time.perf_counter()
    traced_calls = 0
    if traced:
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        stages.program_counters()  # reset: the counters cover the traced slice alone
        program_trace.enable_tracing()
        jax.profiler.start_trace(log_dir)
        with jax.profiler.TraceAnnotation(trace_reducer.WINDOW_SPAN):
            traced_calls = calls_until(t0 + min(seconds, TRACE_SLICE_S))
        jax.profiler.stop_trace()
        program_trace.disable_tracing()
    calls_until(t0 + seconds)
    window_s = time.perf_counter() - t0
    n_calls = len(walls)
    compiles_in_window = compiles.n - compiles_before
    print(f"calls {n_calls}: wall s min {min(walls):.4f} median {np.median(walls):.4f} "
          f"max {max(walls):.4f}", file=sys.stderr)
    stats = devs[0].memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    # the output check, on one call drawn from the seed
    pick = int(np.random.default_rng([seed, 7]).integers(min(n_calls, len(draws))))
    numbers = compare(mix, dep, draws[pick], results[pick])
    correct, checks = judge(numbers, mix["limits"])

    slots = n_calls * slots_per_call
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": n_calls, "failed": 0 if correct else 1}
    if traced:
        reduced = trace_reducer.reduce(trace_reducer.load(log_dir), SCAN_PROGRAM)
        ctx = {"trace": reduced, "slots": traced_calls * slots_per_call,
               "compiles_in_window": compiles_in_window, "log_dir": log_dir}
        metrics = {}
        for pm in bench["per_layer"]:
            if workload in pm.get("workloads", [workload]):
                value = _load_reader(pm["name"])(ctx)
                if value is not None:
                    metrics[pm["name"]] = {"value": value, "unit": pm["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line.update(metrics=metrics, device=device, breakdown={
            "device_ops": [[n, s] for n, s in reduced["device_ops"]],
            "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"]]})
        shutil.rmtree(log_dir, ignore_errors=True)
    else:
        units = {e["name"]: e["unit"] for e in bench["end_to_end"]}
        line.update(metrics={
            "slots_per_s": {"value": slots / window_s, "unit": units["slots_per_s"]},
            "setup_s": {"value": setup_s, "unit": units["setup_s"]}}, device=device)
    line["checks"] = checks
    return line, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line, checks = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
