"""Reduce a JAX profiler trace of the measured window to numbers.

The traced run wraps its window in the host span ``chipbench/window`` and
each call into the system in ``chipbench/call``; the program's own spans
(``potus/...``) appear beside them when its tracing is on. From the
``.xplane.pb`` the profiler writes, :func:`load` takes

* the device operations: events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane, with the device's index;
* the device programs: events of the ``XLA Modules`` line (one per launch of
  a jitted program, named after it);
* the host spans: events of the host plane whose name starts with one of
  ``HOST_SPAN_PREFIXES``.

All times are nanoseconds on the profiler's one clock. :func:`reduce`
turns them into the window's length, each device's busy time (the union of
its operation intervals inside the window), the device time of the programs
whose name contains a given string, the operations that took most time (a
loop's ``while`` spans the operations of its body and is left out of that
list, not of the busy time), and the idle gaps named by the innermost host
span open at their midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

WINDOW_SPAN = "chipbench/window"
HOST_SPAN_PREFIXES = ("chipbench/", "potus/")


@dataclasses.dataclass
class Trace:
    ops: list  # (device, name, start_ns, dur_ns)
    modules: list  # (device, name, start_ns, dur_ns)
    spans: list  # (name, start_ns, dur_ns)


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest.extend((dev, e.name, float(e.start_ns), float(e.duration_ns))
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events if e.name.startswith(HOST_SPAN_PREFIXES))
    return Trace(ops, modules, spans)


#: control-flow ops whose events span the ops they run
_CONTAINERS = ("while", "conditional", "call")


def op_name(hlo: str) -> str:
    """``%fusion.250 = f32[142101]{0:T(1024)} fusion(...)`` -> ``fusion.250
    f32[142101]``: the instruction and its result shape, without layouts."""
    head, _, rest = hlo.partition(" = ")
    shape = rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{head.lstrip('%')} {shape}".strip()


def _union(intervals: np.ndarray) -> np.ndarray:
    """Disjoint sorted (start, end) intervals covering ``intervals``."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def reduce(tr: Trace, module_substr: str, top: int = 10) -> dict:
    """Numbers of the window (see the module docstring). Seconds, except
    where a key says otherwise; ``None`` where the trace holds nothing."""
    windows = [(s, s + d) for name, s, d in tr.spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    w0, w1 = windows[-1]
    devices = sorted({d for d, *_ in tr.ops})
    busy, busy_iv = [], {}
    for dev in devices:
        iv = np.array([(s, s + d) for dv, _, s, d in tr.ops if dv == dev])
        u = _clip(_union(iv), w0, w1)
        busy_iv[dev] = u
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
    per_op: dict = {}
    for _, name, s, d in tr.ops:
        lo, hi = max(s, w0), min(s + d, w1)
        short = op_name(name)
        if hi > lo and short.split(".", 1)[0] not in _CONTAINERS:
            per_op[short] = per_op.get(short, 0.0) + (hi - lo) * 1e-9
    module_s = sum((min(s + d, w1) - max(s, w0)) * 1e-9 for _, name, s, d in tr.modules
                   if module_substr in name and min(s + d, w1) > max(s, w0))
    gaps: dict = {}
    if devices:
        u = busy_iv[devices[0]]
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        host = [(n, s, s + d) for n, s, d in tr.spans if n != WINDOW_SPAN]
        for g0, g1 in edges:
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            open_ = [(e - s, n) for n, s, e in host if s <= mid < e]
            name = min(open_)[1] if open_ else WINDOW_SPAN
            gaps[name] = gaps.get(name, 0.0) + (g1 - g0) * 1e-9
    n_modules = sum(1 for _, name, s, d in tr.modules
                    if module_substr in name and w0 <= s < w1)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": float(np.mean(busy)) if busy else 0.0,
        "module_s": module_s if n_modules else None,
        "module_launches": n_modules,
        "device_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
    }
