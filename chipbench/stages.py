"""Put a traced run's host time down to the program's phases and its scan's
device time down to the stages of the slot step.

The program (``repro``) opens host spans ``potus/<layer>/<stage>`` around
the phases of a call while its tracing is on, and wraps the stages of its
slot step (``reconcile``, ``decide``, ``drain``, ``land``, ``admit``, plus
``metrics`` for the metric streams) in ``jax.named_scope``. A TPU profile
names each device operation by its HLO instruction but carries no name
stack, so the stage of an operation comes from the ``op_name`` metadata of
that instruction in the scan's compiled HLO text, which the program gives
(``repro.core.cohort_fused.scan_hlo_text``); a fusion goes to the stage of
its root instruction.

From the ``.xplane.pb`` of a traced run, :func:`load` takes the host spans
(``potus/`` and ``chipbench/``) with the line (thread) that recorded them,
the device operations (control-flow containers left out) and the launches
of the device programs. :func:`span_self_s` gives each span name's self
time (its duration less what its child spans cover) and :func:`stage_s`
each stage's device time within the scan's launches, both inside the
window span. :func:`of` does all of that once per run for the metric
readers and adds the program's counters (``repro.obs.trace.take_counters``).
A program without the spans, scopes or counters reads ``None`` there, not 0.
The readers find the trace in ``ctx["log_dir"]``, where ``chipbench/run.py``
has the profiler write.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import importlib
import math
import os
import re
import sys
from collections import Counter

from chipbench import trace as trace_reducer

#: stages of the slot step, by the ``jax.named_scope`` that wraps each
STAGES = ("reconcile", "decide", "drain", "land", "admit", "metrics")
SCAN_PROGRAM = "_scan_cohort_fused"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) ")


@dataclasses.dataclass
class Recording:
    spans: list  # (name, start_ns, dur_ns, line)
    ops: list  # (device, "name shape", start_ns, dur_ns); control-flow ops left out
    modules: list  # (device, name, start_ns, dur_ns)


def stage_of(name_stack: str) -> str | None:
    """The stage a name stack names: the first of its ``/``-separated parts
    that is one of :data:`STAGES`."""
    for part in name_stack.split("/"):
        if part in STAGES:
            return part
    return None


def hlo_stages(text: str) -> dict:
    """``{"name shape": stage or None}`` for every instruction of a compiled
    HLO module's text; the keys are :func:`chipbench.trace.op_name`'s, as
    the profile's operations are. A fusion takes the stage of its called
    computation's root instruction, else its own, else the stage most of
    the called computation's instructions name (the TPU compiler drops the
    metadata of the scatters it rewrites, which are roots of fusions)."""
    own, calls, root, members = {}, {}, {}, {}
    computation = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            computation = m.group(1) if m else None
            continue
        body = line.strip()
        is_root = body.startswith("ROOT ")
        body = body.removeprefix("ROOT ")
        if not body.startswith("%") or " = " not in body:
            continue
        key = trace_reducer.op_name(body)
        meta = _OP_NAME.search(body)
        own[key] = stage_of(meta.group(1)) if meta else None
        called = _CALLS.search(body)
        if called:
            calls[key] = called.group(1)
        if computation:
            members.setdefault(computation, []).append(key)
            if is_root:
                root[computation] = key
    memo: dict = {}

    def stage(key):
        if key not in memo:
            memo[key] = None  # a cycle reads no stage
            called = calls.get(key)
            found = stage(root[called]) if called in root else None
            if not found and not own.get(key) and called:
                votes = Counter(filter(None, map(stage, members.get(called, ()))))
                found = votes.most_common(1)[0][0] if votes else None
            memo[key] = found or own.get(key)
        return memo[key]

    return {key: stage(key) for key in own}


def load(log_dir: str) -> Recording:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    spans, ops, modules, short = [], [], [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name = e.name
                        if name not in short:  # once per distinct operation
                            op = trace_reducer.op_name(name)
                            skip = op.split(".", 1)[0] in trace_reducer._CONTAINERS
                            short[name] = None if skip else op
                        if short[name] is not None:
                            ops.append((dev, short[name], float(e.start_ns),
                                        float(e.duration_ns)))
                elif line.name == "XLA Modules":
                    modules.extend((dev, e.name, float(e.start_ns), float(e.duration_ns))
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns),
                              (plane.name, k))
                             for e in line.events
                             if e.name.startswith(trace_reducer.HOST_SPAN_PREFIXES))
    return Recording(spans, ops, modules)


def window(rec: Recording) -> tuple[float, float]:
    wins = [(s, s + d) for n, s, d, _ in rec.spans if n == trace_reducer.WINDOW_SPAN]
    if not wins:
        raise ValueError(f"the trace has no {trace_reducer.WINDOW_SPAN!r} span")
    return wins[-1]


def span_self_s(rec: Recording, w0: float, w1: float) -> dict:
    """Seconds of self time per span name, over the spans that start inside
    the window: each span's duration less what its direct children (spans
    of the same line nested inside it) cover."""
    by_line: dict = {}
    for name, s, d, line in rec.spans:
        by_line.setdefault(line, []).append((s, -d, name))
    out: dict = {}
    for items in by_line.values():
        items.sort()  # by start; an enclosing span before what it encloses
        stack: list = []  # open spans: [end, name, self_ns, starts in the window]
        for s, neg_d, name in items + [(math.inf, 0.0, None)]:
            while stack and stack[-1][0] <= s:
                _, done, self_ns, inside = stack.pop()
                if inside:
                    out[done] = out.get(done, 0.0) + self_ns * 1e-9
            if name is None:
                break
            if stack:
                stack[-1][2] += neg_d  # a child covers part of its parent
            stack.append([s - neg_d, name, -neg_d, w0 <= s < w1])
    return out


def stage_s(rec: Recording, stages_by_op: dict, w0: float, w1: float,
            module_substr: str = SCAN_PROGRAM):
    """(seconds per stage, seconds of the program's launches, seconds of
    those launches that no stage claims), each averaged over the devices,
    inside the window. An operation counts where it starts inside one of the
    program's launches; ``stages_by_op`` is :func:`hlo_stages` of the
    program. The stages map holds only stages that some operation carries."""
    launches: dict = {}
    for dev, name, s, d in rec.modules:
        if module_substr in name:
            launches.setdefault(dev, []).append((s, s + d))
    for iv in launches.values():
        iv.sort()
    per: dict = {}
    module = 0.0
    for dev, iv in launches.items():
        module += sum(max(0.0, min(e, w1) - max(s, w0)) for s, e in iv) * 1e-9
    for dev, name, s, d in rec.ops:
        iv = launches.get(dev)
        stage = stages_by_op.get(name)
        if not iv or stage is None:
            continue
        k = bisect.bisect_right(iv, (s, math.inf)) - 1
        lo, hi = max(s, w0), min(s + d, w1)
        if k >= 0 and iv[k][0] <= s < iv[k][1] and hi > lo:
            per[stage] = per.get(stage, 0.0) + (hi - lo) * 1e-9
    n = max(len(launches), 1)
    per = {k: v / n for k, v in per.items()}
    module /= n
    return per, module, module - sum(per.values())


def program_scan_hlo() -> str | None:
    """The program's compiled scan HLO text; ``None`` where the program
    gives none or fails to (a reader reports nothing rather than fail the run)."""
    try:
        text_of = importlib.import_module("repro.core.cohort_fused").scan_hlo_text
    except (ImportError, AttributeError):
        return None
    try:
        return text_of()
    except Exception as e:  # noqa: BLE001
        print(f"chipbench.stages: no scan HLO: {type(e).__name__}: {e}", file=sys.stderr)
        return None


def program_counters() -> dict | None:
    """The program's counters, read and reset; ``None`` where the program
    keeps none."""
    try:
        take = importlib.import_module("repro.obs.trace").take_counters
    except (ImportError, AttributeError):
        return None
    return take()


_memo: dict = {}


def of(ctx: dict) -> dict | None:
    """Everything the readers need from one traced run, computed once per
    ``ctx``: ``span_self_s``, ``stage_s``, ``scan_s``, ``unclaimed_s`` and
    ``counters``. ``None`` where no trace is found in ``ctx["log_dir"]``."""
    key = id(ctx)
    if key in _memo and _memo[key][0] is ctx:
        return _memo[key][1]
    try:
        rec = load(ctx["log_dir"])
        w0, w1 = window(rec)
    except (FileNotFoundError, ValueError):
        rec = None
    out = None
    if rec is not None:
        text = program_scan_hlo()
        per, scan, unclaimed = stage_s(rec, hlo_stages(text) if text else {}, w0, w1)
        out = {"span_self_s": span_self_s(rec, w0, w1), "stage_s": per,
               "scan_s": scan, "unclaimed_s": unclaimed,
               "counters": program_counters()}
    _memo.clear()
    _memo[key] = (ctx, out)
    return out


def span_us_per_slot(ctx: dict, name: str) -> float | None:
    """Self time of span ``name`` in microseconds per traced slot."""
    st = of(ctx)
    if st is None or name not in st["span_self_s"] or ctx["slots"] <= 0:
        return None
    return st["span_self_s"][name] * 1e6 / ctx["slots"]


def stage_us_per_slot(ctx: dict, stage: str) -> float | None:
    """Device time of slot-step stage ``stage`` in microseconds per traced slot."""
    st = of(ctx)
    if st is None or stage not in st["stage_s"] or ctx["slots"] <= 0:
        return None
    return st["stage_s"][stage] * 1e6 / ctx["slots"]


def counter_per_slot(ctx: dict, name: str) -> float | None:
    """Program counter ``name`` per traced slot."""
    st = of(ctx)
    if st is None or not st["counters"] or name not in st["counters"] or ctx["slots"] <= 0:
        return None
    return st["counters"][name] / ctx["slots"]
