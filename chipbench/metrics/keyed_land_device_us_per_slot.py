"""Device time of the scan's operations in the slot step's ``keyed`` scope
(inside ``drain``: a fields grouping's drained mass landed on the keyed
component's instances by their key shares), per slot simulated in the
traced part of the window.

``chipbench/stages.py`` maps each operation to the first stage its name
stack names, so a ``drain/keyed`` operation counts as ``drain`` there; this
reader maps the scan's instructions to ``keyed`` alone, by the same rules
(an instruction's own ``op_name``, a fusion's root, else most of the fused
instructions), and times them with ``stages.stage_s``. A program without
the scope reads ``None``."""
import re
from collections import Counter

from chipbench import stages
from chipbench import trace as trace_reducer

SCOPE = "keyed"


def _in_scope(name_stack: str) -> str:
    """``keyed`` where a scope of the name stack is (its last part names the
    operation itself, or an argument of the program), else ``""``."""
    return SCOPE if SCOPE in name_stack.split("/")[:-1] else ""


def keyed_ops(text: str) -> dict:
    """``{"name shape": "keyed" or None}`` for each instruction of a
    compiled HLO module's text."""
    own, calls, root, members = {}, {}, {}, {}
    computation = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = re.match(r"^(?:ENTRY )?%(\S+) ", line)
            computation = m.group(1) if m else None
            continue
        body = line.strip()
        is_root = body.startswith("ROOT ")
        body = body.removeprefix("ROOT ")
        if not body.startswith("%") or " = " not in body:
            continue
        key = trace_reducer.op_name(body)
        meta = re.search(r'op_name="([^"]*)"', body)
        own[key] = _in_scope(meta.group(1)) if meta else None
        called = re.search(r"calls=%([^\s,]+)", body)
        if called:
            calls[key] = called.group(1)
        if computation:
            members.setdefault(computation, []).append(key)
            if is_root:
                root[computation] = key
    memo: dict = {}

    def scope(key):  # "keyed", "" (another scope) or None (no name stack)
        if key not in memo:
            memo[key] = None
            called = calls.get(key)
            found = scope(root[called]) if called in root else None
            if found is None and own.get(key) is None and called:
                votes = Counter(v for v in map(scope, members.get(called, ())) if v is not None)
                found = votes.most_common(1)[0][0] if votes else None
            memo[key] = found if found is not None else own.get(key)
        return memo[key]

    return {key: scope(key) or None for key in own}


def read(ctx: dict) -> float | None:
    if ctx["slots"] <= 0:
        return None
    try:
        rec = stages.load(ctx["log_dir"])
        w0, w1 = stages.window(rec)
    except (FileNotFoundError, ValueError):
        return None
    text = stages.program_scan_hlo()
    if not text:
        return None
    per, _, _ = stages.stage_s(rec, keyed_ops(text), w0, w1)
    if SCOPE not in per:
        return None
    return per[SCOPE] * 1e6 / ctx["slots"]
