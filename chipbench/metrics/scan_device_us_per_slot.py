"""Device time of the fused cohort engine's jitted scan program, found by its
name (``_scan_cohort_fused``), per slot simulated in the traced part of the window."""


def read(ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or tr["module_s"] is None or ctx["slots"] <= 0:
        return None
    return tr["module_s"] * 1e6 / ctx["slots"]
