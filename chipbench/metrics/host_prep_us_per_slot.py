"""Host self time of the fused engine's input preparation (span
``potus/cohort-fused/prep``: arrivals, problem build, compact view, stream
prep), per slot simulated in the traced part of the window."""
from chipbench import stages


def read(ctx: dict) -> float | None:
    return stages.span_us_per_slot(ctx, "potus/cohort-fused/prep")
