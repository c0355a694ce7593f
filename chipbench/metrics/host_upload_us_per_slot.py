"""Host self time of handing the call's inputs to the device (span
``potus/cohort-fused/upload``: the device constants, the carry, and each
chunk's arrivals, predictions and parameters), per slot simulated in the
traced part of the window."""
from chipbench import stages


def read(ctx: dict) -> float | None:
    return stages.span_us_per_slot(ctx, "potus/cohort-fused/upload")
