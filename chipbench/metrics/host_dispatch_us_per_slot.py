"""Host self time of dispatching the jitted scan (span
``potus/cohort-fused/chunk``; the call returns before the device is done),
per slot simulated in the traced part of the window."""
from chipbench import stages


def read(ctx: dict) -> float | None:
    return stages.span_us_per_slot(ctx, "potus/cohort-fused/chunk")
