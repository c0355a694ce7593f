"""Host self time of reducing the scan's outputs to the result (span
``potus/cohort-fused/reduce``: arrival weights, reachability, response
aggregation, metric frame), per slot simulated in the traced part of the window."""
from chipbench import stages


def read(ctx: dict) -> float | None:
    return stages.span_us_per_slot(ctx, "potus/cohort-fused/reduce")
