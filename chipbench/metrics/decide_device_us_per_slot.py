"""Device time of the scan's operations in the slot step's ``decide`` scope
(observe the queues, schedule), per slot simulated in the traced part of the window."""
from chipbench import stages


def read(ctx: dict) -> float | None:
    return stages.stage_us_per_slot(ctx, "decide")
