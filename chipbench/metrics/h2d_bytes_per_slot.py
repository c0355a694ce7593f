"""Bytes the fused engine handed to the device (program counter
``h2d_bytes``, counted while its tracing is on), per slot simulated in the
traced part of the window."""
from chipbench import stages


def read(ctx: dict) -> float | None:
    return stages.counter_per_slot(ctx, "h2d_bytes")
