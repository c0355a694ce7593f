"""Compilations (or loads from the compile cache) that JAX reported while the
window ran: every shape is warmed up in set-up, so this should read 0."""


def read(ctx: dict) -> float | None:
    return float(ctx["compiles_in_window"])
