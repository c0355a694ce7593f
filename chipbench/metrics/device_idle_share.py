"""Share of the traced window in which the device ran no operation:
1 - (union of device-op intervals) / window, averaged over the chips used."""


def read(ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0.0 or tr["window_s"] <= 0.0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
