"""Device: share of the capture in which no operation ran on the chip
(1 - union of the device's operation intervals over the traced window)."""


def read(ctx: dict):
    trace = ctx.get("trace") or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
