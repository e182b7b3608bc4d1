"""ops/ssm.py: the Mamba-2 decode state-update kernel's share of its
roofline: the least time to read and to write the live rows' float32 SSM
state (rows counted by the client over the capture) in one Mamba layer,
over the kernel's device time an event (one event = one layer of one
decode step; the events are counted in the trace itself, since a fused
block that straddles the capture's edge is counted whole among the
programs and in part among the operations). Bound by HBM bandwidth: the
update is a few FLOPs a byte.

The kernel's events are found in the device trace by name
(`ssm_state_update`), or by the pattern the configuration's file gives
under `trace_names.ssm_decode_kernels`. None where the trace holds no
such kernel (a program without Mamba layers, or before this kernel)."""

import re

KERNELS = "^ssm_state_update"


def kernel_events(ctx: dict, key: str, default: str) -> tuple[float, int]:
    """(device seconds, events) of the operations whose name matches the
    pattern `trace_names.<key>` of the configuration's file (or
    `default`)."""
    pattern = re.compile(ctx["config"].get("trace_names", {}).get(
        key, default))
    found = [op for name, op in (ctx.get("trace") or {}).get(
        "ops", {}).items() if pattern.search(name)]
    return (sum(op["seconds"] for op in found),
            sum(op["count"] for op in found))


def kernel_seconds(ctx: dict, key: str, default: str) -> float:
    return kernel_events(ctx, key, default)[0]


def live_rows(ctx: dict) -> float:
    """Mean sequences decoding over the capture, sampled every 50 ms, as
    the client can tell (between first token and the stream's close)."""
    window, stats = ctx["window"], ctx["stats"]
    a, b = window["capture_at"], window["capture_end"]
    n = max(1, int((b - a) / 0.05))
    return sum(stats.live_decode_tokens(
        ctx["timelines"], a + (i + 0.5) * (b - a) / n)[0]
        for i in range(n)) / n


def read(ctx: dict):
    kernel_s, events = kernel_events(ctx, "ssm_decode_kernels", KERNELS)
    shapes = ctx["shapes"]
    if (kernel_s <= 0 or "capture_at" not in ctx["window"]
            or not hasattr(shapes, "ssm_decode_kernel_bytes")):
        return None
    layers = shapes.sizes(ctx["config"])["n_m"]
    least_s = (shapes.ssm_decode_kernel_bytes(ctx["config"], live_rows(ctx))
               / layers / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_s / events)
