"""ModelRunner step: the gated short convolution's share of all device
time in the capture, found by operation name
(`trace_names.conv_kernels`, else the mixer's scope, `conv_mixer`). The
mixer is XLA operations under that scope in prefill and decode programs
alike, and the trace reduction keeps operation names, not their scopes:
until it attributes scopes, or the conv step has an operation line of
its own, this reads None on every program (PERF.md, Open questions)."""

KERNELS = "^conv_mixer"


def read(ctx: dict):
    ops = (ctx.get("trace") or {}).get("ops", {})
    total = sum(op["seconds"] for op in ops.values())
    kernels = ctx["layer"]("ssm_decode_roofline_pct").kernel_seconds(
        ctx, "conv_kernels", KERNELS)
    if total <= 0 or kernels <= 0:
        return None
    return 100.0 * kernels / total
