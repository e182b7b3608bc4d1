"""ModelRunner step: the Mamba-2 decode state-update kernel's share of
all device time in the capture, found by name
(`trace_names.ssm_decode_kernels`). The layers' projections, convolution
and norm, and the whole chunked prefill scan, are XLA operations under
the scopes `mamba_mixer` and `ssm_scan`, which the trace reduction does
not attribute (it keeps operation names, not their scopes): they are NOT
in this share (PERF.md, Open questions)."""


def read(ctx: dict):
    ops = (ctx.get("trace") or {}).get("ops", {})
    total = sum(op["seconds"] for op in ops.values())
    ssm = ctx["layer"]("ssm_decode_roofline_pct")
    kernels = ssm.kernel_seconds(ctx, "ssm_decode_kernels", ssm.KERNELS)
    if total <= 0 or kernels <= 0:
        return None
    return 100.0 * kernels / total
