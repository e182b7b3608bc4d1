"""ModelRunner step: the window layers' decode-attention kernel's share of
all device time in the capture, found by name
(`trace_names.window_attention_kernels`). The projections, rope and the
KV writes under the scope `attn_window` are XLA operations the trace
reduction does not attribute: they are NOT in this share, nor is prefill
attention on a window layer (XLA, under the same scope)."""


def read(ctx: dict):
    ops = (ctx.get("trace") or {}).get("ops", {})
    total = sum(op["seconds"] for op in ops.values())
    roofline = ctx["layer"]("window_attn_roofline_pct")
    kernels = ctx["layer"]("ssm_decode_roofline_pct").kernel_seconds(
        ctx, "window_attention_kernels", roofline.KERNELS)
    if total <= 0 or kernels <= 0:
        return None
    return 100.0 * kernels / total
