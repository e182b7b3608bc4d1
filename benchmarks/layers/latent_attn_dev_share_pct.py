"""ModelRunner step: the latent pool's decode-attention kernel's share of
all device time in the capture, found by name
(`trace_names.latent_attention_kernels`). The projections, the
absorption (q W_uk^T, ctx W_uv), rope and the stacked write under the
scope `attn_latent` are XLA operations the trace reduction does not
attribute: they are NOT in this share, nor is prefill attention on a
latent layer (XLA, under the same scope)."""


def read(ctx: dict):
    ops = (ctx.get("trace") or {}).get("ops", {})
    total = sum(op["seconds"] for op in ops.values())
    roofline = ctx["layer"]("latent_attn_roofline_pct")
    kernels = ctx["layer"]("ssm_decode_roofline_pct").kernel_seconds(
        ctx, "latent_attention_kernels", roofline.KERNELS)
    if total <= 0 or kernels <= 0:
        return None
    return 100.0 * kernels / total
