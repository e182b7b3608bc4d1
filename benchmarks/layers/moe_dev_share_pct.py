"""ModelRunner step: the dropless experts' grouped matmuls' share of all
device time in the capture, found by name (`trace_names.expert_kernels`).
The router, the sort, the gather and the shared expert are XLA operations
under the scope `moe_experts`, which the trace reduction does not
attribute: they are NOT in this share (PERF.md, Open questions)."""


def read(ctx: dict):
    ops = (ctx.get("trace") or {}).get("ops", {})
    total = sum(op["seconds"] for op in ops.values())
    gmm = ctx["layer"]("expert_gmm_roofline_pct")
    kernels = ctx["layer"]("ssm_decode_roofline_pct").kernel_seconds(
        ctx, "expert_kernels", gmm.KERNELS)
    if total <= 0 or kernels <= 0:
        return None
    return 100.0 * kernels / total
