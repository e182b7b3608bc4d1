"""ops/paged_attention.py: the decode-attention kernel's share of its
roofline: the least time to read the live contexts' keys and values
(client's count over the capture) over the kernel's device time per
decode step.

The kernel's events are found in the device trace by name
(`paged_decode_attention_pool`: my chip run, PR 25); a configuration whose
kernel is named otherwise gives a pattern of its own in its file, under
`trace_names.attention_kernels`."""

import re

ATTENTION_KERNELS = "^paged_decode_attention"


def read(ctx: dict):
    trace, window = ctx.get("trace") or {}, ctx["window"]
    pattern = re.compile(ctx["config"].get("trace_names", {}).get(
        "attention_kernels", ATTENTION_KERNELS))
    kernel_s = sum(op["seconds"] for name, op in trace.get("ops", {}).items()
                   if pattern.search(name))
    found = ctx["layer"]("decode_step_dev_ms").launches(ctx)
    if found is None or kernel_s <= 0 or "capture_at" not in window:
        return None
    steps = found[0] * found[2]
    live = ctx["stats"].mean_live_decode_tokens(
        ctx["timelines"], window["capture_at"], window["capture_end"])
    least_s = (ctx["shapes"].attention_step_bytes(ctx["config"], live)
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_s / steps)
