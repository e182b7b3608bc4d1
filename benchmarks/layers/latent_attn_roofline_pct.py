"""ops/paged_attention.py: the latent pool's decode-attention kernel's
share of its roofline: the least time ONE latent layer's step needs for
the rows decoding (every cached token's row read once for all heads,
scored over rank + rope lanes and summed over rank lanes a head: rows
and contexts from the client's timelines over the capture, sampled every
50 ms) over the kernel's device time an event (one event = one latent
layer of one decode step; counted in the trace itself). BOTH sides: the
larger of bytes over the HBM bandwidth and operations over the bf16
peak, which lie within 1% of each other on a v5e at the published sizes
(242 operations a byte against a ridge of 240).

The kernel's events are found in the device trace by name
(`paged_decode_attention_latent`), or by the pattern the configuration's
file gives under `trace_names.latent_attention_kernels`. None where the
trace holds no such kernel (a model without latent layers, or a program
before it) or the configuration's counts have no latent layer."""

KERNELS = "^paged_decode_attention_latent"


def mean_layer_floor_s(ctx: dict) -> float:
    """Mean over the capture of the least time one latent layer's step
    takes: the larger of its two sides at each sampled instant."""
    window, shapes, peaks = ctx["window"], ctx["shapes"], ctx["peaks"]
    contexts_at = ctx["layer"]("window_attn_roofline_pct").live_contexts
    a, b = window["capture_at"], window["capture_end"]
    n = max(1, int((b - a) / 0.05))
    total = 0.0
    for i in range(n):
        contexts = contexts_at(ctx["timelines"], a + (i + 0.5) * (b - a) / n)
        total += max(
            shapes.latent_layer_bytes(ctx["config"], contexts)
            / peaks["hbm_bytes_per_s"],
            shapes.latent_layer_flops(ctx["config"], contexts)
            / peaks["bf16_flops"])
    return total / n


def read(ctx: dict):
    kernel_s, events = ctx["layer"]("ssm_decode_roofline_pct").kernel_events(
        ctx, "latent_attention_kernels", KERNELS)
    if (kernel_s <= 0 or "capture_at" not in ctx["window"]
            or not hasattr(ctx["shapes"], "latent_layer_bytes")):
        return None
    return 100.0 * mean_layer_floor_s(ctx) / (kernel_s / events)
