"""ops/paged_attention.py: the window layers' decode-attention kernel's
share of its roofline: the least time to read the keys and values ONE
sliding layer's step can see (for every decoding row the last
`sliding_window` positions of its context: rows and contexts from the
client's timelines over the capture, sampled every 50 ms) over the
kernel's device time an event (one event = one sliding layer of one
decode step; counted in the trace itself, since a fused block that
straddles the capture's edge is counted whole among the programs and in
part among the operations). Bound by HBM bandwidth.

The kernel's events are found in the device trace by name
(`paged_decode_attention_window`, which the full layers' kernel does not
share), or by the pattern the configuration's file gives under
`trace_names.window_attention_kernels`. None where the trace holds no
such kernel (a model without window layers, or a program before it)."""

KERNELS = "^paged_decode_attention_window"


def live_contexts(timelines: list, at: float) -> list:
    """Context lengths of the sequences decoding at instant `at`, as the
    client can tell (`stats.live_decode_tokens`, row by row)."""
    return [t.n_prompt + sum(1 for c in t.chunk_times if c <= at)
            for t in timelines
            if t.first is not None and t.first <= at
            and not (t.end is not None and t.end < at)]


def mean_layer_bytes(ctx: dict) -> float:
    """Mean over the capture of what one sliding layer's step reads."""
    window, shapes = ctx["window"], ctx["shapes"]
    a, b = window["capture_at"], window["capture_end"]
    n = max(1, int((b - a) / 0.05))
    return sum(shapes.window_layer_kv_bytes(
        ctx["config"], live_contexts(ctx["timelines"],
                                     a + (i + 0.5) * (b - a) / n))
        for i in range(n)) / n


def read(ctx: dict):
    kernel_s, events = ctx["layer"]("ssm_decode_roofline_pct").kernel_events(
        ctx, "window_attention_kernels", KERNELS)
    if (kernel_s <= 0 or "capture_at" not in ctx["window"]
            or not hasattr(ctx["shapes"], "window_layer_kv_bytes")):
        return None
    least_s = mean_layer_bytes(ctx) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_s / events)
