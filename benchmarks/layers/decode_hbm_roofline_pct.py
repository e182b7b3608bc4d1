"""Kernels, the whole decode step: the least time the chip could take to
read what a decode step must read (the weights as stored, and the keys
and values of the live contexts, counted by the client over the capture)
over the step's device time. Bound by HBM bandwidth: a decode step's
arithmetic is far under the compute roof at these batch sizes."""


def read(ctx: dict):
    step_ms = ctx["read"]("decode_step_dev_ms")
    window = ctx["window"]
    if step_ms is None or "capture_at" not in window:
        return None
    live = ctx["stats"].mean_live_decode_tokens(
        ctx["timelines"], window["capture_at"], window["capture_end"])
    least_s = (ctx["shapes"].decode_step_bytes(ctx["config"], live)
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (step_ms / 1e3)
