"""Expert layer: share of the held experts that at least one token is
routed to in one decode step's call of the layer, the window's mean:
growth of `dynamo_moe_experts_touched_total{phase="decode"}` over
`dynamo_moe_expert_layer_calls_total{phase="decode"}`, over the experts
held. These are the experts whose weights a decode step has to read;
under uniform routing at a hundred rows it would be 99%, and what it
reads below that is the skew of the router (PERF.md). None where the
program keeps no such counters."""


def read(ctx: dict):
    touched = ctx["layer"]("expert_gmm_roofline_pct").touched_per_call(
        ctx["window"], "decode")
    shapes = ctx["shapes"]
    if touched is None or not hasattr(shapes, "sizes"):
        return None
    return 100.0 * touched / shapes.sizes(ctx["config"])["held"]
