"""Pallas kernels / expert layer: the busiest held expert's token-slots
over the mean, over the window: growth of
`dynamo_moe_expert_tokens_total{expert}` (counted on the device by the
dropless layer, summed over the expert layers). 1.0 is a flat load; the
busiest expert's rows are the grouped matmul's longest group. None where
the program keeps no such counter (before this layer)."""

EXPERT_TOKENS = "dynamo_moe_expert_tokens_total"


def read(ctx: dict):
    window = ctx["window"]
    before = {row.get("expert"): v for row, v in
              window["before"].get(EXPERT_TOKENS, [])}
    grown = [v - before.get(row.get("expert"), 0.0) for row, v in
             window["after"].get(EXPERT_TOKENS, [])]
    if not grown or sum(grown) <= 0:
        return None
    return max(grown) / (sum(grown) / len(grown))
