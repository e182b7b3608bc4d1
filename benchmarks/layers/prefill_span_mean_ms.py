"""ModelRunner step: mean time from `prefill_start` to `first_token`: the
sequence's prefill launches, whatever the device ran between them, and the
deferred readback of its first token, over the requests the worker closed
inside the window: stage `prefill` of `dynamo_stage_duration_seconds`,
growth of `_sum` over growth of `_count` (layers/ingress_mean_ms.py holds
the arithmetic). None where the program keeps no such stage."""

STAGE = "prefill"


def read(ctx: dict):
    return ctx["layer"]("ingress_mean_ms").mean_ms(ctx, STAGE)
