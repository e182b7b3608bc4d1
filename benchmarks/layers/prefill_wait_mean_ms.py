"""Scheduler thread: mean time from `scheduled` to `prefill_start`: admitted
and holding pages, waiting for a prefill launch that takes the sequence,
over the requests the worker closed inside the window: stage
`prefill_wait` of `dynamo_stage_duration_seconds`, growth of `_sum` over
growth of `_count` (layers/ingress_mean_ms.py holds the arithmetic). None
where the program keeps no such stage."""

STAGE = "prefill_wait"


def read(ctx: dict):
    return ctx["layer"]("ingress_mean_ms").mean_ms(ctx, STAGE)
