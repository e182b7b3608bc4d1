"""Scheduler thread: mean time from the worker's `received` stamp to
`scheduled`: the handler's work before submit, the incoming queue and the
wait for a slot and pages, over the requests the worker closed inside the
window: stage `queue` of `dynamo_stage_duration_seconds`, growth of `_sum`
over growth of `_count` (layers/ingress_mean_ms.py holds the arithmetic).
None where the program keeps no such stage."""

STAGE = "queue"


def read(ctx: dict):
    return ctx["layer"]("ingress_mean_ms").mean_ms(ctx, STAGE)
