"""Frontend, router, request plane: mean time from the frontend's handler
entry to the worker's `received` stamp, over the requests the worker
closed inside the window: stage `ingress` of
`dynamo_stage_duration_seconds` (growth of `_sum` over growth of
`_count`). The frontend sends its arrival time with the request; both
ends read time.time(), on one host here. None where the program keeps no
such stage (before PR 26)."""

from dtbench import scrapes

FAMILY = "dynamo_stage_duration_seconds"
STAGE = "ingress"


def mean_ms(ctx: dict, stage: str):
    """Mean of one stage over the window, in ms; the other stage readers
    come here with their own stage."""
    window = ctx["window"]
    return scrapes.ratio(
        scrapes.growth(window, FAMILY + "_sum", stage=stage),
        scrapes.growth(window, FAMILY + "_count", stage=stage), 1e3)


def read(ctx: dict):
    return mean_ms(ctx, STAGE)
