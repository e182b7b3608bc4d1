"""Scheduler thread: prefill launches a prompt took: growth of
`dynamo_ssm_prefill_launch_rows_total` over both carries (every row of
every prefill launch) over the growth of its `carry="fresh"` rows (a
request's first launch: the requests that began prefilling in the
window). 1 where every prompt fits one launch's token budget; the
scheduler makes one launch between two decode blocks (ROADMAP A7), so
this is also how many blocks a prompt waits through. None where the
program keeps no such counter."""

from dtbench import scrapes

ROWS = "dynamo_ssm_prefill_launch_rows_total"


def read(ctx: dict):
    window = ctx["window"]
    return scrapes.ratio(scrapes.growth(window, ROWS),
                         scrapes.growth(window, ROWS, carry="fresh"))
