"""ModelRunner step: the share of the steps' wall time that the host
spent inside the runner's submit calls (argument transfer and program
launch, before the device owns the step): growth of
`dynamo_step_part_ms_total{part="dispatch"}` over the growth of the
steps' wall ms. A part of `sched_host_share_pct`, which is all the host's
residual. None where the program publishes no parts (before PR 26)."""

from dtbench import scrapes

PARTS = "dynamo_step_part_ms_total"
PART = "dispatch"


def read(ctx: dict):
    window = ctx["window"]
    return scrapes.ratio(scrapes.growth(window, PARTS, part=PART),
                         scrapes.step_wall_ms(window), 100.0)
