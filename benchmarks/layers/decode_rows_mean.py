"""Scheduler thread: useful rows per device decode step over the window:
growth of `dynamo_engine_tokens{kind="decode"}` (tokens the scheduler
kept) over growth of `dynamo_engine_launches{kind="decode_step"}` (device
decode steps; a fused block of 8 counts 8). A row whose tokens are thrown
away after it finished inside a block does not count, nor does a slot
whose sequence waits or prefills: this is the useful occupancy of the
decode batch, not the slots held. None where the program counts no
launches (before PR 26)."""

from dtbench import scrapes

TOKENS = "dynamo_engine_tokens"
LAUNCHES = "dynamo_engine_launches"


def read(ctx: dict):
    window = ctx["window"]
    return scrapes.ratio(
        scrapes.growth(window, TOKENS, kind="decode"),
        scrapes.growth(window, LAUNCHES, kind="decode_step"))
