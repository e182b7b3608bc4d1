"""ModelRunner step: prompt tokens per prefill program launched over the
window: growth of `dynamo_engine_tokens{kind="prefill"}` over growth of
`dynamo_engine_launches{kind="prefill"}` (single-row and batched launches
alike). What the scheduler's shared prefill budget really fills. None
where the program counts no launches (before PR 26)."""

from dtbench import scrapes

TOKENS = "dynamo_engine_tokens"
LAUNCHES = "dynamo_engine_launches"


def read(ctx: dict):
    window = ctx["window"]
    return scrapes.ratio(
        scrapes.growth(window, TOKENS, kind="prefill"),
        scrapes.growth(window, LAUNCHES, kind="prefill"))
