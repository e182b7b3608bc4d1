"""Paged cache: share of the pool's pages that sequences holding a slot
have reserved, as the time-weighted mean over the window's committed
steps: growth of `dynamo_kv_reserved_page_ms` (pages allocated to
sequences in a slot x the step's wall ms; prefix-cache residue left out)
over the growth of the steps' wall ms, over `serve.num_pages`. Counted by
the program, so a sequence in prefill and a sequence's unused reservation
count: `kv_pool_live_pct`, the client's count of tokens in use, is its
floor. None where the program keeps no such counter (before PR 26)."""

from dtbench import scrapes

RESERVED = "dynamo_kv_reserved_page_ms"


def read(ctx: dict):
    window = ctx["window"]
    pages = scrapes.ratio(scrapes.growth(window, RESERVED),
                          scrapes.step_wall_ms(window))
    return scrapes.ratio(pages, ctx["config"]["serve"]["num_pages"], 100.0)
