"""Paged cache: share of the WINDOW page group that sequences holding a
slot hold, as the time-weighted mean over the window's committed steps:
growth of `dynamo_kv_window_reserved_page_ms` (pages of the second pool
held by sequences in a slot x the step's wall ms) over the growth of the
steps' wall ms, over the group's pages (`--window-pages` among
`serve.worker_args`). A decoding row holds at most window / page size + 2
of them whatever its context, a row inside a prefill chunk the chunk's
more: with every slot decoding this reads rows x 66 over the group.
`kv_reserved_pct` keeps reading the full group. None where the program
keeps no such counter (a model without window layers, or before it)."""

from dtbench import scrapes

RESERVED = "dynamo_kv_window_reserved_page_ms"


def window_pages(config: dict):
    args = config["serve"].get("worker_args", [])
    if "--window-pages" not in args:
        return None
    return float(args[args.index("--window-pages") + 1])


def read(ctx: dict):
    window = ctx["window"]
    pages = scrapes.ratio(scrapes.growth(window, RESERVED),
                          scrapes.step_wall_ms(window))
    return scrapes.ratio(pages, window_pages(ctx["config"]), 100.0)
