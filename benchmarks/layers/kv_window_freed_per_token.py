"""Paged cache: pages of the window group returned to the pool because
they fell out of every window layer's reach WHILE their sequence decoded
(growth of `dynamo_kv_window_pages_freed_total{phase="decode"}`: not at
release), over the positions by which decoding sequences' windows moved
(growth of `dynamo_kv_window_edge_tokens_total{phase="decode"}`: a
decode token of a row whose context has passed the window moves it by
one). 1 / page size (0.0625) when the allocator is sound: a page behind
for every 16 tokens ahead; 0 where nothing is freed behind. None where
the program keeps no such counters."""

from dtbench import scrapes


def read(ctx: dict):
    window = ctx["window"]
    return scrapes.ratio(
        scrapes.growth(window, "dynamo_kv_window_pages_freed_total",
                       phase="decode"),
        scrapes.growth(window, "dynamo_kv_window_edge_tokens_total",
                       phase="decode"))
