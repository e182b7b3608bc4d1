"""Paged cache: the share of a decode step's page reads made by layers
that own no pages: growth of
`dynamo_kv_page_layer_reads_total{by="shared"}` (active rows x layers
that read another layer's pages, over decode steps) over the growth of
both. 7 of 16 layer-reads = 43.75 where seven cross-attention layers
read the one full layer's pages beside that layer and eight window
layers; by bytes their share is far larger, since a shared read covers a
row's whole context and a window layer's its last 512 tokens
(`shapes.attention_step_bytes`). None where the program keeps no such
counter (every layer owns what it reads, or a program before it)."""

from dtbench import scrapes

READS = "dynamo_kv_page_layer_reads_total"


def read(ctx: dict):
    window = ctx["window"]
    shared = scrapes.growth(window, READS, by="shared")
    return scrapes.ratio(shared, scrapes.growth(window, READS), 100.0)
