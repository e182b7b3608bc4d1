"""ModelRunner step: the share of prefill rows on which the cross-decoder
(the tail of layers that caches nothing and carries nothing in time:
gated memory units and cross-attention) ran for nothing: growth of
`dynamo_prefill_cross_decoder_rows_total{chunk="earlier"}` (rows whose
chunk did not end their prompt: the logits are not read) over the growth
of both chunks. The program runs the tail on ONE position of every row of
every launch, so that a launch shape is one program; this is what that
spends: 0 where every prompt fits one launch, 50 where every prompt takes
two. Times the tail's weights over a launch's time it is the share of
prefill device time to win by a second form. None where the program keeps
no such counter (a stack without such a tail, or a program before it)."""

from dtbench import scrapes

ROWS = "dynamo_prefill_cross_decoder_rows_total"


def read(ctx: dict):
    window = ctx["window"]
    earlier = scrapes.growth(window, ROWS, chunk="earlier")
    return scrapes.ratio(earlier, scrapes.growth(window, ROWS), 100.0)
