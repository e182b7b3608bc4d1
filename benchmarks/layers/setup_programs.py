"""ModelRunner step (building programs): programs built before the
window opened (`dynamo_jit_compiles_total`, every entry point and
`unscoped`, at the window's start: a persistent-cache load counts too).
The sum of `backends` over `/debug/programs` at that moment. None where
the program has no such counter."""

from dtbench import scrapes


def read(ctx: dict):
    return scrapes.total(ctx["window"]["before"],
                         "dynamo_jit_compiles_total")
