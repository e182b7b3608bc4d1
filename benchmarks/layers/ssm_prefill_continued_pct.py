"""ModelRunner step: the share of the prefill scan that ran on a CARRIED
state: growth of `dynamo_ssm_prefill_positions_total{carry="continued"}`
(valid positions x Mamba layers of rows that took up the state their
slot kept from the launch before) over the growth of both carries. 0
where every prompt fits one launch; a prompt of 3,072 tokens under a
2,048-token budget reads 33. None where the program keeps no such
counter (a model without Mamba layers, or a program before it)."""

from dtbench import scrapes

POSITIONS = "dynamo_ssm_prefill_positions_total"


def read(ctx: dict):
    window = ctx["window"]
    continued = scrapes.growth(window, POSITIONS, carry="continued")
    return scrapes.ratio(continued, scrapes.growth(window, POSITIONS), 100.0)
