"""Paged cache: share of the per-slot recurrent state that is live, as
the time-weighted mean over the window's committed steps: growth of
`dynamo_ssm_state_slot_ms` (scheduler slots held, each with one
fixed-size Mamba-2 state, x the step's wall ms) over the growth of the
steps' wall ms, over `serve.max_batch`. The state cache is sized for
every slot whatever the traffic; this is how much of it a step can use.
None where the program keeps no such counter."""

from dtbench import scrapes


def read(ctx: dict):
    window = ctx["window"]
    slots = scrapes.ratio(
        scrapes.growth(window, "dynamo_ssm_state_slot_ms"),
        scrapes.step_wall_ms(window))
    return scrapes.ratio(slots, ctx["config"]["serve"]["max_batch"], 100.0)
