"""ModelRunner step (building programs): seconds of backend compiles
since process start (`dynamo_jit_stage_seconds_total{stage="compile"}`,
every entry point), from the scrape at the window's start. Near 0 in a
warm start (what the persistent cache does not keep, the programs under
its minimum compile time, compile every time); most of a cold one. None
where the program does not split a build by stage (before PR 40); 0
where it does and nothing compiled."""

from dtbench import scrapes

STAGES = "dynamo_jit_stage_seconds_total"


def stage_seconds(ctx: dict, *stages: str):
    """Seconds of the named build stages at the window's start, all
    entry points; None where the family is absent."""
    before = ctx["window"]["before"]
    if STAGES not in before:
        return None
    return sum(scrapes.total(before, STAGES, stage=s) or 0.0
               for s in stages)


def read(ctx: dict):
    return stage_seconds(ctx, "compile")
