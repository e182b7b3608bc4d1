"""ModelRunner step (building programs): seconds of backend steps the
persistent compile cache served since process start
(`dynamo_jit_stage_seconds_total{stage="cache_load"}`: reading and
deserialising an executable), from the scrape at the window's start. 0
in a cold start. None where the program does not split a build by
stage."""


def read(ctx: dict):
    return ctx["layer"]("setup_compile_s").stage_seconds(ctx, "cache_load")
