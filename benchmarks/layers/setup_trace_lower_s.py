"""ModelRunner step (building programs): seconds of tracing and of
jaxpr -> MLIR lowering since process start
(`dynamo_jit_stage_seconds_total`, stages `trace` + `lower`: paid at
every start, cache or no cache), from the scrape at the window's start.
None where the program does not split a build by stage."""


def read(ctx: dict):
    return ctx["layer"]("setup_compile_s").stage_seconds(
        ctx, "trace", "lower")
