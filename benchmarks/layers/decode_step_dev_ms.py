"""ModelRunner step: device milliseconds of one decode step, from the
trace: the mean device duration of one launch of the fused decode block
(`decode_multi`) over the steps it fuses (`serve.decode_block`, which the
harness hands the worker as DYNT_DECODE_BLOCK).

The compiled program is found by the name a real device trace prints
(`jit_multi`: my chip run, PR 25); a configuration whose program is named
otherwise says so in its own file, under `trace_names.decode_module`."""

DECODE_MODULE = "jit_multi"


def launches(ctx: dict):
    """(launches of the fused decode block, their device seconds, steps
    fused per launch), or None where the trace holds none."""
    name = ctx["config"].get("trace_names", {}).get(
        "decode_module", DECODE_MODULE)
    row = (ctx.get("trace") or {}).get("modules", {}).get(name)
    if not row or not row["count"]:
        return None
    return row["count"], row["seconds"], int(
        ctx["config"]["serve"].get("decode_block", 8))


def read(ctx: dict):
    found = launches(ctx)
    if found is None:
        return None
    count, seconds, fused = found
    return seconds / count / fused * 1e3
