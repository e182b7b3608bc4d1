"""ModelRunner step: the prefill programs' share of all device time in
the capture (the compiled programs of `prefill_chunk` and
`prefill_chunk_batch`, which a real device trace prints as `jit_step`: my
chip run, PR 25; a configuration whose programs are named otherwise gives
a pattern in its own file, under `trace_names.prefill_modules`)."""

import re

PREFILL_MODULES = "^jit_step$"


def read(ctx: dict):
    modules = (ctx.get("trace") or {}).get("modules", {})
    total = sum(m["seconds"] for m in modules.values())
    if total <= 0:
        return None
    pattern = re.compile(ctx["config"].get("trace_names", {}).get(
        "prefill_modules", PREFILL_MODULES))
    prefill = sum(m["seconds"] for name, m in modules.items()
                  if pattern.search(name))
    return 100.0 * prefill / total
