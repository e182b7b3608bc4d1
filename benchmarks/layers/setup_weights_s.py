"""ModelRunner step (the worker's start): seconds the worker spent
resolving its weights and putting them and the pools on the device:
`dynamo_coldstart_phase_seconds`, phases `fetch` + `load`, less the
seconds of the `unscoped` builds (`dynamo_jit_stage_seconds_total{fn=
"unscoped"}`): the programs of engine construction (parameter init,
quantisation, the pools) are built inside `load` and the build readers
count them, 30-60 s of a cold start; what else compiles outside any
entry, a sliced token, is tens of milliseconds. From the scrape at the
window's start. None where the program has no ladder; a program from
before PR 40 reads its `fetch` + `load` whole."""

from dtbench import scrapes


def read(ctx: dict):
    held = ctx["layer"]("setup_boot_s").phase_seconds(ctx, "fetch", "load")
    if held is None:
        return None
    built = scrapes.total(ctx["window"]["before"],
                          "dynamo_jit_stage_seconds_total", fn="unscoped")
    return max(0.0, held - (built or 0.0))
