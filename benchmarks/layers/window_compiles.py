"""ModelRunner: programs first run inside the window (growth of
`dynamo_jit_compiles_total`, all entry points; a persistent-cache load
counts too). 0 in a sound run: the warm-up enumerates the mix's shapes."""


def _total(scrape: dict) -> float:
    return sum(v for _labels, v in
               scrape.get("dynamo_jit_compiles_total", []))


def read(ctx: dict):
    window = ctx["window"]
    if "dynamo_jit_compiles_total" not in window["after"]:
        return None
    return _total(window["after"]) - _total(window["before"])
