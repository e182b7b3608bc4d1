"""Paged cache / scheduler: sequences preempted inside the window (growth
of `dynamo_preempt_total`, every kind)."""


def _total(scrape: dict) -> float:
    return sum(v for _labels, v in scrape.get("dynamo_preempt_total", []))


def read(ctx: dict):
    window = ctx["window"]
    return _total(window["after"]) - _total(window["before"])
