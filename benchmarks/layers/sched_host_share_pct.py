"""Scheduler: the host's share of the steps' wall time, from the program's
own step attribution (`dynamo_step_host_ms` / `dynamo_step_device_ms`,
every phase), as the growth of the histograms' sums over the window.
"Device" there is the submit-to-drain window, which contains device
queueing: this is host-side attribution, never device busy time."""


def _sum(scrape: dict, family: str) -> float:
    return sum(v for _labels, v in scrape.get(family + "_sum", []))


def read(ctx: dict):
    before, after = ctx["window"]["before"], ctx["window"]["after"]
    host = (_sum(after, "dynamo_step_host_ms")
            - _sum(before, "dynamo_step_host_ms"))
    device = (_sum(after, "dynamo_step_device_ms")
              - _sum(before, "dynamo_step_device_ms"))
    if host + device <= 0:
        return None
    return 100.0 * host / (host + device)
