"""Arithmetic over two scrapes of the worker's `/metrics` page, for the
readers of counters and histograms that the program keeps itself
(`Fleet.scrape()`: {sample name: [(labels, value)]}). A family the
program does not have reads as None, never as zero and never as an
error: a reader then returns None and the line leaves its metric out."""

from __future__ import annotations

from typing import Optional


def total(scrape: dict, sample: str, **labels: str) -> Optional[float]:
    """Sum of the sample's rows whose labels include `labels`; None
    where no row does."""
    rows = [value for row, value in scrape.get(sample, [])
            if all(row.get(k) == v for k, v in labels.items())]
    return sum(rows) if rows else None


def growth(window: dict, sample: str, **labels: str) -> Optional[float]:
    """after - before over the window's two scrapes; None where the
    later scrape lacks the sample (a row that first appears inside the
    window started from nothing)."""
    after = total(window["after"], sample, **labels)
    if after is None:
        return None
    return after - (total(window["before"], sample, **labels) or 0.0)


def ratio(num: Optional[float], den: Optional[float],
          scale: float = 1.0) -> Optional[float]:
    """scale * num / den; None where either is missing or nothing grew."""
    if num is None or den is None or den <= 0:
        return None
    return scale * num / den


def step_wall_ms(window: dict) -> Optional[float]:
    """Growth of the committed steps' wall time, which the program sums
    itself (`dynamo_step_part_ms_total{part="wall"}`). Not the sums of
    `dynamo_step_host_ms` and `dynamo_step_device_ms`: a step's device
    windows are observed phase by phase and overlap (a deferred prefill
    readback rides the decode block's window), so together they read
    1.4 times the wall in m7b-w4kv8.chunk-sat (my chip run, PR 26)."""
    return growth(window, "dynamo_step_part_ms_total", part="wall")
