"""Scheduler thread (the worker's start): seconds from the worker
process's own start, as the kernel has it, to the first line of its
engine build (`dynamo_coldstart_phase_seconds{phase="boot"}`: the
interpreter, imports, JAX's start, the native build in a new checkout).
Read from the scrape at the window's start: everything since process
start, which is what `setup_s` spans. None where the ladder has no such
phase (a program from before PR 40)."""

from dtbench import scrapes

PHASES = "dynamo_coldstart_phase_seconds"


def phase_seconds(ctx: dict, *phases: str):
    """Sum of the ladder's named phases at the window's start; None
    where it holds none of them."""
    before = ctx["window"]["before"]
    held = [s for s in (scrapes.total(before, PHASES, phase=p)
                        for p in phases) if s is not None]
    return sum(held) if held else None


def read(ctx: dict):
    return phase_seconds(ctx, "boot")
