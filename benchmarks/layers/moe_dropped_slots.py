"""Expert layer: assignments to a held expert that were not computed
inside the window (growth of `dynamo_moe_dropped_slots_total`). 0 when
sound: the layer is dropless. None where the program keeps no such
counter."""

from dtbench import scrapes


def read(ctx: dict):
    return scrapes.growth(ctx["window"], "dynamo_moe_dropped_slots_total")
