"""Page pool or state bank: mean share of the engine's slots that were
decoding, over the decode steps of the window
(``UtilizationMetrics.slot_samples``)."""

NAME, UNIT = "slot_occupancy", "%"


def read(ctx):
    s = ctx.slot_samples
    return 100.0 * sum(s) / len(s) if s else None
