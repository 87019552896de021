"""Slots advanced per batched decode step (router counters, over the
window)."""
UNIT = "slots"


def read(w):
    steps = w.delta("pump_steps")
    return w.delta("sum_live_slots") / steps if steps else None
