"""Seconds a released turn waited for a free engine slot, per turn admitted
in the window (router counter ``slot_wait_s`` on the wall clock)."""
UNIT = "s"


def read(w):
    n = sum(1 for t in w.admitted if w.inside(t))
    return w.delta("slot_wait_s") / n if n else None
