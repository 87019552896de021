"""Megabytes offloaded to and reloaded from the host tier per turn
finished in the window (router counters, wire bytes)."""
UNIT = "MB"


def read(w):
    turns = w.delta("steps_completed")
    moved = w.delta("offload_bytes") + w.delta("reload_bytes")
    return moved / 1e6 / turns if turns else None
