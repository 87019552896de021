"""Share of the prompt tokens of turns finished in the window that the
radix cache served (router counters)."""
UNIT = "%"


def read(w):
    hit, miss = w.delta("cached_tokens"), w.delta("prefilled_tokens")
    return 100.0 * hit / (hit + miss) if hit + miss else None
