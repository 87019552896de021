"""Median time from a turn's due time to its first token (waiting turns at
their age at the window's end)."""
from bench.window import percentile

UNIT = "s"


def read(w):
    return percentile(w.ttft_samples(), 50)
