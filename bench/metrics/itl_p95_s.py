"""95th percentile of the gaps between consecutive output tokens of one
turn, as the host sees them."""
from bench.window import percentile

UNIT = "s"


def read(w):
    return percentile(w.token_gaps(), 95)
