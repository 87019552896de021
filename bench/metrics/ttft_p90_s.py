"""90th percentile of the time from a turn's due time to its first token
(waiting turns at their age at the window's end). Its tail is set by the
decode pump's passes (a due turn waits for the next one) and by the
scheduler's gate; in a closed population it bounds the turn rate."""
from bench.window import percentile

UNIT = "s"


def read(w):
    return percentile(w.ttft_samples(), 90)
