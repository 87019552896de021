"""Seconds from the start of the process to the window's opening: start-up,
weights, pool sizing, compiles or cache loads, warm-up, and the ramp that
brings the session population to its steady state."""
UNIT = "s"


def read(w):
    return w.setup_s
