"""Output tokens produced in the window, each counted at the step that
produced it, over the window's seconds."""
UNIT = "tokens/s"


def read(w):
    return w.tokens_in_window() / w.seconds
