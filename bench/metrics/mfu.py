"""Model operations of the tokens prefilled and generated in the traced
window over the window's length times the chip's bf16 peak: the whole
step's share of the chip, which bounds every kernel's gain."""
UNIT = "%"


def read(w):
    f = w.model_flops()
    if w.reduced is None or f <= 0:
        return None
    return 100.0 * f / (w.reduced.window_s * w.peaks["bf16_flops_per_s"])
