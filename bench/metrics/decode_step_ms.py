"""Device milliseconds per run of the engine's jitted paged decode step:
the compiled modules that run the Pallas paged-attention kernel."""
UNIT = "ms"
KERNEL = "paged_attention"


def read(w):
    r = w.reduced
    runs = [m for m in r.modules if KERNEL in m.ops] if r is not None else []
    return 1e3 * sum(m.seconds for m in runs) / len(runs) if runs else None
